"""The parameter server: live params + aggregation policy under a lock.

The server owns the one mutable copy of the parameters — as a flat
**gradient slab** (:mod:`repro.core.slab`) — and reuses the repo's
aggregation policies (a :class:`repro.core.schedule.ThresholdSchedule`
K(t)) against real concurrent workers:

  * ``async``  — K(t) ≡ 1: every ingested gradient is applied at once;
  * ``hybrid`` — gradients buffer until |buffer| >= K(version), then
    flush as one update (Smooth Switch);
  * ``sync``   — a barrier round: one gradient from every *live* worker
    at the current version, aggregated in worker-id order (which makes
    the policy bitwise-reproducible), applied as their mean.  Gradients
    from an older version (e.g. a worker that died mid-round and came
    back) are dropped and accounted.

The aggregation hot path is the slab path end-to-end: workers ship
``(P,)`` gradient slabs (see :class:`~repro.cluster.transport.
GradientMsg`), the server holds each in one of ``K_max`` staging slots
— by reference when it is already on the device in the staging dtype,
else after the one transfer or cast it needs (counted as
``stage.held`` / ``stage.copied``) — and **one** jitted, donated
executable (:class:`repro.core.slab.SlabAggregator`) applies every
flush over the held rows — any buffer size K, any fleet size, one
compile.  The pre-slab server
compiled ``num_workers`` separate executables at startup and copied the
full params pytree on every update; both costs are gone (the startup
probe in ``tests/test_slab.py`` pins the executable count to 1).

Donation rule: the params slab is updated *in place*, so nothing that
escapes the server may alias it.  Workers receive the published copy
the flush executable emits; :meth:`snapshot` decodes **and copies to
host** under the lock — a checkpoint that held a live reference would
be silently corrupted by the next flush.

Every mutation happens under ``self.lock``; membership changes
(kill/respawn) re-check the sync barrier so a shrinking fleet cannot
deadlock a round.  Exact accounting — ``applied`` / ``dropped``
gradients and ``version`` (= updates) — is what
``RunResult.num_gradients`` reports, to the gradient.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Set

import numpy as np

from repro.core.slab import SlabAggregator, SlabBuffer, slab_codec
from repro.core.schedule import ThresholdSchedule
from repro.cluster.transport import GradientMsg, ParamsMsg, Transport
from repro.obs.telemetry import NULL
from repro.optim.slab_form import SlabOptimizer


class ParameterServer:
    def __init__(self, params, *, lr: float, mode: str,
                 transport: Transport, num_workers: int,
                 schedule: Optional[ThresholdSchedule] = None,
                 flush_mode: str = "sum", staleness_decay: float = 1.0,
                 max_gradients: Optional[int] = None,
                 start_version: int = 0,
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False,
                 slab_dtype: str = "f32",
                 optimizer: Optional[SlabOptimizer] = None,
                 obs=None):
        assert mode in ("sync", "async", "hybrid")
        assert flush_mode in ("sum", "mean")
        if mode in ("async", "hybrid"):
            assert schedule is not None, f"{mode} mode needs a K(t) schedule"
        self.lock = threading.RLock()
        self.obs = obs if obs is not None else NULL
        self._last_k: Optional[int] = None  # K(t) switch detection
        self.version = int(start_version)   # parameter updates applied
        self.start_version = int(start_version)
        self.mode = mode
        self.lr = lr
        self.schedule = schedule
        self.flush_mode = flush_mode
        self.staleness_decay = staleness_decay
        self.max_gradients = max_gradients
        self.transport = transport
        # a flush aggregates at most one gradient per worker — except
        # async, where the policy is K ≡ 1 *by definition* (the
        # schedule is ignored; see _ingest_buffered), pinning the
        # staging to one slot.  For hybrid, a schedule built for a
        # larger fleet can demand K > num_workers, so the staging slots
        # cover the schedule's own ceiling too.
        if mode == "async":
            k_max = 1
        else:
            k_max = max(1, num_workers,
                        schedule.num_workers if schedule else 0)
        # slab_dtype is the declared aggregation/wire dtype: staging
        # rows, the published slab, and every frame on the transport
        # carry it, while the master params slab and the flush
        # reduction stay f32 (see repro.core.slab)
        self.codec = slab_codec(params, slab_dtype)
        # the optimizer lives on the slab: moments (if any) are f32
        # slab-shaped buffers inside the aggregator, applied by the same
        # fused executable as the aggregation itself
        self.optimizer = optimizer or SlabOptimizer("sgd")
        self.agg = SlabAggregator(self.codec, params, k_max,
                                  use_pallas=use_pallas,
                                  interpret=interpret,
                                  optimizer=self.optimizer)
        # compile the flush executable before the clock starts
        # (compiling mid-run would stall the whole fleet under the
        # server lock) — one compile, for any fleet size
        self.agg.warmup()
        self.buffer = SlabBuffer(self.agg, staleness_decay)
        self.applied = 0                    # gradients folded into updates
        self.dropped = 0                    # stale / discarded gradients
        self.updates_applied = 0            # _apply calls (never rolled
        #                                     back, unlike version)
        self.restore_epoch = 0              # bumped per restore(); rides
        #                                     on ParamsMsg so workers can
        #                                     tell a restore from a slow
        #                                     round (see ParamsMsg.epoch)
        # membership starts empty: workers register as they spawn
        # (num_workers is the fleet size = the staging slots' K_max)
        self.live: Set[int] = set()
        self._round: Dict[int, Any] = {}    # sync: worker_id -> grad slab
        self.done = threading.Event()       # max_gradients budget reached
        # time of the last publish of an update, or of the window's
        # start before the first (publish_gap_s); None until
        # start_clock, so set-up's publishes are never counted
        self._last_publish_t: Optional[float] = None
        transport.publish_params(ParamsMsg(self.version,
                                           self.agg.params_slab))

    # ------------------------------------------------------- membership
    def grow_fleet(self, num_workers: int,
                   schedule: Optional[ThresholdSchedule] = None) -> None:
        """Admit a fleet larger than construction time planned for
        (elastic membership): lengthen the staging slots to cover
        ``num_workers`` simultaneous contributions and, when a
        re-derived K(t) ``schedule`` for the new fleet size is handed
        in, swap it in atomically with the resize.  Must run *before*
        :meth:`register` for any worker id beyond the old ceiling — a
        sync round stages one row per live worker, so staging must
        already cover the grown fleet when the barrier fills.  Exact
        accounting is untouched: staged rows are preserved by
        :meth:`repro.core.slab.SlabAggregator.grow` and the host-side
        version list never moves."""
        with self.lock:
            if schedule is not None:
                self.schedule = schedule
            if self.mode == "async":
                k_max = 1       # K ≡ 1 by definition: one row, any fleet
            else:
                k_max = max(1, int(num_workers),
                            self.schedule.num_workers
                            if self.schedule else 0)
            self.agg.grow(k_max)

    def register(self, worker_id: int) -> None:
        with self.lock:
            self.live.add(worker_id)

    def deregister(self, worker_id: int) -> None:
        with self.lock:
            self.live.discard(worker_id)
            if self.mode == "sync":
                # a shrinking fleet may complete the round it was blocking
                self._maybe_complete_round()

    def start_clock(self, t: float) -> None:
        """The serving window opened at ``t`` (``time.monotonic()``):
        the first ``publish_gap_s`` sample runs from here."""
        with self.lock:
            self._last_publish_t = t

    # ---------------------------------------------------------- ingest
    def ingest(self, msg: GradientMsg) -> None:
        # the span covers the wait for the lock too (a snapshot or a
        # membership change holds it)
        with self.obs.span("server", "ingest", worker=msg.worker_id,
                           seq=msg.seq, version=msg.version), self.lock:
            # telemetry: every gradient that reached the server, and
            # how stale it was on arrival (server version minus the
            # version it was computed against; negative after a restore
            # rolled the clock back).  The ledger cross-check is
            # grads_ingested == applied + dropped + buffered + pending
            self.obs.count("grads_ingested")
            self.obs.count(f"grads_ingested.w{msg.worker_id}")
            self.obs.observe("staleness", self.version - msg.version)
            if self.done.is_set():
                self.dropped += 1
                self.obs.count("drops.budget")
                return
            if self.mode == "sync":
                self._ingest_sync(msg)
            else:
                self._ingest_buffered(msg)

    def _ingest_sync(self, msg: GradientMsg) -> None:
        if msg.version != self.version:
            self.dropped += 1       # late arrival from a previous round
            self.obs.count("drops.stale")
            return
        if msg.worker_id in self._round:
            # a worker re-contributing to an in-progress round (it can,
            # legitimately, after a restore rolled the version back
            # while it was waiting): latest wins, the overwritten
            # gradient is accounted as dropped
            self.dropped += 1
            self.obs.count("drops.duplicate")
        self._round[msg.worker_id] = msg.grad
        self._maybe_complete_round()

    def _maybe_complete_round(self) -> None:
        if not self.live or not set(self._round) >= self.live:
            return
        wids = sorted(self._round)          # deterministic fold order
        with self.obs.span("server", "stage_dispatch", k=len(wids)):
            for slot, w in enumerate(wids):
                self._count_stage(self.agg.stage(self._round[w], slot))
        k = len(wids)
        self._round = {}
        # sync: the plain mean of the round's gradients
        self._apply(np.ones((k,)), self.lr)

    def _ingest_buffered(self, msg: GradientMsg) -> None:
        with self.obs.span("server", "stage_dispatch", k=1):
            self._count_stage(self.buffer.add(msg.grad, msg.version))
        # async is K ≡ 1 by definition (its one-slot staging
        # depends on it); hybrid asks the K(t) schedule
        k_needed = 1 if self.mode == "async" else \
            self.schedule(self.version)
        if k_needed != self._last_k:
            # the paper's async→sync handoff, as a timeline event
            if self._last_k is not None:
                self.obs.count("k_switches")
                self.obs.instant("server", "k_switch", k=k_needed,
                                 version=self.version)
            self._last_k = k_needed
        if len(self.buffer) >= k_needed:
            weights = self.buffer.weights(self.version)
            k = len(self.buffer)
            self.buffer.clear()
            # "sum" applies every buffered gradient at full lr (the
            # paper's Algorithm 1; K=1 ≡ async exactly); "mean" is the
            # sync-style confident update — both are one fused scale
            scale = self.lr * k if self.flush_mode == "sum" else self.lr
            self._apply(weights, scale)

    def _count_stage(self, held: bool) -> None:
        # how often staging holds the gradient as is, and how often it
        # pays a transfer or cast (a socket transport's host rows)
        self.obs.count("stage.held" if held else "stage.copied")

    def _apply(self, weights: np.ndarray, scale: float) -> None:
        # host dispatch of the fused flush + optimizer step: the device
        # time is in a profile of the run (the span's annotation puts
        # it on the same clock), not in this histogram
        with self.obs.span("server", "flush_dispatch",
                           hist="flush_dispatch_s", k=len(weights),
                           version=self.version + 1):
            pub = self.agg.flush_apply(weights, scale)
        self.version += 1
        self.updates_applied += 1
        self.applied += len(weights)
        self.obs.count("optimizer_steps")
        self.obs.count("grads_applied", len(weights))
        self.obs.count("updates")
        with self.obs.span("server", "publish", hist="publish_s",
                           version=self.version):
            self.transport.publish_params(
                ParamsMsg(self.version, pub, epoch=self.restore_epoch))
        self.obs.count("params_published")
        if self._last_publish_t is not None:
            now = time.monotonic()
            self.obs.observe("publish_gap_s", now - self._last_publish_t)
            self._last_publish_t = now
        if self.max_gradients and self.applied >= self.max_gradients:
            self.done.set()

    # ----------------------------------------------- snapshot / restore
    def snapshot(self):
        """(version, params, applied) — params is a **host copy** of the
        decoded tree: with a donated params slab a live reference to the
        server's internals would be invalidated by the next flush.  Only
        the published-slab grab needs the lock (it is a fresh,
        never-donated executable output); the decode + host copy happens
        outside it, so samplers/checkpointers never stall ingest on the
        serial resource they are measuring."""
        with self.lock:
            version, pub, applied = (self.version, self.agg.params_slab,
                                     self.applied)
        return version, self.codec.decode_host(pub), applied

    def snapshot_slab(self):
        """(version, params_slab, applied) — the *published* params
        slab, which by the donation contract is a fresh executable
        output that stays valid forever.  The zero-work snapshot for
        in-run samplers: decode after the run, off the hot path."""
        with self.lock:
            return self.version, self.agg.params_slab, self.applied

    def snapshot_for_checkpoint(self):
        """(version, params, applied, opt_state) with params and the
        optimizer moments captured under **one** lock acquisition — a
        flush landing between two separate snapshots would persist
        moments one step ahead of the params they belong to.  The
        moment copy runs under the lock (donation rule); the params
        decode + host copy happens outside it, like :meth:`snapshot`."""
        with self.lock:
            version, pub, applied = (self.version, self.agg.params_slab,
                                     self.applied)
            opt_state = self.agg.opt_state_host()
        return version, self.codec.decode_host(pub), applied, opt_state

    def snapshot_opt_state(self):
        """Host copies of the optimizer's moment slabs + update count
        (``None`` for sgd).  The whole copy runs **under the lock**, per
        the donation rule: the moments are donated buffers, and a
        concurrent flush would invalidate them mid-copy — unlike the
        published params slab, there is no fresh-output shortcut."""
        with self.lock:
            return self.agg.opt_state_host()

    def restore(self, params, step: int, opt_state=None) -> None:
        """Restore-into-running-server: replace the live params and
        version (so K(t) continues from ``step``), discarding any
        in-buffer or mid-round gradients (they were computed against a
        history that no longer exists — and are *dropped*, not just
        masked, because a diverged non-finite gradient would poison
        later flushes through ``0 · inf = nan``)."""
        with self.lock:
            lost = len(self.buffer) + len(self._round)
            self.dropped += lost
            self.obs.count("drops.restore", lost)
            self.obs.count("restores")
            self.obs.instant("server", "restore", step=int(step),
                             lost=lost)
            self.buffer.discard()
            self._round = {}
            self.agg.reset_params(params)
            # moments resync with the same epoch bump: either the
            # checkpointed slabs + count, or zeros — stale moments
            # against restored params would re-apply abandoned history
            self.agg.reset_opt_state(opt_state)
            self.version = int(step)
            # the epoch bump is what tells a sync worker "this is a
            # restore, recontribute" — the version alone can look like
            # an ordinary not-yet-finished round
            self.restore_epoch += 1
            self.transport.publish_params(
                ParamsMsg(self.version, self.agg.params_slab,
                          epoch=self.restore_epoch))

    def accounting(self) -> Dict[str, int]:
        with self.lock:
            # "updates" counts _apply calls: a mid-run restore rolls
            # version backwards but not the work actually done, so this
            # stays consistent with the applied-gradient counter
            return {"applied": self.applied, "dropped": self.dropped,
                    "buffered": len(self.buffer),
                    "pending_round": len(self._round),
                    "updates": self.updates_applied}
