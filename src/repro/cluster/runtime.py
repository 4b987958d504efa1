"""The wall-clock cluster runtime: server + workers + faults + metrics.

:class:`ClusterRuntime` wires one :class:`~repro.cluster.server.
ParameterServer`, a worker fleet, a transport, and the
:class:`~repro.cluster.faults.FaultPlan` injector, then runs until a
wall-clock budget elapses or an applied-gradient budget is hit.

Four transports (``transport_kind``, = ``ExperimentSpec.transport``):

  * ``inproc`` — worker *threads* + an in-process queue (default; the
    parity baseline).  Gradient compute shares one GIL/JAX runtime;
  * ``socket`` — worker threads, but every message crosses a real TCP
    socket as a length-prefixed slab frame (the wire format is
    physical; the address space is still shared);
  * ``proc``   — one OS *process* per worker over Unix-domain sockets
    (:mod:`repro.cluster.mptransport`): each worker has its own JAX
    runtime, FaultPlan kills are SIGKILL, and the fleet-ready barrier
    starts the clock only after every child has compiled and connected
    (so the budget measures contention, not XLA).  Requires
    ``spec_dict`` — worker processes rebuild the workload from the
    experiment spec via the ``SIM_WORKLOADS`` registry;
  * ``host``   — the multi-host mode (:mod:`repro.cluster.hostlink`):
    the server binds ``listen`` (``HOST:PORT``) and *waits* for remote
    workers to join via ``python -m repro join HOST:PORT`` — the spec
    travels to them in the leader handshake, worker ids are leased
    (with generation fencing), and the fleet-ready barrier is "every
    expected worker has joined".  Kill faults cut the worker's
    connection (the leader cannot SIGKILL a remote process); respawns
    are rejected — replacement capacity rejoins from its own host.

Pieces that run concurrently with training:

  * **metric sampler** — snapshots the live params on a fixed wall-clock
    grid.  It holds the *published* params slab — by the donation
    contract a fresh, never-donated executable output, so the reference
    costs nothing and stays valid — and all decoding plus loss/accuracy
    evaluation happens *after* the run, so measurement never perturbs
    the contention being measured;
  * **fault injector** — kills workers at their planned times (and
    deregisters them so a sync barrier cannot deadlock on the dead),
    respawning them after ``respawn_after_s`` with a fresh data-stream
    generation;
  * **checkpointer** — saves the server state via :mod:`repro.checkpoint`
    on a cadence, and optionally restores the latest checkpoint mid-run
    (``restore_at_s``, simulated server recovery).

Everything blocking takes a timeout and every thread watches a stop
event, so a wedged run degrades to "budget elapses, run ends" rather
than a hang.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import (latest_step, load_opt_state,
                              restore_checkpoint, save_checkpoint)
from repro.cluster.faults import FaultPlan
from repro.cluster.mptransport import (ProcTransport, ProcWorkerConfig,
                                       SocketTransport,
                                       worker_process_platform)
from repro.cluster.server import ParameterServer
from repro.cluster.transport import TRANSPORTS, InProcTransport, Transport
from repro.cluster.worker import Worker
from repro.core.schedule import ThresholdSchedule, constant_schedule
from repro.core.slab import slab_codec
from repro.data.pipeline import shard_iterator
from repro.obs.telemetry import Telemetry
from repro.optim.slab_form import SlabOptimizer

_log = logging.getLogger("repro.cluster.runtime")


@dataclasses.dataclass
class ClusterResult:
    """What one cluster run produced (adapted into ``RunResult`` by
    :class:`repro.cluster.trainer.ClusterTrainer`)."""
    times: np.ndarray            # wall-clock metric grid (seconds)
    train_loss: np.ndarray
    test_loss: np.ndarray
    test_acc: np.ndarray
    num_updates: int             # parameter updates applied this run
    num_gradients: int           # == the server's applied counter, exactly
    mode: str
    start_version: int           # >0 when resumed from a checkpoint
    accounting: Dict[str, int]   # applied/dropped/buffered/... + computed
    events: List[Dict[str, Any]]   # kills, respawns, checkpoints, restores
    final_params: Any
    wall_s: float
    # serving plane: per-serve-client push stats.  Always a dict on the
    # cluster backend (empty-shaped when the transport has no serving
    # plane), so consumers key on content, not key presence
    serving: Optional[Dict[str, Any]] = None
    # where the work ran: the server's platform and device kind, which
    # flush path its aggregator took, and the platform each worker id
    # computed its gradients on ("unreported" for joined hosts)
    placement: Optional[Dict[str, Any]] = None
    setup_s: float = 0.0         # pre-clock compile + warm-up seconds
    # telemetry plane: the obs summary (counters / gauges / histograms)
    # plus a ledger_check block cross-checking the telemetry counters
    # against the conservation ledger
    telemetry: Optional[Dict[str, Any]] = None


class ClusterRuntime:
    """One wall-clock parameter-server training run."""

    def __init__(self, loss_fn: Callable, init_params, data, *,
                 mode: str, lr: float = 0.01, batch: int = 32,
                 num_workers: int = 4, wall_budget_s: float = 5.0,
                 sample_every_s: float = 0.25,
                 schedule: Optional[ThresholdSchedule] = None,
                 flush_mode: str = "sum", staleness_decay: float = 1.0,
                 max_gradients: Optional[int] = None, seed: int = 0,
                 faults: FaultPlan = FaultPlan(),
                 accuracy_fn: Optional[Callable] = None,
                 transport: Optional[Transport] = None,
                 transport_kind: str = "inproc",
                 spec_dict: Optional[Dict[str, Any]] = None,
                 listen: Optional[str] = None,
                 heartbeat_s: float = 2.0, serve_every: int = 1,
                 max_workers: Optional[int] = None,
                 join_secret: Optional[str] = None,
                 lease_grace_s: float = 2.0,
                 slab_dtype: str = "f32",
                 optimizer: Optional[SlabOptimizer] = None,
                 proc_ready_timeout_s: float = 180.0,
                 verbose: bool = False,
                 ckpt_dir: Optional[str] = None,
                 resume_from: Optional[str] = None,
                 trace: Optional[str] = None,
                 prom_port: Optional[int] = None):
        assert mode in ("sync", "async", "hybrid")
        if transport_kind not in TRANSPORTS:
            raise ValueError(f"transport_kind must be one of {TRANSPORTS},"
                             f" got {transport_kind!r}")
        if transport_kind == "proc" and spec_dict is None:
            raise ValueError(
                'transport_kind="proc" needs spec_dict (an ExperimentSpec'
                " dict): worker processes rebuild the workload from it "
                "via the SIM_WORKLOADS registry — run through "
                'ClusterTrainer / repro.api.run(spec) with '
                'spec.transport="proc"')
        if transport_kind == "host" and spec_dict is None \
                and transport is None:
            raise ValueError(
                'transport_kind="host" needs spec_dict (an ExperimentSpec'
                " dict): it is what joining hosts receive in the leader "
                "handshake and rebuild their workload from — run through "
                'ClusterTrainer / repro.api.run(spec) with '
                'spec.transport="host"')
        if transport_kind == "host" and faults.respawn_after_s > 0:
            raise ValueError(
                "the host transport cannot respawn remote workers (the "
                "leader does not own the remote machine) — drop "
                "respawn_after_s and rejoin replacement capacity with "
                "`python -m repro join` instead")
        if mode == "async":
            schedule = constant_schedule(num_workers, 1)
        if mode == "hybrid":
            assert schedule is not None, "hybrid mode needs a schedule"
        # elastic admission is a host-transport feature: the other
        # transports own their whole fleet at construction time
        if max_workers is not None and transport_kind != "host":
            raise ValueError(
                "max_workers (elastic admission) requires "
                'transport_kind="host" — the other transports spawn '
                "their entire fleet up front")
        self.max_workers = max(num_workers, int(max_workers
                                                or num_workers))
        # faults may target any admissible worker id, including elastic
        # ones that have not joined yet (a kill aimed at an absent
        # worker just finds nobody)
        faults.validate_worker_ids(self.max_workers)
        if (faults.checkpoint_every_s > 0 or faults.restore_at_s > 0) \
                and not ckpt_dir:
            raise ValueError(
                "FaultPlan requests checkpointing "
                f"(checkpoint_every_s={faults.checkpoint_every_s}, "
                f"restore_at_s={faults.restore_at_s}) but no ckpt_dir "
                "was given — pass --ckpt-dir / ClusterTrainer(ckpt_dir=)")
        # every metric snapshot holds a full parameter pytree until the
        # post-run evaluation; bound the count so a long budget with a
        # fine grid fails loudly instead of exhausting host memory
        if wall_budget_s / sample_every_s > 4096:
            raise ValueError(
                f"wall_budget_s/sample_every_s = "
                f"{wall_budget_s / sample_every_s:.0f} metric snapshots "
                "(> 4096), each retaining a full parameter copy — "
                "increase sample_every_s")
        self.loss_fn = loss_fn
        self.init_params = init_params
        self.x_tr, self.y_tr, self.x_te, self.y_te = data
        self.mode = mode
        self.lr = lr
        self.batch = batch
        self.num_workers = num_workers
        # the *current* fleet size: seeded at num_workers, grown by
        # online admission up to max_workers (host transport only).
        # K(t) schedules and the staging buffer re-derive from it
        self.fleet_size = num_workers
        self._fleet_lock = threading.Lock()
        self.wall_budget_s = wall_budget_s
        self.sample_every_s = sample_every_s
        self.schedule = schedule
        self.flush_mode = flush_mode
        self.staleness_decay = staleness_decay
        self.max_gradients = max_gradients
        self.seed = seed
        self.faults = faults
        self.transport_kind = transport_kind
        self.spec_dict = spec_dict
        self.proc_ready_timeout_s = proc_ready_timeout_s
        self.ckpt_dir = ckpt_dir
        self.resume_from = resume_from
        self.verbose = verbose
        # the telemetry bus: metrics always on (lock-cheap counters /
        # histograms), timeline spans only when a trace file was asked
        # for.  trace is the output path (written by the trainer after
        # the run), not a spec field — tracing is a run artifact, like
        # --out, and must not perturb spec round-trips over the wire
        self.trace_path = trace
        self.obs = Telemetry(trace=bool(trace))
        # --prom-port: a Prometheus /metrics endpoint over the live
        # stats payload — an invocation artifact like trace/ckpt_dir,
        # never a spec field (started in _run, closed with the run)
        self.prom_port = prom_port
        self.prom_server = None

        # the slab wire format: workers fetch a params *slab*, decode,
        # differentiate, and re-encode the gradient — all in one jitted
        # executable, so each gradient ships as a single contiguous
        # (P,) array and is flattened exactly once, on the worker.
        # slab_dtype declares the staging/wire precision (f32 | bf16);
        # the server's master params and flush reduction stay f32
        self.slab_dtype = str(slab_dtype)
        # the server-side optimizer: moments live as f32 slab buffers
        # inside the aggregator's fused flush executable (see
        # repro.core.slab); "sgd" is the historical flush, bit for bit
        self.optimizer = optimizer or SlabOptimizer("sgd")
        self.codec = slab_codec(init_params, self.slab_dtype)
        grad_fn = jax.grad(loss_fn)

        def _grad_slab(p_slab, x, y):
            # named stages: a profile's op names say which part of the
            # fused executable an op belongs to
            with jax.named_scope("decode"):
                params = self.codec.decode(p_slab)
            with jax.named_scope("loss_grad"):
                grads = grad_fn(params, x, y)
            with jax.named_scope("encode"):
                return self.codec.encode(grads)

        self._grad = jax.jit(_grad_slab)
        self._loss = jax.jit(loss_fn)
        self._acc = accuracy_fn

        # bounded gradient channel = backpressure: a worker whose
        # gradient the server can't take yet blocks — on a queue for
        # thread workers, on real socket flow control otherwise.
        # Constructed LAST: everything above can raise (e.g. the codec
        # rejecting a leaf dtype), and a socket transport created
        # before a failed validation would leak its listener/threads
        cap = max(4, 2 * num_workers)
        self._own_transport = transport is None
        if transport is not None:
            self.transport = transport
        elif transport_kind == "socket":
            self.transport = SocketTransport(cap, family="tcp",
                                             slab_dtype=self.slab_dtype)
        elif transport_kind == "proc":
            self.transport = ProcTransport(cap, family="unix",
                                           slab_dtype=self.slab_dtype)
        elif transport_kind == "host":
            from repro.cluster.hostlink import (HostTransport,
                                                parse_hostport)
            bind_host, bind_port = parse_hostport(listen
                                                  or "127.0.0.1:0")
            self.transport = HostTransport(
                cap, host=bind_host, port=bind_port,
                num_workers=num_workers,
                welcome_config={"spec": spec_dict},
                heartbeat_s=heartbeat_s, serve_every=serve_every,
                max_workers=self.max_workers,
                join_secret=join_secret,
                lease_grace_s=lease_grace_s,
                slab_dtype=self.slab_dtype)
        else:
            self.transport = InProcTransport(grad_capacity=cap)
        # hand the transport the live bus (the in-process queue's
        # grad_queue_s; the socket hubs' wire byte counters, grad_rx
        # spans and STATS push plane)
        self.transport.obs = self.obs
        # the resolved bind address (host transport): port 0 in `listen`
        # has been replaced by the real ephemeral port by now
        self.listen_address: Optional[Any] = \
            tuple(self.transport.address) \
            if transport_kind == "host" else None

        self._stop = threading.Event()
        self._workers: Dict[int, Worker] = {}
        self._all_workers: List[Worker] = []
        self._generation: Dict[int, int] = {}
        self._worker_platforms: Dict[int, str] = {}
        # the platform joined (host) workers compute on, where the
        # caller launched them itself (``spawn_join_process(platform=)``);
        # None: a joined host picks its own and the wire does not carry it
        self.join_platform: Optional[str] = None
        self.events: List[Dict[str, Any]] = []
        self._control_errors: List[str] = []
        self._t0 = 0.0

    def _guarded(self, fn: Callable, name: str) -> threading.Thread:
        """Control thread whose failure is captured and re-raised by
        ``run()`` — a dead checkpointer/injector means the fault plan
        was not executed, which must not look like a clean run."""
        def body():
            try:
                fn()
            except Exception:
                import traceback
                self._control_errors.append(
                    f"{name}:\n{traceback.format_exc()}")
        return threading.Thread(target=body, name=name, daemon=True)

    # ------------------------------------------------------------ hooks
    def _elapsed(self) -> float:
        return time.monotonic() - self._t0

    def _log_event(self, kind: str, **kw) -> None:
        ev = {"t": round(self._elapsed(), 3), "event": kind, **kw}
        self.events.append(ev)
        # every fault/lifecycle event is also a timeline instant (the
        # trace shows kills/restores against the spans they perturb)
        # and a structured log record
        self.obs.instant("server", kind, **kw)
        self.obs.count(f"events.{kind}")
        _log.info("+%.2fs %s %s", ev["t"], kind, kw)
        if self.verbose:
            print(f"[cluster +{ev['t']:6.2f}s] {kind} "
                  f"{ {k: v for k, v in kw.items()} }", flush=True)

    def _spawn(self, wid: int) -> None:
        gen = self._generation.get(wid, -1) + 1
        self._generation[wid] = gen
        if self.transport_kind == "proc":
            # membership is driven by the connection, not the spawn:
            # the hub's on_worker_ready hook registers this worker when
            # its HELLO arrives (after its JAX runtime is warm).  A
            # sync barrier must not wait ~seconds of child startup for
            # a worker that cannot yet contribute — an inproc respawn
            # is instant, and a real cluster's barrier also only counts
            # nodes that have joined
            platform = worker_process_platform()
            self.transport.spawn_worker(ProcWorkerConfig(
                spec=self.spec_dict, worker_id=wid, generation=gen,
                num_workers=self.num_workers, mode=self.mode,
                straggle_s=self.faults.straggle_s(wid), seed=self.seed,
                batch=self.batch, platform=platform))
            self._worker_platforms[wid] = platform
            return
        self._worker_platforms[wid] = jax.default_backend()
        batches = shard_iterator(self.x_tr, self.y_tr, wid,
                                 self.num_workers, self.batch,
                                 seed=self.seed, generation=gen)
        wtrans: Any = self.transport
        if self.transport_kind == "socket":
            wtrans = self.transport.connect(wid, gen)
        w = Worker(wid, grad_fn=self._grad, batches=batches,
                   transport=wtrans, mode=self.mode,
                   straggle_s=self.faults.straggle_s(wid),
                   generation=gen, obs=self.obs)
        if wtrans is not self.transport:
            w.endpoint = wtrans     # flushed + closed at shutdown
            # a dead connection must stop the worker (not leave it
            # spinning on instant-False sends); conversely kill/
            # shutdown setting the stop event wakes the endpoint waits
            w.stop_event = wtrans.closed
        self._workers[wid] = w
        self._all_workers.append(w)
        self.server.register(wid)
        w.start()

    def _grow_fleet_to(self, n: int) -> None:
        """Online admission: a joiner beyond the current fleet size
        grows the server's staging buffer and re-derives the K(t)
        schedule for the new effective fleet — *before* the worker
        registers, so a sync barrier that fills immediately already has
        a staging row for every live member.  The conservation ledger
        is untouched (the resize preserves staged rows and the host-
        side counters never move)."""
        with self._fleet_lock:
            if n <= self.fleet_size:
                return
            old = self.fleet_size
            schedule = None
            if self.mode == "async":
                schedule = constant_schedule(n, 1)
            elif self.mode == "hybrid" and self.spec_dict \
                    and self.spec_dict.get("schedule"):
                from repro.api.schedules import parse_schedule
                schedule = parse_schedule(self.spec_dict["schedule"], n)
            self.server.grow_fleet(n, schedule)
            self.fleet_size = n
        self.obs.gauge("fleet_size", n)
        self.obs.count("members.admitted_beyond_seed", n - old)
        self._log_event("fleet_grow", from_workers=old, to_workers=n)

    def _on_remote_ready(self, wid: int, gen: int) -> None:
        # hub reader thread: a worker finished connecting.  For spawned
        # (proc) workers, guard on the exact generation so an orphan
        # HELLO from a superseded process cannot re-register a worker
        # the injector killed.  For joined (host) workers the transport
        # leases generations itself — any *newer* generation is the
        # legitimate holder of the worker id's shard
        if self.transport_kind == "host":
            if gen >= self._generation.get(wid, -1):
                self._worker_platforms[wid] = (self.join_platform
                                               or "unreported")
                # grow BEFORE register: the staging buffer must cover
                # the live fleet when this worker's first sync round
                # fills
                self._grow_fleet_to(wid + 1)
                self._generation[wid] = gen
                self.server.register(wid)
                self.obs.count("members.joined")
                self.obs.gauge("live_workers", len(self.server.live))
                self._log_event("member_join", worker=wid,
                                generation=gen)
            return
        if self._generation.get(wid) == gen:
            self.server.register(wid)

    def _on_remote_gone(self, wid: int, gen: int) -> None:
        # hub reader thread: a worker's connection died (kill, crash,
        # shutdown).  Deregistering here (idempotent) closes the race
        # where a HELLO lands between the injector's kill and the
        # process actually dying — a registered-but-dead worker would
        # stall every later sync round
        if self._generation.get(wid) == gen:
            self.server.deregister(wid)
            if self.transport_kind == "host":
                self.obs.count("members.departed")
                self.obs.gauge("live_workers", len(self.server.live))
                self._log_event("member_gone", worker=wid,
                                generation=gen)

    def _kill(self, wid: int) -> None:
        if self.transport_kind == "proc":
            sigkilled = self.transport.kill_worker(wid)   # SIGKILL
            self.server.deregister(wid)
            self._log_event("kill", worker=wid, sigkill=sigkilled)
            return
        if self.transport_kind == "host":
            # the one fault a leader can inflict on a remote host: cut
            # the connection (the worker exits cleanly on EOF)
            cut = self.transport.kill_worker(wid)
            self.server.deregister(wid)
            self._log_event("kill", worker=wid, connection_cut=cut)
            return
        w = self._workers.get(wid)
        if w is not None:
            w.stop_event.set()
        self.server.deregister(wid)
        self._log_event("kill", worker=wid)

    # ------------------------------------------------- background loops
    def _injector(self) -> None:
        # one merged timeline: a pending respawn must not delay (or
        # starve) later kill events, so kills and respawns interleave
        # in wall-clock order ("kill" sorts before "spawn" on ties —
        # a kill and a respawn at the same instant kill first)
        events = [(t, "kill", wid) for t, wid in self.faults.kill_events()]
        if self.faults.respawn_after_s > 0:
            events += [(t + self.faults.respawn_after_s, "spawn", wid)
                       for t, wid in self.faults.kill_events()]
        for t, kind, wid in sorted(events):
            if self._stop.wait(max(0.0, t - self._elapsed())):
                return
            if kind == "kill":
                self._kill(wid)
            else:
                self._spawn(wid)
                self._log_event("respawn", worker=wid,
                                generation=self._generation[wid])

    def _checkpointer(self) -> None:
        while not self._stop.wait(self.faults.checkpoint_every_s):
            # params + optimizer moments captured atomically (one lock
            # acquisition): a checkpoint whose moments ran one flush
            # ahead of its params would resume subtly wrong
            version, params, applied, opt_state = \
                self.server.snapshot_for_checkpoint()
            path = os.path.join(self.ckpt_dir, f"step_{version}")
            save_checkpoint(path, params, version,
                            extra={"mode": self.mode, "applied": applied,
                                   "backend": "cluster",
                                   "optimizer": self.optimizer.name},
                            opt_state=opt_state)
            self._log_event("checkpoint", step=version)

    def _restorer(self) -> None:
        if self._stop.wait(self.faults.restore_at_s):
            return
        step = latest_step(self.ckpt_dir)
        if step is None:
            self._log_event("restore_skipped", reason="no checkpoint yet")
            return
        path = os.path.join(self.ckpt_dir, f"step_{step}")
        params, step = restore_checkpoint(path, like=self.init_params)
        # moment slabs + update count ride the same checkpoint; an old
        # (or sgd-written) checkpoint has none and the moments restart
        # from zero with the same epoch bump
        self.server.restore(params, step,
                            opt_state=load_opt_state(path))
        self._log_event("restore", step=step)

    def _stats_payload(self) -> Dict[str, Any]:
        """One `repro top` tick: the live ledger columns, staleness
        percentiles, and fleet state.  Runs on the hub's stats-push
        thread; everything it reads is lock-protected or a snapshot."""
        a = self.server.accounting()
        st = self.obs.hist_stats("staleness") or {}
        serve_clients = 0
        if hasattr(self.transport, "serve_stats"):
            serve_clients = self.transport.serve_stats()["clients"]
        counters = self.obs.counters()
        return {
            "t": round(self._elapsed(), 3),
            "version": self.server.version,
            "mode": self.mode,
            "optimizer": self.optimizer.name,
            "optimizer_steps": counters.get("optimizer_steps", 0),
            "applied": a["applied"],
            "dropped": a["dropped"],
            "buffered": a["buffered"],
            "pending_round": a["pending_round"],
            "updates": a["updates"],
            "staleness": {"p50": st.get("p50"), "p99": st.get("p99")},
            "queue_depth": self.transport.pending_gradients(),
            "live_workers": len(self.server.live),
            "num_workers": self.num_workers,
            "fleet_size": self.fleet_size,
            "max_workers": self.max_workers,
            "serve_clients": serve_clients,
        }

    def _sampler(self, snaps: List) -> None:
        # snapshot_slab is zero work (a reference to the published,
        # never-donated params slab): sampling must not steal decode /
        # host-copy time from the serial resource it is measuring —
        # the slabs are decoded after the run, with the metrics
        i = 0
        while True:
            target = i * self.sample_every_s
            wait = target - self._elapsed()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            with self.obs.span("sampler", "snapshot"):
                version, slab, _ = self.server.snapshot_slab()
            snaps.append((target, version, slab))
            i += 1

    def _wind_down(self) -> "tuple[int, List[str]]":
        """Fleet teardown with the gradient channel kept flowing.

        Joins workers, flushes socket endpoints, joins worker
        processes, and quiesces the transport — all while continuously
        draining the gradient channel into the ``in_flight`` counter: a
        backpressured sender can only finish its final frame if the
        server side keeps making room (stalling here is what used to
        tear the last frames of a clean shutdown).  After this returns,
        every complete frame has been received and counted, so
        ``pending_gradients()`` is exact (0) and the conservation
        ledger can be asserted to the gradient.  Returns
        ``(in_flight, proc_errors)``."""
        in_flight = 0
        deadline = time.monotonic() + 15.0

        def drain() -> None:
            nonlocal in_flight
            while self.transport.recv_gradient(timeout=0) is not None:
                in_flight += 1

        for w in self._all_workers:     # prompt: all waits see stop
            w.join(timeout=10.0)
        if self.transport_kind == "proc":
            while self.transport.procs_alive():
                drain()
                # a child still starting up (e.g. a respawn racing the
                # end of the budget) has no connection to receive the
                # shutdown EOF on — SIGKILL it; it has sent nothing
                self.transport.kill_unconnected()
                if time.monotonic() > deadline:
                    break
                time.sleep(0.02)
        proc_errors: List[str] = []
        if self.transport_kind == "proc":
            proc_errors = self.transport.join_workers(timeout=5.0)
        # socket endpoints: push out accepted-but-unshipped gradients
        # (they are already counted as computed), then hang up so the
        # hub reader sees EOF and can quiesce
        endpoints = [ep for ep in (getattr(w, "endpoint", None)
                                   for w in self._all_workers)
                     if ep is not None]
        unflushed = list(endpoints)
        while unflushed and time.monotonic() < deadline:
            drain()
            # an endpoint whose sender thread died (connection error)
            # can never flush its remainder — waiting out the deadline
            # on it would stall every such teardown by ~15s
            unflushed = [ep for ep in unflushed
                         if not ep.flush(0.05) and ep.can_flush()]
        for ep in endpoints:
            ep.close()
        while True:
            drain()
            if self.transport.quiesce(timeout=0.1):
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "transport failed to quiesce within 15s — the "
                    "conservation ledger would be approximate")
        drain()
        return in_flight, proc_errors

    # -------------------------------------------------------------- run
    def run(self) -> ClusterResult:
        try:
            return self._run()
        finally:
            if self.prom_server is not None:
                self.prom_server.close()
            if self._own_transport:
                self.transport.close()

    def _run(self) -> ClusterResult:
        setup_t0 = time.monotonic()
        self._t0 = setup_t0             # provisional: pre-barrier events
        #                                 (listening, ...) get small ts;
        #                                 reset when the clock starts
        start_version = 0
        start_params = self.init_params
        resume_opt_state = None
        if self.resume_from:
            start_params, start_version = restore_checkpoint(
                self.resume_from, like=self.init_params)
            # optimizer moments + update count resume with the params;
            # None (old / sgd-written checkpoint) keeps them at zero
            resume_opt_state = load_opt_state(self.resume_from)

        if self.transport_kind not in ("proc", "host"):
            # compile the worker gradient before the clock starts, so
            # the budget measures contention, not XLA (process workers
            # and joined hosts compile in their own runtime and connect
            # once warm; the metric fns are only evaluated after the run)
            wx, wy = next(shard_iterator(self.x_tr, self.y_tr, 0,
                                         self.num_workers, self.batch,
                                         seed=self.seed))
            with self.obs.span("runtime", "compile_grad"):
                jax.block_until_ready(
                    self._grad(self.codec.encode(start_params), wx, wy))

        if self.transport_kind in ("proc", "host"):
            # hold BEFORE the server's construction-time publish: a
            # remote worker that joined while the leader was still
            # setting up must idle in fetch_params, not bank gradients
            # before the serving clock starts
            self.transport.hold_params()
        # construction compiles and warms the stage + flush executables
        with self.obs.span("runtime", "server_warmup"):
            self.server = ParameterServer(
                start_params, lr=self.lr, mode=self.mode,
                transport=self.transport, num_workers=self.num_workers,
                schedule=self.schedule, flush_mode=self.flush_mode,
                staleness_decay=self.staleness_decay,
                max_gradients=self.max_gradients,
                start_version=start_version,
                slab_dtype=self.slab_dtype, optimizer=self.optimizer,
                obs=self.obs)
        if resume_opt_state is not None:
            # after construction (warmup rewound the count to 0) and
            # before any worker can flush: load the checkpointed
            # moments so the resumed run continues bias correction
            # from the saved step, not from step 0
            self.server.agg.reset_opt_state(resume_opt_state)
        if hasattr(self.transport, "stats_provider"):
            # the STATS push plane (`repro top`): now that the server
            # exists, the hub can answer stats subscribers with live
            # ledger + staleness numbers
            self.transport.stats_provider = self._stats_payload
        if self.prom_port is not None:
            # Prometheus scrape surface over the same payload (plus the
            # raw telemetry counters, e.g. repro_wire_tx_bytes_total);
            # started only once the server exists so every scrape sees
            # a coherent ledger
            from repro.obs.prom import PromServer
            self.prom_server = PromServer(
                lambda: (self._stats_payload(), self.obs.counters()),
                self.prom_port)
            self._log_event("prom_listening",
                            port=int(self.prom_server.port))
            if self.verbose:
                print(f"[cluster] prometheus metrics at "
                      f"{self.prom_server.url}", file=sys.stderr,
                      flush=True)

        snaps: List = []
        threads: List[threading.Thread] = []
        try:
            if self.transport_kind in ("proc", "host"):
                # assemble the fleet (spawn it, or advertise and wait
                # for joins), then hold the clock until every worker
                # has compiled and connected (HELLO == ready); fail
                # fast on a spawned child that crashed during startup.
                # The params broadcast is withheld until the barrier
                # passes, so early workers idle in fetch_params
                # instead of banking gradients before the clock starts
                # (which would flatter the multi-process benchmark)
                self.transport.on_worker_ready = self._on_remote_ready
                self.transport.on_worker_gone = self._on_remote_gone
                if self.transport_kind == "host":
                    self.transport.on_serve_ready = \
                        lambda sid: self._log_event("serve_client",
                                                    serve_id=sid)
                if self.transport_kind == "proc":
                    for wid in range(self.num_workers):
                        self._spawn(wid)
                else:
                    # externally-joined workers may have said HELLO
                    # before the hooks existed — register them now
                    for wid, gen in \
                            self.transport.connected_workers().items():
                        self._on_remote_ready(wid, gen)
                    bind_host, bind_port = self.listen_address
                    self._log_event("listening", host=bind_host,
                                    port=int(bind_port),
                                    expected_workers=self.num_workers)
                    # a wildcard bind is not a dialable address — the
                    # copy-paste hint must name a host the workers can
                    # actually reach
                    adv_host = bind_host if bind_host not in \
                        ("0.0.0.0", "::", "") else "<LEADER_HOST>"
                    print(f"[cluster] leader listening on {bind_host}:"
                          f"{bind_port} — waiting for "
                          f"{self.num_workers} worker(s) to join "
                          f"(python -m repro join "
                          f"{adv_host}:{bind_port})",
                          file=sys.stderr, flush=True)
                ready_deadline = (time.monotonic()
                                  + self.proc_ready_timeout_s)
                while not self.transport.wait_for_workers(
                        self.num_workers, timeout=1.0):
                    if self.transport_kind == "proc":
                        dead = self.transport.dead_workers()
                        if dead:
                            raise RuntimeError(
                                "worker process(es) died before the "
                                "fleet was ready:\n" + "\n".join(dead))
                    if time.monotonic() > ready_deadline:
                        raise RuntimeError(
                            f"only "
                            f"{sorted(self.transport.live_workers())} "
                            f"of {self.num_workers} workers "
                            "connected within "
                            f"{self.proc_ready_timeout_s}s")

            self._t0 = time.monotonic()
            setup_s = self._t0 - setup_t0
            self.server.start_clock(self._t0)
            if self.transport_kind in ("proc", "host"):
                self.transport.release_params()     # the starting gun
            if start_version:
                self._log_event("resume", step=start_version,
                                path=self.resume_from)
            threads.append(self._guarded(lambda: self._sampler(snaps),
                                         "sampler"))
            if self.faults.kill:
                threads.append(self._guarded(self._injector, "injector"))
            if self.ckpt_dir and self.faults.checkpoint_every_s > 0:
                threads.append(self._guarded(self._checkpointer, "ckpt"))
            if self.ckpt_dir and self.faults.restore_at_s > 0:
                threads.append(self._guarded(self._restorer, "restore"))
            for t in threads:
                t.start()
            if self.transport_kind not in ("proc", "host"):
                # local thread workers; proc spawned its fleet at the
                # barrier, and host workers joined from outside
                for wid in range(self.num_workers):
                    self._spawn(wid)

            deadline = self._t0 + self.wall_budget_s
            while time.monotonic() < deadline \
                    and not self.server.done.is_set():
                with self.obs.span("server", "recv_wait",
                                   hist="recv_wait_s"):
                    msg = self.transport.recv_gradient(timeout=min(
                        0.02, max(1e-3, deadline - time.monotonic())))
                if msg is not None:
                    self.server.ingest(msg)
            wall_s = self._elapsed()
        finally:
            # ---------------------------------------------- shutdown
            # ALWAYS propagate shutdown to the workers — including when
            # the server loop above died mid-run: a worker blocked on a
            # bounded send retries until its stop event is set, so a
            # crashed server must not strand a live worker (regression-
            # tested).  Control threads stop first: the injector must
            # not respawn a worker nobody stops (all its waits watch
            # self._stop, so these joins return promptly).
            self._stop.set()
            for t in threads:
                t.join(timeout=10.0)
            if self.transport_kind in ("proc", "host"):
                # EOF on the params direction tells each worker process
                # (spawned or remotely joined) to stop; its in-flight
                # gradient frames are still drained
                self.transport.half_close_workers()
            for w in self._all_workers:
                w.stop_event.set()

        in_flight, proc_errors = self._wind_down()
        errors = [f"worker {w.worker_id}.{w.generation}:\n{w.error}"
                  for w in self._all_workers if w.error]
        errors += proc_errors
        errors += self._control_errors
        # a thread that outlived its join would keep mutating transport/
        # server state under the accounting we are about to report
        errors += [f"{t.name} did not stop within the join timeout"
                   for t in (*self._all_workers, *threads)
                   if t.is_alive()]
        if errors:
            raise RuntimeError("cluster thread(s)/process(es) crashed "
                               "or hung:\n" + "\n".join(errors))

        leftover = self.transport.pending_gradients()
        if leftover:
            raise RuntimeError(
                f"{leftover} gradients appeared after the post-quiesce "
                "drain — a producer outlived shutdown")

        accounting = self.server.accounting()
        accounting["in_flight"] = in_flight
        if self.transport_kind in ("proc", "socket", "host"):
            # "computed" on the socket transports = complete frames
            # that physically reached the hub (exact under every
            # failure mode: whatever a killed worker or dying
            # connection had not finished sending died with it, like a
            # thread worker killed before send; the conformance suite
            # separately asserts nothing is lost on a healthy wire)
            received = self.transport.received_counts()
            accounting["computed"] = sum(received.values())
            # an elastic fleet may have grown past the seed: report a
            # column for every member that ever existed
            fleet_ids = set(range(self.fleet_size)) | set(received)
            accounting["computed_per_worker"] = {
                str(wid): received.get(wid, 0)
                for wid in sorted(fleet_ids)}
            accounting["torn_frames"] = self.transport.torn_frames
        else:
            accounting["computed"] = sum(w.sent
                                         for w in self._all_workers)
            per_worker: Dict[str, int] = {}
            for w in self._all_workers:     # all generations of each id
                key = str(w.worker_id)
                per_worker[key] = per_worker.get(key, 0) + w.sent
            accounting["computed_per_worker"] = per_worker

        # ---------------------------------- evaluate the metric snapshots
        times, tr, te, acc = [], [], [], []
        with self.obs.span("runtime", "eval", snapshots=len(snaps)):
            for target, _, slab in snaps:
                params = self.codec.decode(slab)
                times.append(target)
                tr.append(float(self._loss(params, self.x_tr[:2048],
                                           self.y_tr[:2048])))
                te.append(float(self._loss(params, self.x_te,
                                           self.y_te)))
                acc.append(float(self._acc(params, self.x_te, self.y_te))
                           if self._acc is not None else 0.0)

        # snapshot() already returns a host copy (the donation rule:
        # nothing escaping the server may alias the donated slab)
        _, final_params, applied = self.server.snapshot()
        # the serving report is shape-stable across transports: a hub
        # transport reports its real serve-plane state, and a transport
        # with no serving plane (inproc) reports the same keys, empty —
        # consumers key on content, never on key presence
        if hasattr(self.transport, "serve_stats"):
            serving = self.transport.serve_stats()
        else:
            serving = {"clients": 0, "rejected_peers": 0,
                       "serve_every": 1, "stats_clients": 0,
                       "per_client": []}
        # telemetry summary + the ledger cross-check: every gradient
        # the server ingested is exactly accounted (applied + dropped +
        # buffered + pending), and everything computed that was never
        # ingested is the post-loop in_flight drain
        telemetry = self.obs.summary()
        c = telemetry["counters"]
        ingested = c.get("grads_ingested", 0)
        ledger_sum = (accounting["applied"] + accounting["dropped"]
                      + accounting["buffered"]
                      + accounting["pending_round"])
        telemetry["ledger_check"] = {
            "grads_ingested": ingested,
            "ledger_sum": ledger_sum,
            "computed": accounting["computed"],
            "in_flight": accounting["in_flight"],
            "consistent": (ingested == ledger_sum
                           and accounting["computed"]
                           == ingested + accounting["in_flight"]),
        }
        agg = self.server.agg
        placement = {
            "platform": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "flush": ("jnp" if not agg.use_pallas else
                      "pallas_interpret" if agg.interpret else "pallas"),
            "worker_platforms": {str(w): p for w, p in
                                 sorted(self._worker_platforms.items())},
        }
        return ClusterResult(
            times=np.asarray(times), train_loss=np.asarray(tr),
            test_loss=np.asarray(te), test_acc=np.asarray(acc),
            num_updates=accounting["updates"], num_gradients=applied,
            mode=self.mode, start_version=start_version,
            accounting=accounting, events=list(self.events),
            final_params=final_params, wall_s=wall_s, serving=serving,
            telemetry=telemetry, placement=placement, setup_s=setup_s)
