"""A cluster worker: one thread running real jitted gradient steps.

Each worker owns a deterministic minibatch iterator over its shard of
the training data (see :func:`repro.data.pipeline.shard_iterator`),
fetches the latest published parameter *slab* from the transport,
computes a real (jitted) gradient, and sends the gradient back as a
slab tagged with the parameter version it read — staleness in this
runtime is physical, not simulated.  ``grad_fn`` is slab-in/slab-out
(decode → grad → encode fused into one executable, built by the
runtime), so the worker flattens each gradient exactly once and the
transport carries single contiguous arrays in both directions.

Policy differences live entirely in *when* a worker blocks:

  * ``async`` / ``hybrid`` — fetch whatever version is current, never
    wait: a slow server means more stale gradients, exactly the
    contention the hybrid buffer amortises;
  * ``sync`` — after contributing to round v, block until the server
    publishes v+1 (the barrier's worker side).

Fault hooks: ``straggle_s`` adds a sleep per gradient (a slow node /
link); ``stop_event`` is the cooperative kill switch the fault injector
and the runtime's shutdown both use — the runtime *always* sets it on
the way out (even when the server died mid-run), and a worker process
wires it to its socket client's ``closed`` event, so neither a crashed
server nor a closed connection can leave a worker spinning in the
bounded-send retry loop.  A killed worker's in-flight gradient is lost
*before* send, so the accounting invariant
(sent == applied + dropped + buffered + pending + in-flight) holds.

Every transport wait here is a short *positive* timeout (never ``None``
= block forever, never ``<= 0`` = spin): each iteration re-checks
``stop_event``, which is what keeps the loop killable from outside.
"""
from __future__ import annotations

import threading
import traceback
from typing import Callable, Iterator, Optional

import jax

from repro.cluster.transport import GradientMsg, Transport
from repro.obs.telemetry import NULL


class Worker(threading.Thread):
    def __init__(self, worker_id: int, *, grad_fn: Callable,
                 batches: Iterator, transport: Transport, mode: str,
                 straggle_s: float = 0.0, generation: int = 0,
                 name: Optional[str] = None, obs=None):
        super().__init__(name=name or f"worker-{worker_id}.{generation}",
                         daemon=True)
        self.worker_id = worker_id
        self.generation = generation
        self.grad_fn = grad_fn
        self.batches = batches
        self.transport = transport
        self.mode = mode
        self.straggle_s = straggle_s
        self.stop_event = threading.Event()
        self.sent = 0            # gradients actually handed to the server
        self.error: Optional[str] = None
        self.obs = obs if obs is not None else NULL

    def run(self) -> None:
        try:
            self._loop()
        except Exception:                       # surfaced by the runtime
            self.error = traceback.format_exc()

    def _loop(self) -> None:
        next_version = 0        # sync: the round we haven't contributed to
        epoch = 0               # restore epoch of the params last used
        track = f"worker/{self.worker_id}"
        while not self.stop_event.is_set():
            min_v = next_version if self.mode == "sync" else 0
            with self.obs.span(track, "fetch_wait"):
                msg = self.transport.fetch_params(min_version=min_v,
                                                  timeout=0.05)
            if msg is None:
                if self.mode == "sync" and min_v > 0:
                    # a checkpoint restore moves the server's version
                    # *backwards* (and wipes the in-progress round);
                    # waiting for the old round would stall the barrier
                    # until the budget expires — resync.  The restore
                    # EPOCH is the signal: a merely-lower version is
                    # indistinguishable from "my round has not finished
                    # yet" on a slow fleet, and re-contributing on that
                    # false positive would double-draw from the batch
                    # stream and break sync determinism
                    cur = self.transport.fetch_params(timeout=0)
                    if cur is not None \
                            and getattr(cur, "epoch", 0) != epoch:
                        msg = cur
                if msg is None:
                    continue
            epoch = getattr(msg, "epoch", 0)
            x, y = next(self.batches)
            seq = self.sent + 1
            # blocks on the device: the span is the gradient's compute
            with self.obs.span(track, "grad_compute", hist="grad_s",
                               worker=self.worker_id, seq=seq,
                               version=msg.version):
                grad = self.grad_fn(msg.params, x, y)
                jax.block_until_ready(grad)
            if self.straggle_s and self.stop_event.wait(self.straggle_s):
                break           # killed mid-straggle: gradient is lost
            out = GradientMsg(self.worker_id, grad, msg.version, seq)
            # bounded queue: block until the server drains, or until
            # killed while blocked (the gradient is then lost)
            ok = False
            with self.obs.span(track, "send_wait", hist="send_wait_s",
                               worker=self.worker_id, seq=seq,
                               version=msg.version):
                while not ok and not self.stop_event.is_set():
                    ok = self.transport.send_gradient(out, timeout=0.05)
            if not ok:
                break
            self.sent += 1
            if self.mode == "sync":
                next_version = msg.version + 1
