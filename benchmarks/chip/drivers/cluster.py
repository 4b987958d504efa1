"""Driver ``cluster``: the wall-clock parameter server, one window.

Builds the workload itself (weights and rows from the seed), then drives
``repro.cluster.runtime.ClusterRuntime.run`` with ``transport=inproc``
for exactly ``--seconds`` of serving window.  The runtime compiles the
worker's gradient program and warms the server's stage and flush before
its clock starts; everything up to that clock is set-up.

The transport handed to the runtime is :class:`BenchTransport`, the
program's ``InProcTransport`` with the harness's clock and host spans
around each call:

* ``fetch_params`` returning to a worker: when that worker's next
  gradient was started;
* ``send_gradient`` returning, ``recv_gradient`` taking it: the queue;
* ``publish_params``: the update that applied every gradient received
  since the last publish (the runtime ingests each gradient as it
  receives it, on one thread), and the copy that workers fetch.
"""
from __future__ import annotations

import gc
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

import check
import gen
import xplane

TRACE_AFTER_S = 2.0       # profile from this far into the window ...
TRACE_FOR_S = 5.0         # ... for this long (capped at half the window)


def make_transport(base, capacity: int, on_publish, on_window_end):
    """The program's ``InProcTransport`` (``base``, imported late, once
    the compile cache is set) with the harness's records."""
    import jax

    class BenchTransport(base):
        def __init__(self):
            super().__init__(grad_capacity=capacity)
            self._lock = threading.Lock()
            self._tl = threading.local()
            self.sent: Dict[tuple, List[float]] = {}
            self.pending: List[tuple] = []
            self.flushes: List[Dict[str, Any]] = []
            self.started = threading.Event()
            self.closed = False

        def fetch_params(self, min_version=0, timeout=None):
            with jax.profiler.TraceAnnotation("bench/fetch_params"):
                msg = super().fetch_params(min_version, timeout)
            if msg is not None:
                self._tl.fetched = time.monotonic()
                self.started.set()
            return msg

        def send_gradient(self, msg, timeout=None):
            key = (msg.worker_id, msg.seq)
            with self._lock:
                if key not in self.sent:
                    self.sent[key] = [self._tl.fetched, None]
            with jax.profiler.TraceAnnotation("bench/send_gradient"):
                ok = super().send_gradient(msg, timeout)
            if ok:
                with self._lock:
                    self.sent[key][1] = time.monotonic()
            return ok

        def recv_gradient(self, timeout=None):
            # the runtime polls with a positive timeout while its window
            # runs and drains with timeout 0 once it has closed
            if timeout is not None and timeout <= 0 and not self.closed:
                self.closed = True
                on_window_end()
            with jax.profiler.TraceAnnotation("bench/recv_gradient"):
                msg = super().recv_gradient(timeout)
            if msg is not None and not self.closed:
                self.pending.append((msg.worker_id, msg.seq, msg.version,
                                     time.monotonic()))
            return msg

        def publish_params(self, msg):
            with jax.profiler.TraceAnnotation("bench/publish_params"):
                super().publish_params(msg)
            if msg.version > 0:
                self.flushes.append({"version": msg.version,
                                     "t": time.monotonic(),
                                     "members": self.pending})
                self.pending = []
                on_publish(msg)

    return BenchTransport()


def _master_reading(codec, template):
    """Compiled ``(w0 tree, f32 master slab, published slab) ->
    (per-leaf |master - w0|, elements where the published copy is not the
    master in the slab dtype)``, the slabs read in the codec's layout
    (leaves in flatten order).  The copies are compared bit for bit: on
    the TPU a compare of their values lets the compiler drop the cast,
    and it then reads the float32 master against its bfloat16 copy."""
    import jax
    import jax.numpy as jnp

    bits = jnp.dtype(f"uint{8 * jnp.dtype(codec.slab_dtype).itemsize}")

    def f(tree, slab, pub):
        norms = jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(
                slab[o:o + n] - jnp.ravel(leaf).astype(jnp.float32))))
            for o, n, leaf in zip(codec.offsets, codec.sizes,
                                  jax.tree.leaves(tree))])
        return norms, jnp.sum(
            jax.lax.bitcast_convert_type(pub, bits) !=
            jax.lax.bitcast_convert_type(slab.astype(pub.dtype), bits))
    n = codec.padded_size
    return jax.jit(f).lower(
        template, jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), codec.slab_dtype)).compile()


def _window_started(transport, stop: threading.Event) -> bool:
    """Wait until a worker has fetched its first parameters (the window
    has started); False if the run stopped first."""
    while not transport.started.wait(0.05):
        if stop.is_set():
            return False
    return not stop.is_set()


def _profile(transport, seconds: float, out: Dict[str, Any],
             stop: threading.Event) -> None:
    import jax

    if not _window_started(transport, stop):
        return
    if stop.wait(min(TRACE_AFTER_S, seconds / 4)):
        return
    out["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(out["dir"])
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        out["t0"] = time.monotonic()
        stop.wait(min(TRACE_FOR_S, seconds / 2))
        out["t1"] = time.monotonic()
    jax.profiler.stop_trace()


def run(ctx) -> Dict[str, Any]:
    """One run.  ``ctx`` carries the configuration (``cfg``), the
    traffic, the seed, the window, ``trace``, the process's start
    (``t_start``), the reference and the cell's ``limits``."""
    import jax
    import jax.numpy as jnp

    from repro.api.schedules import parse_schedule
    from repro.cluster.runtime import ClusterRuntime
    from repro.cluster.transport import InProcTransport
    from repro.models import model as M
    from repro.optim.slab_form import SlabOptimizer

    tr, cfg, seed = ctx.traffic, ctx.cfg, ctx.seed
    W, B, S, lr = tr["workers"], tr["batch"], tr["seq"], tr["lr"]
    template = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    w0 = jax.block_until_ready(gen.weights(seed, template))
    ctx.mark("weights")
    x_tr, y_tr, x_te, y_te = gen.token_rows(seed, tr, cfg.vocab_size)
    feed_seed = int(gen.rng(seed, 3).integers(0, 2 ** 62))

    def loss(p, x, y):
        return M.loss_fn(p, {"tokens": x, "labels": y}, cfg)[0]

    readings: Dict[int, Any] = {}
    peak: Dict[str, int] = {}
    holder: Dict[str, Any] = {}

    def on_publish(msg):
        if msg.version in (1, check.STEPS):
            slab = holder["runtime"].server.agg._slab
            readings[msg.version] = holder["read"](w0, slab, msg.params)

    def peak_now():
        # the CPU keeps no such record: 0 there
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())

    def on_window_end():
        peak["bytes"] = peak_now()

    transport = make_transport(InProcTransport, max(4, 2 * W), on_publish,
                               on_window_end)
    runtime = ClusterRuntime(
        loss, w0, (x_tr, y_tr, x_te, y_te), mode=tr["mode"], lr=lr,
        batch=B, num_workers=W, wall_budget_s=float(ctx.seconds),
        sample_every_s=float(ctx.seconds) + 1.0,
        schedule=parse_schedule(tr["schedule"], W), max_gradients=None,
        seed=feed_seed, transport=transport, transport_kind=tr["transport"],
        slab_dtype=tr["slab_dtype"], optimizer=SlabOptimizer(tr["optimizer"]))
    holder["runtime"] = runtime
    holder["read"] = _master_reading(runtime.codec, template)
    ctx.mark("runtime built")
    grad = runtime._grad

    def timed_grad(p, x, y):
        with jax.profiler.TraceAnnotation("bench/worker_grad"):
            return jax.block_until_ready(grad(p, x, y))

    runtime._grad = timed_grad

    prof: Dict[str, Any] = {}
    stop = threading.Event()
    prof_thread = None
    if ctx.trace:
        prof_thread = threading.Thread(
            target=_profile, args=(transport, ctx.seconds, prof, stop),
            name="bench-profiler", daemon=True)
        prof_thread.start()
    try:
        res = runtime.run()
    finally:
        stop.set()
        if prof_thread is not None:
            prof_thread.join()
    window_start = runtime._t0
    setup_s = window_start - ctx.t_start
    ctx.mark("runtime run: window and snapshot evaluation")
    if "bytes" not in peak:
        on_window_end()
    acct = res.accounting
    ledger = res.telemetry["ledger_check"]
    out: Dict[str, Any] = {
        "placement": res.placement, "setup_s": setup_s,
        "wall_s": res.wall_s, "peak_bytes": peak["bytes"],
        "attempted": int(acct["computed"]),
        "failed": int(acct["dropped"]),
        "ledger_consistent": bool(ledger["consistent"]),
        "applied": int(acct["applied"]),
    }
    flushes = transport.flushes
    ages, queue = [], []
    for f in flushes:
        for wid, seq, _, t_recv in f["members"]:
            fetched, sent = transport.sent[(wid, seq)]
            ages.append(f["t"] - fetched)
            queue.append(t_recv - sent)
    out["grad_age_s"] = ages
    out["queue_s"] = queue
    out["applied_tokens"] = int(acct["applied"]) * B * S
    out["flush_times"] = [(f["t"], len(f["members"])) for f in flushes]
    prog = {k: np.asarray(readings[v][0]) for k, v in
            (("update1", 1), ("change3", check.STEPS)) if v in readings}
    if len(prog) == 2:
        prog["publish"] = sum(int(readings[v][1]) for v in (1, check.STEPS))
    schedule = [[(wid, seq, ver) for wid, seq, ver, _ in f["members"]]
                for f in flushes[:check.STEPS]]
    if prof:
        out["trace_mono"] = (prof["t0"], prof["t1"])
        out["trace_dir"] = prof["dir"]

    # the program's state goes before the reference runs on the chip
    transport.flushes = []
    holder.clear()
    del runtime, res, transport, grad, timed_grad, readings
    gc.collect()

    checks: Optional[Dict] = None
    if "publish" in prog and len(schedule) == check.STEPS:
        ref_grad = ctx.reference.grad
        # a worker's gradient number ``seq`` is its seq-th batch
        drawn: Dict[tuple, tuple] = {}
        for wid in range(W):
            feed = gen.worker_batches(x_tr, y_tr, wid, W, B, feed_seed)
            last = max([s for members in schedule for w, s, _ in members
                        if w == wid], default=0)
            for s in range(1, last + 1):
                drawn[(wid, s)] = next(feed)
        ref = check.replay(ref_grad, w0, schedule,
                           lambda w, s: drawn[(w, s)], lr,
                           jnp.bfloat16 if tr["slab_dtype"] == "bf16"
                           else jnp.float32)
        checks = check.verdict(prog, ref, lr, ctx.limits)
    ctx.mark("reference")
    out["checks"] = checks
    return out
