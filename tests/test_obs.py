"""Telemetry-plane tests (``repro.obs``): the bus itself, the Chrome
trace export, and the conformance bars the plane must clear —

  * telemetry counters reconcile EXACTLY with the conservation ledger
    on every transport (``grads_ingested == applied + dropped +
    buffered + pending_round`` and ``computed == grads_ingested +
    in_flight``);
  * a tracing-disabled run is bitwise identical to a tracing-enabled
    one (spans are the only trace-gated work, and they never touch the
    math);
  * a read-only STATS reader attached to a live leader streams
    progress without perturbing the run — a sync host run with a stats
    reader is bitwise identical to inproc;
  * the perf gate fails serve cells that regress training throughput
    or client staleness.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax

from repro.api import ExperimentSpec, run
from repro.cluster.trainer import ClusterTrainer
from repro.obs import NULL, Telemetry, chrome_trace, write_chrome_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                 # for `import benchmarks.*`
    sys.path.insert(0, REPO)

CHILD_PLATFORM = None if jax.default_backend() == "cpu" else "cpu"


def _spec(**kw):
    base = dict(arch="mlp", backend="cluster", mode="hybrid",
                schedule="step:40", cluster_workers=2, wall_budget_s=1.5,
                wall_sample_every_s=0.5, batch=16, smoke=True)
    base.update(kw)
    return ExperimentSpec(**base)


def _sync_spec(**kw):
    base = dict(arch="mlp", backend="cluster", mode="sync",
                schedule=None, cluster_workers=2, wall_budget_s=30.0,
                wall_sample_every_s=10.0, batch=16, smoke=True,
                max_gradients=12)
    base.update(kw)
    return ExperimentSpec(**base)


def _check_reconcile(res):
    """Telemetry counters vs the conservation ledger, exactly."""
    a = res.extra["accounting"]
    tel = res.extra["telemetry"]
    c = tel["counters"]
    ingested = c.get("grads_ingested", 0)
    # every gradient the server saw is in exactly one ledger bucket
    assert ingested == (a["applied"] + a["dropped"] + a["buffered"]
                        + a["pending_round"]), (c, a)
    # every gradient computed either reached the server or is in flight
    assert a["computed"] == ingested + a["in_flight"], (c, a)
    assert c.get("grads_applied", 0) == a["applied"]
    assert c.get("updates", 0) == a["updates"]
    per_worker = sum(v for k, v in c.items()
                     if k.startswith("grads_ingested.w"))
    assert per_worker == ingested
    check = tel["ledger_check"]
    assert check["consistent"], check
    return tel


# --------------------------------------------------------------- the bus

def test_telemetry_counters_gauges_histograms():
    tel = Telemetry()
    tel.count("grads")
    tel.count("grads", 4)
    tel.count("bytes", 100)
    tel.gauge("depth", 3.0)
    tel.gauge("depth", 7.0)               # last write wins
    for v in range(100):
        tel.observe("staleness", float(v))
    assert tel.counters() == {"grads": 5, "bytes": 100}
    st = tel.hist_stats("staleness")
    assert st["count"] == 100 and st["min"] == 0.0 and st["max"] == 99.0
    assert st["p50"] == 50.0 and st["p95"] == 94.0 and st["p99"] == 98.0
    assert tel.hist_stats("nope") is None
    s = tel.summary()
    assert s["trace"] is False and s["spans_recorded"] == 0
    assert s["gauges"] == {"depth": 7.0}
    assert s["counters"]["grads"] == 5
    assert s["histograms"]["staleness"]["mean"] == pytest.approx(49.5)


def test_spans_recorded_only_when_tracing():
    # the span's histogram is always fed; the ring buffer only when
    # tracing
    off = Telemetry(trace=False)
    with off.span("server", "flush_dispatch", hist="flush_dispatch_s",
                  k=3):
        time.sleep(0.002)
    off.instant("server", "k_switch", k=1)
    assert off.spans() == []
    st = off.hist_stats("flush_dispatch_s")
    assert st["count"] == 1 and st["min"] >= 0.002

    on = Telemetry(trace=True)
    with on.span("worker/0", "grad_compute", version=7):
        pass
    with on.span("server", "flush_dispatch", hist="flush_dispatch_s",
                 k=2):
        pass
    on.instant("server", "k_switch", k=1)
    spans = on.spans()
    assert len(spans) == 3
    kinds = sorted(s[0] for s in spans)
    assert kinds == ["I", "X", "X"]
    x = next(s for s in spans if s[2] == "grad_compute")
    assert x[1] == "worker/0" and x[5] == {"version": 7}
    assert on.summary()["spans_recorded"] == 3
    assert on.hist_stats("flush_dispatch_s")["count"] == 1
    assert on.hist_stats("grad_compute") is None    # no hist named


def test_null_telemetry_is_inert():
    assert NULL.enabled is False
    NULL.count("x")
    NULL.gauge("x", 1.0)
    NULL.observe("x", 1.0)
    with NULL.span("t", "n", hist="h", k=1):
        pass
    NULL.instant("t", "n")
    assert NULL.counters() == {} and NULL.spans() == []
    assert NULL.hist_stats("x") is None
    assert NULL.summary() == {"trace": False, "counters": {},
                              "gauges": {}, "histograms": {},
                              "spans_recorded": 0}


def test_chrome_trace_export(tmp_path):
    tel = Telemetry(trace=True)
    with tel.span("worker/1", "grad_compute", version=5):
        time.sleep(0.003)
    with tel.span("server", "flush_dispatch", k=2):
        time.sleep(0.001)
    tel.instant("server", "k_switch", k=1)
    doc = chrome_trace(tel)
    events = doc["traceEvents"]
    # the server track sorts first regardless of name order
    meta = {e["args"]["name"]: e["tid"] for e in events
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert meta["server"] == 0 and meta["worker/1"] == 1
    recorded = {s[2]: s for s in tel.spans()}
    flush = next(e for e in events if e["name"] == "flush_dispatch")
    assert flush["ph"] == "X" and flush["dur"] >= 1000.0
    assert flush["dur"] == pytest.approx(recorded["flush_dispatch"][4]
                                         * 1e6, abs=1e-3)
    assert flush["args"] == {"k": 2} and flush["cat"] == "server"
    grad = next(e for e in events if e["name"] == "grad_compute")
    assert grad["tid"] == 1 and grad["cat"] == "worker"
    assert grad["dur"] >= 3000.0
    inst = next(e for e in events if e["name"] == "k_switch")
    assert inst["ph"] == "i" and inst["s"] == "t"
    # X events carry microsecond timestamps on the profiler's clock:
    # the bus's wall-clock anchor plus the span's monotonic offset
    for e, name in ((flush, "flush_dispatch"), (grad, "grad_compute")):
        assert e["ts"] == pytest.approx(
            tel.t0_wall_ns / 1e3 + recorded[name][3] * 1e6, abs=1e-3)
    assert flush["ts"] >= grad["ts"] + grad["dur"]
    assert abs(grad["ts"] - time.time_ns() / 1e3) < 10e6

    out = tmp_path / "trace.json"
    n = write_chrome_trace(tel, str(out))
    assert n == 3                        # metadata rows not counted
    loaded = json.loads(out.read_text())
    assert loaded["displayTimeUnit"] == "ms"
    assert len(loaded["traceEvents"]) == len(events)


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    """A span is a profiler annotation: inside a profile it appears on
    the ``/host:`` plane as ``<track>/<name>`` with its args, and the
    Chrome export's ``ts`` for it lies within 1 ms of that event's
    start (the profiler's starts are relative to the profile's
    ``profile_start_time``, a wall-clock ns stamp)."""
    import glob

    from jax.profiler import ProfileData

    tel = Telemetry(trace=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("server", "recv_wait", hist="recv_wait_s",
                      worker=1, seq=3):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    start = None
    hits = []
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = stats["profile_start_time"]
        if plane.name.startswith("/host:"):
            hits += [ev for line in plane.lines for ev in line.events
                     if ev.name == "server/recv_wait"]
    assert start is not None and len(hits) == 1
    ev = hits[0]
    assert dict(ev.stats) == {"worker": 1, "seq": 3}
    assert ev.duration_ns >= 5e6
    exported = next(e for e in chrome_trace(tel)["traceEvents"]
                    if e["name"] == "recv_wait")
    assert abs(exported["ts"] - (start + ev.start_ns) / 1e3) < 1000.0
    assert tel.hist_stats("recv_wait_s")["count"] == 1


# ------------------------------------- named stages in the executables

def _op_names(compiled) -> set:
    import re
    return {part for name in re.findall(r'op_name="([^"]*)"',
                                        compiled.as_text())
            for part in name.split("/")}


@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adamw"])
def test_executables_carry_their_stage_names(optimizer):
    """The worker's fused gradient and the server's flush executables
    name their stages (``jax.named_scope``): the compiled HLO keeps them
    in each op's ``op_name``.  Staging runs no executable: the flush,
    lowered over the held rows, is the aggregator's only one."""
    import jax.numpy as jnp

    from repro.core.slab import SlabAggregator, slab_codec
    from repro.optim.slab_form import SlabOptimizer

    rt = ClusterTrainer().build_runtime(_sync_spec(slab_dtype="bf16"))
    p = rt.codec.encode(rt.init_params)
    x, y = rt.x_tr[:16], rt.y_tr[:16]
    assert {"decode", "loss_grad", "encode"} <= \
        _op_names(rt._grad.lower(p, x, y).compile())

    codec = slab_codec(rt.init_params, "bf16")
    agg = SlabAggregator(codec, rt.init_params, 2,
                         optimizer=SlabOptimizer(optimizer))
    jitted = {name for name, v in vars(agg).items() if hasattr(v, "lower")}
    assert jitted == ({"_flush"} if optimizer == "sgd"
                      else {"_flush", "_flush_opt"})
    assert agg.stage(p, 0) and agg.stage(p, 1)
    rows = tuple(r for r, in agg._rows)
    w, s = jnp.ones((2,), jnp.float32), jnp.float32(0.1)
    if optimizer == "sgd":
        flush = agg._flush.lower(agg._slab, rows, w, s)
    else:
        state = [agg._moments[m] for m in agg.opt.moment_names]
        flush = agg._flush_opt.lower(agg._slab, *state, agg._count,
                                     rows, w, s)
    assert {"aggregate", "apply", "publish_cast"} <= \
        _op_names(flush.compile())


def test_named_stages_leave_the_sync_run_bitwise_unchanged(monkeypatch):
    """Names are metadata: a sync run under a gradient budget with the
    stage names compiled out ends on bit-identical parameters."""
    import contextlib

    spec = _sync_spec(slab_dtype="bf16")
    named = ClusterTrainer()
    assert named.run(spec).extra["accounting"]["applied"] == 12
    jax.clear_caches()
    try:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        plain = ClusterTrainer()
        assert plain.run(spec).extra["accounting"]["applied"] == 12
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for key in named.last_params:
        assert np.array_equal(np.asarray(named.last_params[key]),
                              np.asarray(plain.last_params[key])), key


# --------------------------------------------- ledger reconciliation

@pytest.mark.parametrize("transport", ["inproc", "socket"])
def test_counters_reconcile_with_ledger(transport):
    res = run(_spec(transport=transport))
    tel = _check_reconcile(res)
    h = tel["histograms"]
    a = res.extra["accounting"]
    # the instrumented seams produced samples: staleness per ingest,
    # flush dispatch/publish and the gap since the last per update,
    # grad/send-wait per worker gradient, the ingest loop's waits
    for name in ("staleness", "flush_dispatch_s", "publish_s",
                 "publish_gap_s", "grad_s", "send_wait_s",
                 "recv_wait_s"):
        assert h.get(name, {}).get("count", 0) > 0, name
    assert h["flush_dispatch_s"]["count"] == a["updates"]
    assert h["publish_gap_s"]["count"] == a["updates"]
    # the in-process queue times every gradient it hands out (the
    # window's and the post-window drain's); the wire carries no send
    # time, so the socket hub has none
    if transport == "inproc":
        assert h["grad_queue_s"]["count"] == a["computed"]
    else:
        assert "grad_queue_s" not in h
    # what nothing read is gone
    for name in h:
        assert name not in ("flush_s", "opt_update_s", "queue_depth")
        assert not name.startswith(("staleness.w", "grad_s.w")), name
    assert tel["counters"].get("params_published", 0) > 0


@pytest.mark.parametrize("transport", ["inproc", "socket"])
def test_stage_counts_held_and_copied_gradients(transport):
    """In process, every staged bf16 gradient is the worker's own device
    slab, held as is (``stage.held``); a socket transport hands the
    server host rows, each copied to the device (``stage.copied``)."""
    res = run(_spec(transport=transport, slab_dtype="bf16"))
    a = res.extra["accounting"]
    c = _check_reconcile(res)["counters"]
    staged = a["applied"] + a["buffered"]
    assert staged > 0
    want = {"inproc": (staged, 0), "socket": (0, staged)}[transport]
    assert (c.get("stage.held", 0), c.get("stage.copied", 0)) == want, c


def test_counters_reconcile_with_ledger_proc():
    """Same reconciliation across the process boundary: worker-side
    compute telemetry stays in the children, but the server/wire-side
    counters the ledger check needs are all in the parent."""
    res = run(_spec(transport="proc", wall_budget_s=8.0,
                    wall_sample_every_s=2.0, max_gradients=200))
    tel = _check_reconcile(res)
    c = tel["counters"]
    assert c.get("wire.rx_bytes", 0) > 0
    assert c.get("wire.tx_bytes", 0) > 0


# ----------------------------------------------- tracing is inert

def test_trace_on_off_bitwise_identical(tmp_path):
    """A sync run under a gradient budget, traced and untraced, must
    produce bit-identical final parameters — tracing only records
    spans, never reorders or perturbs the math.  The traced run's
    artifact must be a loadable Chrome trace with at least one
    grad-compute span per worker, plus flush and publish spans."""
    spec = _sync_spec()
    plain = ClusterTrainer()
    res = plain.run(spec)
    assert res.extra["accounting"]["applied"] == 12
    assert "trace_path" not in res.extra
    assert res.extra["telemetry"]["trace"] is False
    assert res.extra["telemetry"]["spans_recorded"] == 0

    out = tmp_path / "trace.json"
    traced = ClusterTrainer(trace=str(out))
    res_t = traced.run(spec)
    assert res_t.extra["accounting"]["applied"] == 12
    assert res_t.extra["trace_path"] == str(out)
    assert res_t.extra["telemetry"]["spans_recorded"] > 0

    for key in plain.last_params:
        assert np.array_equal(np.asarray(plain.last_params[key]),
                              np.asarray(traced.last_params[key])), key

    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    tracks = {e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"server", "worker/0", "worker/1"} <= tracks
    tid_of = {e["tid"]: e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    grads_by_track = {}
    for e in events:
        if e.get("ph") == "X" and e["name"] == "grad_compute":
            track = tid_of[e["tid"]]
            grads_by_track[track] = grads_by_track.get(track, 0) + 1
    assert grads_by_track.get("worker/0", 0) >= 1
    assert grads_by_track.get("worker/1", 0) >= 1
    names = [e["name"] for e in events if e.get("ph") == "X"]
    assert names.count("flush_dispatch") >= 1
    assert names.count("publish") >= 1


# -------------------------------------------- live stats plane (STATS)

def test_stats_reader_does_not_perturb_sync_run():
    """The `repro top` acceptance bar: a read-only STATS subscriber on
    a live host-transport leader streams progress snapshots but never
    enters the run — the sync outcome stays bitwise identical to
    inproc, the ledger stays exact, and the reader is reported as a
    stats client, not a serve client."""
    from repro.cluster.hostlink import spawn_join_process
    from repro.obs.top import StatsClient

    spec = _sync_spec()
    base = ClusterTrainer()
    res = base.run(spec)
    assert res.extra["accounting"]["applied"] == 12
    # serving report is always present, empty-shaped off-host
    assert res.extra["serving"] == {
        "clients": 0, "rejected_peers": 0, "serve_every": 1,
        "stats_clients": 0, "per_client": []}

    hspec = _sync_spec(transport="host", listen="127.0.0.1:0")
    trainer = ClusterTrainer()
    runtime = trainer.build_runtime(hspec)
    procs = [spawn_join_process(runtime.listen_address, workers=1,
                                platform=CHILD_PLATFORM)
             for _ in range(2)]
    reader = StatsClient(runtime.listen_address)
    docs = []
    try:
        res_h = trainer.finish(runtime, hspec)
        # drain whatever snapshots arrived during the run
        while True:
            doc = reader.wait_stats(timeout=0.5)
            if doc is None:
                break
            docs.append(doc)
    finally:
        codes = []
        for p in procs:
            try:
                codes.append(p.wait(timeout=60))
            except Exception:
                p.kill()
                codes.append("killed")
        reader.close()
    assert codes == [0, 0], codes

    a = res_h.extra["accounting"]
    assert a["applied"] == 12
    _check_reconcile(res_h)
    serving = res_h.extra["serving"]
    assert serving["clients"] == 0          # never a serve client...
    assert serving["stats_clients"] == 1    # ...counted as a reader

    assert docs, "stats reader saw no pushes"
    live = [d for d in docs if "version" in d]
    if live:                                # saw the run mid-flight
        assert live[-1]["mode"] == "sync"
        assert 0 <= live[-1]["applied"] <= 12

    for key in base.last_params:
        assert np.array_equal(np.asarray(base.last_params[key]),
                              np.asarray(trainer.last_params[key])), key


def test_top_formats_waiting_and_live_rows():
    from repro.obs.top import _fmt_line
    line = _fmt_line({"state": "waiting"}, None)
    assert "waiting" in line
    doc = {"t": 1.5, "version": 42, "mode": "hybrid", "applied": 120,
           "dropped": 1, "buffered": 2, "pending_round": 0,
           "updates": 40, "staleness": {"p50": 0.0, "p99": 2.0},
           "queue_depth": 3, "live_workers": 2, "num_workers": 2,
           "serve_clients": 0}
    line = _fmt_line(doc, 99.5)
    assert "42" in line and "99.5" in line and "hybrid" in line


def test_stats_history_ring_backfills_late_attaching_top():
    """A `repro top` that attaches mid-run is not blind: the hub's
    cadence thread feeds a history ring even with zero subscribers, a
    new subscriber receives that ring as a one-shot backfill before its
    first live push (seeding the grads/sec delta), and live pushes stay
    coalesced — a slow reader skips ticks instead of queueing them."""
    import io

    from repro.cluster.hostlink import HostTransport
    from repro.obs.top import StatsClient, top_main

    hub = HostTransport(4, host="127.0.0.1", port=0, num_workers=1,
                        welcome_config={})
    hub.stats_every_s = 0.05
    state = {"n": 0}

    def provider():
        state["n"] += 1
        return {"t": state["n"] * 0.05, "version": state["n"],
                "applied": state["n"] * 10, "dropped": 0, "buffered": 0,
                "pending_round": 0, "queue_depth": 0, "live_workers": 1,
                "fleet_size": 1, "serve_clients": 0, "mode": "async",
                "staleness": {"p50": 0.0, "p99": 0.0}}

    reader = None
    try:
        # installing the provider starts the cadence thread at once —
        # the ring fills with nobody watching
        hub.stats_provider = provider
        deadline = time.monotonic() + 5.0
        while len(hub.stats_history()) < 3:
            assert time.monotonic() < deadline, "history ring never fed"
            time.sleep(0.02)

        # late attach: the backfill arrives before the first live push
        reader = StatsClient(hub.address)
        first = reader.wait_stats(timeout=5.0)
        assert first is not None and "version" in first
        assert reader.backfill, "no history backfill received"
        assert all("version" in c for c in reader.backfill)
        # ring cells are oldest-first and precede the first live push
        versions = [c["version"] for c in reader.backfill]
        assert versions == sorted(versions)
        assert versions[-1] <= first["version"]

        # coalescing: a slow reader skips the ticks it slept through
        time.sleep(0.4)
        latest = reader.wait_stats(timeout=5.0)
        assert latest is not None
        assert latest["version"] > first["version"] + 1

        # and the CLI body seeds its rate delta from the backfill: the
        # very first printed row already carries grads/sec (applied
        # moves 10 per 0.05s of leader clock = 200.0 exactly)
        out = io.StringIO()
        assert top_main(tuple(hub.address), count=1, out=out) == 0
        text = out.getvalue()
        assert "backfilled" in text, text
        assert "200.0" in text, text
    finally:
        if reader is not None:
            reader.close()
        hub.close()


# ------------------------------------------------ perf gate: serve cells

def _serve_report(cells):
    return {"schema": "repro.bench.serve/v1",
            "grid": [{"clients": c,
                      "train": {"grads_per_s": gps},
                      "client_stats": [
                          {"client": i, "staleness": {"p99": p99}}
                          for i, p99 in enumerate(p99s)]}
                     for c, gps, p99s in cells]}


def test_perf_gate_serve_cells(tmp_path):
    from benchmarks import perf_gate

    server = {"grid": [{"fleet": 4, "K": 1,
                        "slab": {"grads_per_s": 100.0}}]}
    server_path = tmp_path / "server.json"
    server_path.write_text(json.dumps(server))
    base_path = tmp_path / "serve_base.json"
    base_path.write_text(json.dumps(_serve_report(
        [(0, 100.0, []), (2, 50.0, [1.0, 1.0])])))

    def gate(fresh_cells):
        fresh_path = tmp_path / "serve_fresh.json"
        fresh_path.write_text(json.dumps(_serve_report(fresh_cells)))
        return perf_gate.main([
            "--fresh", str(server_path),
            "--baseline", str(server_path),
            "--serve-fresh", str(fresh_path),
            "--serve-baseline", str(base_path)])

    # identical report passes
    assert gate([(0, 100.0, []), (2, 50.0, [1.0, 1.0])]) == 0
    # noise within tolerance passes; additive staleness slack honoured
    assert gate([(0, 40.0, []), (2, 20.0, [3.0, 2.0])]) == 0
    # training throughput under serving load regressed
    assert gate([(0, 100.0, []), (2, 10.0, [1.0, 1.0])]) == 1
    # client-observed staleness regressed
    assert gate([(0, 100.0, []), (2, 50.0, [1.0, 50.0])]) == 1
    # a baseline cell missing from the fresh report FAILS, not skips
    assert gate([(0, 100.0, [])]) == 1
    # without serve args the serve plane is not gated
    assert perf_gate.main(["--fresh", str(server_path),
                           "--baseline", str(server_path)]) == 0
