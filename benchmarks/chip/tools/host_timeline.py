"""Find the longest stall in a cell's window on the program's own timeline.

    python3 benchmarks/chip/tools/host_timeline.py \
        --workload danube4l.k1.t256 --seeds 11 13 --off 12 14 \
        --seconds 30 --out /tmp/timeline [--profile 11]

Each seed is one run of the cell as the benchmark runs it (``harness.run``,
profiler off), all in this one process, the traced seeds (``--seeds``) and
the untraced ones (``--off``) in turn.  A traced run has the runtime's
telemetry bus record its spans (``trace=True``) and writes them as Chrome
JSON, gzipped, to ``<out>/<workload>.<seed>.json.gz``; an untraced run keeps
only the bus's always-on histograms.  For every run one JSON line goes to
stdout: the benchmark's end-to-end metrics, what the program's histograms
read (``readings``), the compilations JAX finished inside the window, and for
a traced run the longest interval between publishes with the spans of each
track that overlap it.  A last line gives the median of applied gradients,
the runs that applied 0.8% fewer or more, and every run's longest gap.

While a run goes on, a watchdog dumps every thread's Python
stack (``faulthandler``, a C thread that needs no interpreter lock) whenever
the tool's own thread has not run for ``STALL_S``: the line then carries
each dump and when it ended.  It also carries what the kernel counted over
the run: stall time under CPU, memory and I/O pressure, and major page
faults.

``--profile S`` also runs the JAX profiler for a few seconds of seed ``S``'s
window (``<out>/<workload>.<seed>.xplane/``) and reports how far each of the
program's spans lies from its annotation on the profile's host plane, and
how many device modules of the flush and the gradient the profile holds.
"""
from __future__ import annotations

import argparse
import faulthandler
import gzip
import json
import os
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))

PROFILE_AFTER_S = 2.0     # profile from this far into the window ...
PROFILE_FOR_S = 3.0       # ... for this long
SHORT = 0.992             # a run applying less than this x the median
STALL_S = 0.5             # a stall this long dumps every thread's stack
MODULES = ("jit__flush_impl", "jit__grad_slab")


def readings(telemetry: Optional[Dict[str, Any]],
             wall_s: float) -> Dict[str, Optional[float]]:
    """What the program's always-on histograms say about the window:

    * ``publish_gap_ms_max``: the longest time without a publish (from the
      window's start to the first, then between consecutive ones);
    * ``grad_queue_ms_p95``: p95 of the time gradients sat in the
      in-process queue;
    * ``ingest_wait_share``: the share of the window (%) the server's
      ingest loop spent blocked waiting for a gradient.

    A value is None where the program keeps no such histogram."""
    hists = (telemetry or {}).get("histograms", {})

    def stat(name: str, key: str) -> Optional[float]:
        h = hists.get(name) or {}
        return h.get(key) if h.get("count") else None

    gap = stat("publish_gap_s", "max")
    queue = stat("grad_queue_s", "p95")
    wait = stat("recv_wait_s", "mean")
    return {
        "publish_gap_ms_max": None if gap is None else 1e3 * gap,
        "grad_queue_ms_p95": None if queue is None else 1e3 * queue,
        "ingest_wait_share": None if wait is None else
        100.0 * hists["recv_wait_s"]["count"] * wait / wall_s,
    }


def longest_gap(spans: List[tuple], t_open: float):
    """The longest interval without a ``server/publish``, from ``t_open``
    (the window's start, on the spans' clock) to the first publish and
    between consecutive ones, as ``(start, end)``; and for each track the
    spans overlapping it, longest overlap first (``name, start, dur,
    args``, times relative to the interval's start)."""
    ends = sorted(t + d for kind, track, name, t, d, _ in spans
                  if kind == "X" and track == "server" and name == "publish")
    if not ends:
        return None, {}
    marks = [t_open] + ends
    a, b = max(zip(marks, marks[1:]), key=lambda ab: ab[1] - ab[0])
    open_: Dict[str, List] = {}
    for kind, track, name, t, d, args in spans:
        ov = min(b, t + d) - max(a, t)
        if kind == "X" and ov > 0:
            open_.setdefault(track, []).append((ov, name, t - a, d, args))
    overlaps = {track: [[n, round(t, 6), round(d, 6), args]
                        for _, n, t, d, args in sorted(v, key=lambda x:
                                                       -x[0])[:8]]
                for track, v in sorted(open_.items())}
    return (a, b), overlaps


def overlay(tel, xplane_dir: str) -> Dict[str, Any]:
    """Line the bus's spans up with their annotations on the profile's
    host plane: the offset of each annotation from the span's start on
    the bus's clock, matched by name and args (the nearest of the same
    name where a span has none); and the device modules of the flush and
    the gradient that ran inside the profile."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(xplane_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    start, host, modules = None, [], {m: 0 for m in MODULES}
    for plane in pd.planes:
        stats = dict(plane.stats)
        start = stats.get("profile_start_time", start)
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/host:") and "/" in ev.name:
                    host.append((ev.name, dict(ev.stats), ev.start_ns))
                elif line.name == "XLA Modules":
                    name = ev.name.split("(")[0]
                    if name in modules:
                        modules[name] += 1
    by_key: Dict[str, List] = {}
    for kind, track, name, t, _, args in tel.spans():
        if kind == "X":
            by_key.setdefault(f"{track}/{name}", []).append(
                (tel.t0_wall_ns + 1e9 * t, args or {}))
    offsets: Dict[str, List[float]] = {}
    for name, args, t_ns in host:
        if name not in by_key or start is None:
            continue
        t_abs = start + t_ns
        same = [s for s, a in by_key[name] if a == args] or \
            [s for s, _ in by_key[name]]
        offsets.setdefault(name, []).append(
            min(abs(s - t_abs) for s in same) / 1e3)
    return {"modules": modules,
            "spans_us": {n: {"n": len(v), "max": max(v),
                             "median": statistics.median(v)}
                         for n, v in sorted(offsets.items())}}


def _compile_events(log: List[tuple]):
    """Append ``(time, event, seconds)`` to ``log`` for every compilation
    JAX reports from now on; returns the listener, to unregister."""
    import jax

    def listen(event: str, secs: float, **_) -> None:
        if "compile" in event:
            log.append((time.monotonic(), event, secs))

    jax.monitoring.register_event_duration_secs_listener(listen)
    return listen


def _host_counters() -> Dict[str, int]:
    """Microseconds the kernel counted tasks stalled under pressure
    (``/proc/pressure``, where the kernel has it) and this process's
    major page faults, so far."""
    out: Dict[str, int] = {}
    for res in ("cpu", "memory", "io"):
        try:
            with open(f"/proc/pressure/{res}") as f:
                for row in f:
                    kind, *fields = row.split()
                    out[f"{res}_{kind}_us"] = int(fields[-1].split("=")[1])
        except OSError:
            pass
    with open("/proc/self/stat") as f:
        out["major_faults"] = int(f.read().rsplit(")", 1)[1].split()[9])
    return out


def _watch(path: str, stop: threading.Event) -> None:
    """Arm faulthandler's watchdog every 0.1 s until ``stop``; after each
    dump it wrote, note when this thread ran again (``@ <monotonic>``)."""
    def size() -> int:
        return os.fstat(f.fileno()).st_size

    with open(path, "w") as f:
        seen = 0
        while not stop.wait(0.1):
            if size() != seen:
                ended = time.monotonic()
                # the dump may still be being written: let it finish
                while seen != size():
                    seen = size()
                    time.sleep(0.05)
                f.write(f"@ {ended}\n")
                f.flush()
                seen = size()
            faulthandler.dump_traceback_later(STALL_S, file=f)
        faulthandler.cancel_dump_traceback_later()


def _stalls(path: str, t0: float) -> List[list]:
    """The watchdog's dumps: ``[ended, in s of the window, stacks]``."""
    with open(path) as f:
        text = f.read()
    out, dump = [], []
    for row in text.splitlines():
        if row.startswith("@ "):
            out.append([round(float(row[2:]) - t0, 3), "\n".join(dump)])
            dump = []
        else:
            dump.append(row)
    return out


def _profile(got: Dict[str, Any], out_dir: str, stop: threading.Event):
    import jax

    while not stop.wait(0.05):
        obs = got.get("obs")
        if obs is not None and obs.counters().get("grads_ingested"):
            break
    if stop.wait(PROFILE_AFTER_S):
        return
    jax.profiler.start_trace(out_dir)
    stop.wait(PROFILE_FOR_S)
    jax.profiler.stop_trace()
    got["profiled"] = True


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             out: str, compiles: List[tuple], profile: bool = False,
             c: Optional[Dict[str, Any]] = None,
             require_tpu: bool = True) -> Dict[str, Any]:
    """One run of the cell; the result line as a dict.  Tests pass
    ``require_tpu=False`` and a cell dict ``c`` of their own."""
    import harness
    import repro.cluster.runtime as rt

    got: Dict[str, Any] = {}
    base = rt.ClusterRuntime
    json_path = os.path.join(out, f"{workload}.{seed}.json.gz")

    class Runtime(base):
        # the benchmark's cluster cell builds the runtime itself: this
        # one traces, and hands back its bus and window start (never
        # itself: the cell frees the program's state before the
        # reference runs)
        def __init__(self, *a, **kw):
            super().__init__(*a, trace=json_path if traced else None, **kw)
            got["obs"] = self.obs

        def run(self):
            try:
                res = super().run()
                got["wall_s"] = res.wall_s
                return res
            finally:
                got["t0"] = self._t0

    stop = threading.Event()
    threads = []
    xplane_dir = os.path.join(out, f"{workload}.{seed}.xplane")
    if profile:
        threads.append(threading.Thread(target=_profile,
                                        args=(got, xplane_dir, stop)))
    stall_path = os.path.join(out, f"{workload}.{seed}.stalls.txt")
    threads.append(threading.Thread(target=_watch,
                                    args=(stall_path, stop)))
    rt.ClusterRuntime = Runtime
    before = _host_counters()
    try:
        for t in threads:
            t.start()
        res = harness.run(workload, seed, seconds, False, time.monotonic(),
                          require_tpu=require_tpu, c=c)
    finally:
        rt.ClusterRuntime = base
        stop.set()
        for t in threads:
            t.join()
    after = _host_counters()
    obs, t0 = got["obs"], got["t0"]
    tel = obs.summary()
    line: Dict[str, Any] = {
        "workload": workload, "seed": seed, "traced": traced,
        "correct": res["correct"], "attempted": res["attempted"],
        "applied": tel["counters"].get("grads_applied", 0),
        "e2e": {k: v["value"] for k, v in res["metrics"].items()},
        "readings": readings(tel, got["wall_s"]),
        "compiles_in_window": [[round(t - t0, 3), e, round(s, 3)]
                               for t, e, s in compiles
                               if t0 <= t <= t0 + seconds],
        "host_counters": {k: after[k] - before.get(k, 0) for k in after},
        "stalls": _stalls(stall_path, t0),
    }
    if traced:
        gap, spans_open = longest_gap(obs.spans(), t0 - obs.t0)
        if gap is not None:
            line["longest_gap_s"] = [round(t - (t0 - obs.t0), 6)
                                     for t in gap]
            line["open_in_gap"] = spans_open
        from repro.obs import chrome_trace

        with gzip.open(json_path, "wt") as f:
            json.dump(chrome_trace(obs), f)
    if got.get("profiled"):
        line["overlay"] = overlay(obs, xplane_dir)
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[],
                    help="seeds run with the bus tracing")
    ap.add_argument("--off", type=int, nargs="*", default=[],
                    help="seeds run with tracing off")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=tempfile.gettempdir())
    ap.add_argument("--profile", type=int, default=None,
                    help="a traced seed to also profile")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.makedirs(args.out, exist_ok=True)
    compiles: List[tuple] = []
    _compile_events(compiles)
    order = [(s, True) for s in args.seeds]
    for i, s in enumerate(args.off):
        order.insert(min(2 * i + 1, len(order)), (s, False))
    lines = []
    for seed, traced in order:
        line = run_cell(args.workload, seed, args.seconds, traced, args.out,
                        compiles, profile=seed == args.profile)
        lines.append(line)
        print(json.dumps(line), flush=True)
    med = statistics.median(ln["applied"] for ln in lines)
    print(json.dumps({
        "workload": args.workload, "median_applied": med,
        "short": [[ln["seed"], ln["applied"],
                   ln["readings"]["publish_gap_ms_max"]]
                  for ln in lines if ln["applied"] <= SHORT * med],
        "publish_gap_ms_max": sorted(ln["readings"]["publish_gap_ms_max"]
                                     or 0.0 for ln in lines)}), flush=True)


if __name__ == "__main__":
    main()
