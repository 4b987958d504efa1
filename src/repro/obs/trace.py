"""Chrome trace-event export: a cluster run as a Perfetto waterfall.

Converts a :class:`~repro.obs.telemetry.Telemetry` span buffer into
the Chrome trace-event JSON format (the ``traceEvents`` array of
``ph: "X"`` complete events and ``ph: "i"`` instants, microsecond
timestamps) that ``chrome://tracing`` and https://ui.perfetto.dev load
directly.  Every telemetry *track* becomes one named thread row —
``server`` first, then ``runtime``, ``sampler``, ``worker/0``,
``worker/0/wire``, ... — so an async→sync K(t) run reads as a timeline:
per-worker ``fetch_wait``/``grad_compute``/``send_wait`` spans
interleaving with the server's ``recv_wait``/``ingest``/``publish``
spans, wire ``grad_rx`` spans showing backpressure waits, and instant
markers for K(t) switches, kills, and restores.

Timestamps are on the profiler's clock (wall-clock microseconds, from
the bus's anchor pair), so the export overlays a ``jax.profiler`` trace
of the same run, whose host plane carries the same spans by name.

Produced by ``python -m repro run --backend cluster --trace out.json``
(or the ``python -m repro trace out.json ...`` sugar).
"""
from __future__ import annotations

import json
from typing import Any, Dict, List


def chrome_trace(tel) -> Dict[str, Any]:
    """The trace-event document for a telemetry bus's span buffer."""
    spans = tel.spans()
    tracks = sorted({s[1] for s in spans},
                    key=lambda t: (t != "server", t))
    tid = {track: i for i, track in enumerate(tracks)}
    events: List[Dict[str, Any]] = [
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid[t],
         "args": {"name": t}} for t in tracks]
    events += [
        {"name": "thread_sort_index", "ph": "M", "pid": 1,
         "tid": tid[t], "args": {"sort_index": tid[t]}} for t in tracks]
    t0_us = tel.t0_wall_ns / 1e3
    for kind, track, name, t_rel, dur, args in spans:
        ev: Dict[str, Any] = {
            "name": name, "pid": 1, "tid": tid[track],
            "ts": round(t0_us + t_rel * 1e6, 3),
            "cat": track.split("/", 1)[0],
        }
        if kind == "X":
            ev["ph"] = "X"
            ev["dur"] = round(dur * 1e6, 3)
        else:
            ev["ph"] = "i"
            ev["s"] = "t"           # instant scoped to its thread row
        if args:
            ev["args"] = dict(args)
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(tel, path: str) -> int:
    """Write the trace JSON; returns the number of timeline events
    (excluding track metadata)."""
    doc = chrome_trace(tel)
    with open(path, "w") as f:
        json.dump(doc, f)
        f.write("\n")
    return sum(1 for e in doc["traceEvents"] if e["ph"] != "M")
