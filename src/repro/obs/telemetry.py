"""The in-process telemetry bus: counters, histograms, trace spans.

One :class:`Telemetry` instance rides a cluster run (created by the
runtime, shared with the server, the thread workers, the in-process
transport and the socket hub).  The design constraint is the hot path:
``ingest`` and the hub reader threads call into this on *every
gradient*, so every metric is a dict update under one lock — no
allocation beyond the first use of a name, no I/O — and a span adds one
small object and a profiler annotation, a few microseconds.

Vocabulary:

  * ``count(name, n)`` — monotonic counters (``grads_ingested``,
    ``wire.rx_bytes``, ...);
  * ``gauge(name, v)`` — last-write-wins instantaneous values;
  * ``observe(name, v)`` — histogram samples (``staleness``,
    ``publish_gap_s``, ``grad_queue_s``): running count/min/max/sum
    plus a capped sample buffer for percentiles;
  * ``span(track, name, hist=..., **args)`` — a context manager held
    open around the work on a named track (``server``, ``worker/3``,
    ``worker/3/wire``, ``runtime``, ``sampler``).  It always enters a
    ``jax.profiler.TraceAnnotation`` named ``<track>/<name>`` (a no-op
    unless the profiler is recording, so any device profile shows
    the program's spans on its host plane) and feeds its duration to
    the ``hist`` histogram if one is named; when ``trace=True`` it also
    lands in the ring buffer that :mod:`repro.obs.trace` exports;
  * ``instant(track, name, **args)`` — a zero-duration marker (K(t)
    switch, kill, restore), ring buffer only.

Spans are timed on ``time.monotonic()`` relative to the bus's creation;
the bus records one anchor pair there — monotonic time and the wall
clock in nanoseconds, which is the profiler's time base — so the Chrome
export lands on the profiler's clock and overlays a device trace of the
same run.  Tracing records spans only; it never touches the math, which
keeps sync runs bitwise-identical with tracing on or off
(regression-tested in ``tests/test_obs.py``).

:data:`NULL` is the no-op singleton: components take ``obs=None`` and
fall back to it, so instrumentation is zero-cost for callers that
construct subsystems directly (tests, benchmarks, library use).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# spans are ring-buffered: a long run keeps the most recent window
# rather than growing without bound (200k spans ~ tens of MB of JSON,
# about what a trace viewer stays responsive on)
SPAN_CAPACITY = 200_000
# histogram sample retention per name: percentiles are computed over a
# capped buffer; count/min/max/sum stay exact past the cap
HIST_CAPACITY = 65_536


class _Hist:
    __slots__ = ("count", "total", "vmin", "vmax", "samples")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin: Optional[float] = None
        self.vmax: Optional[float] = None
        self.samples: List[float] = []

    def add(self, v: float) -> None:
        self.count += 1
        self.total += v
        if self.vmin is None or v < self.vmin:
            self.vmin = v
        if self.vmax is None or v > self.vmax:
            self.vmax = v
        if len(self.samples) < HIST_CAPACITY:
            self.samples.append(v)

    def stats(self) -> Dict[str, float]:
        if not self.count:
            return {"count": 0}
        s = sorted(self.samples)

        def pct(q: float) -> float:
            if not s:
                return 0.0
            idx = min(len(s) - 1, max(0, round(q * (len(s) - 1))))
            return float(s[int(idx)])

        return {"count": self.count,
                "min": float(self.vmin), "max": float(self.vmax),
                "mean": self.total / self.count,
                "p50": pct(0.50), "p95": pct(0.95), "p99": pct(0.99)}


class _SpanCtx:
    """Context manager around one unit of work: a profiler annotation
    while open; on exit, its duration to ``hist`` (if named) and, when
    tracing, one completed span to the ring buffer."""
    __slots__ = ("_tel", "_track", "_name", "_hist", "_args", "_ann",
                 "_t0")

    def __init__(self, tel: "Telemetry", track: str, name: str,
                 hist: Optional[str], args: Optional[Dict[str, Any]]):
        self._tel = tel
        self._track = track
        self._name = name
        self._hist = hist
        self._args = args

    def __enter__(self) -> "_SpanCtx":
        self._ann = TraceAnnotation(f"{self._track}/{self._name}",
                                    **(self._args or {}))
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        tel = self._tel
        if self._hist is not None:
            tel.observe(self._hist, t1 - self._t0)
        if tel.trace:
            tel._spans.append(
                ("X", self._track, self._name,
                 self._t0 - tel.t0, t1 - self._t0, self._args))


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Telemetry:
    """The live bus.  Thread-safe; every mutation is O(1) under one
    lock (spans append to a lock-free deque)."""

    def __init__(self, trace: bool = False):
        self.trace = bool(trace)
        # the anchor pair: span/instant time base, and the same instant
        # on the profiler's clock (the wall clock, in ns)
        self.t0 = time.monotonic()
        self.t0_wall_ns = time.time_ns()
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, _Hist] = {}
        # (kind "X"|"I", track, name, t_rel_s, dur_s, args|None)
        self._spans: "collections.deque[Tuple]" = \
            collections.deque(maxlen=SPAN_CAPACITY)

    @property
    def enabled(self) -> bool:
        return True

    # ------------------------------------------------------------ metrics
    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = _Hist()
            h.add(float(value))

    # ----------------------------------------------------------- timeline
    def span(self, track: str, name: str, hist: Optional[str] = None,
             **args) -> Any:
        """``with obs.span("worker/0", "grad_compute", hist="grad_s",
        version=v): ...`` — a profiler annotation around the block, its
        duration observed into ``hist``, and a ring-buffered span when
        tracing.  ``args`` (ints: worker, seq, version, ...) ride on
        both the annotation and the buffered span."""
        return _SpanCtx(self, track, name, hist, args or None)

    def instant(self, track: str, name: str, **args) -> None:
        """A zero-duration timeline marker (K(t) switch, kill,
        restore, ...)."""
        if self.trace:
            self._spans.append(("I", track, name,
                                time.monotonic() - self.t0, 0.0,
                                args or None))

    # ------------------------------------------------------------ exports
    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def hist_stats(self, name: str) -> Optional[Dict[str, float]]:
        """Live percentile snapshot of one histogram (the STATS frame
        provider reads ``staleness`` here mid-run)."""
        with self._lock:
            h = self._hists.get(name)
            return h.stats() if h is not None else None

    def spans(self) -> List[Tuple]:
        return list(self._spans)

    def summary(self) -> Dict[str, Any]:
        """The structured metrics report that lands in
        ``RunResult.extra["telemetry"]``."""
        with self._lock:
            return {
                "trace": self.trace,
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: h.stats()
                               for k, h in sorted(self._hists.items())},
                "spans_recorded": len(self._spans),
            }


class NullTelemetry:
    """The disabled bus: every call is a no-op.  Components default to
    this when no ``obs`` is passed, so instrumentation costs nothing
    outside an observed run."""

    trace = False

    @property
    def enabled(self) -> bool:
        return False

    def count(self, name: str, n: int = 1) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def observe(self, name: str, value: float) -> None:
        pass

    def span(self, track: str, name: str, hist: Optional[str] = None,
             **args) -> Any:
        return _NULL_SPAN

    def instant(self, track: str, name: str, **args) -> None:
        pass

    def counters(self) -> Dict[str, int]:
        return {}

    def hist_stats(self, name: str) -> Optional[Dict[str, float]]:
        return None

    def spans(self) -> List[Tuple]:
        return []

    def summary(self) -> Dict[str, Any]:
        return {"trace": False, "counters": {}, "gauges": {},
                "histograms": {}, "spans_recorded": 0}


NULL = NullTelemetry()
