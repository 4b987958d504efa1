"""Reduce a profiler trace (``.xplane.pb``) to device time.

The window is the host span ``bench/trace_window`` that the harness
writes around the profiled seconds.  For each TPU device plane:

* busy: the union of the intervals of its ``XLA Ops`` events, clipped
  to the window (``busy_s``); idle is the rest;
* per executable: the summed duration and count of its ``XLA Modules``
  events whose start lies in the window, keyed by the module name
  without its ``(id)`` suffix (``jit__flush_impl``);
* per op: the summed duration of its ``XLA Ops`` events.

Each idle gap is labelled by the harness's host span (``bench/...``)
that overlaps it most: what the host was doing while the chip waited.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench/trace_window"
SPAN_PREFIX = "bench/"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Summary:
    window: Tuple[int, int]                        # ns, trace clock
    devices: int
    busy_s: float                                  # mean over devices
    module_s: Dict[str, float]                     # summed over devices
    module_n: Dict[str, int]
    op_s: Dict[str, float]
    idle_by_span: Dict[str, float]                 # mean over devices

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _module(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _overlap(a0, a1, b0, b1) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def summarize(path: str) -> Optional[Summary]:
    """The trace's device summary, or None when it holds no window span
    or no TPU device plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, int, int]] = []
    window = None
    dev_planes = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            dev_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW_SPAN:
                    window = (s, e)
                else:
                    spans.append((ev.name, s, e))
    if window is None or not dev_planes:
        return None
    w0, w1 = window
    busy_total = 0
    module_s: Dict[str, float] = collections.defaultdict(float)
    module_n: Dict[str, int] = collections.defaultdict(int)
    op_s: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for plane in dev_planes:
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s = int(ev.start_ns)
                    if w0 <= s < w1:
                        module_s[_module(ev.name)] += ev.duration_ns * 1e-9
                        module_n[_module(ev.name)] += 1
            elif line.name == "XLA Ops":
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = int(ev.start_ns + ev.duration_ns)
                    if _overlap(s, e, w0, w1):
                        intervals.append((max(s, w0), min(e, w1)))
                        op_s[ev.name] += (min(e, w1) - max(s, w0)) * 1e-9
        busy = _union(intervals)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            best, label = 0, "no bench span"
            for name, s, e in spans:
                ov = _overlap(a, b, s, e)
                if ov > best:
                    best, label = ov, name
            idle[label] += (b - a) * 1e-9 / len(dev_planes)
    return Summary(window=window, devices=len(dev_planes),
                   busy_s=busy_total * 1e-9 / len(dev_planes),
                   module_s=dict(module_s), module_n=dict(module_n),
                   op_s=dict(op_s), idle_by_span=dict(idle))


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
