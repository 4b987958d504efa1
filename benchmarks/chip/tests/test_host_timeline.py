"""The host-timeline tool on the CPU: what it reads from the program's
histograms, how it finds the longest stall, and one tiny traced run whose
spans line up with a profile of it."""
import gzip
import json
import os
import sys

import pytest

from conftest import HERE

sys.path.insert(0, os.path.join(HERE, "tools"))

import host_timeline as ht  # noqa: E402

CELL = "danube4l.k2.t2048"


def _hist(**kw):
    return dict({"count": 4, "min": 0.0, "max": 0.0, "mean": 0.0,
                 "p50": 0.0, "p95": 0.0, "p99": 0.0}, **kw)


def test_readings_of_the_program_histograms():
    tel = {"histograms": {
        "publish_gap_s": _hist(max=0.25),
        "grad_queue_s": _hist(p95=0.004),
        "recv_wait_s": _hist(count=300, mean=0.02)}}
    r = ht.readings(tel, 30.0)
    assert r["publish_gap_ms_max"] == pytest.approx(250.0)
    assert r["grad_queue_ms_p95"] == pytest.approx(4.0)
    # 300 waits of 20 ms over a 30 s window
    assert r["ingest_wait_share"] == pytest.approx(20.0)


@pytest.mark.parametrize("tel", [
    None,
    {"histograms": {}},
    # a program without these histograms, or one that kept none yet
    {"histograms": {"staleness": _hist(), "publish_gap_s": {"count": 0}}},
])
def test_readings_are_empty_where_the_program_has_nothing(tel):
    assert set(ht.readings(tel, 30.0).values()) == {None}


def test_longest_gap_and_the_spans_open_in_it():
    def x(track, name, t, d, **args):
        return ("X", track, name, t, d, args or None)

    spans = [
        x("server", "publish", 1.0, 0.01),            # ends 1.01
        x("server", "publish", 1.2, 0.01),            # ends 1.21
        x("worker/0", "grad_compute", 1.15, 1.5, seq=3),
        x("server", "recv_wait", 1.3, 0.02),
        x("server", "recv_wait", 1.32, 1.0),
        x("sampler", "snapshot", 0.1, 0.001),
        ("I", "server", "k_switch", 2.0, 0.0, None),
        x("server", "publish", 2.7, 0.01),            # ends 2.71
    ]
    gap, open_ = ht.longest_gap(spans, t_open=0.5)
    assert gap == pytest.approx((1.21, 2.71))
    assert set(open_) == {"server", "worker/0"}
    # longest overlap first, times from the gap's start
    assert [s[0] for s in open_["server"]] == ["recv_wait", "recv_wait",
                                               "publish"]
    name, t, d, args = open_["worker/0"][0]
    assert (name, args) == ("grad_compute", {"seq": 3})
    assert t == pytest.approx(-0.06) and d == pytest.approx(1.5)
    # the first interval runs from the window's start
    gap, _ = ht.longest_gap(spans[:1], t_open=0.0)
    assert gap == pytest.approx((0.0, 1.01))
    assert ht.longest_gap(spans[2:6], t_open=0.0) == (None, {})


def test_a_stall_dumps_every_thread_and_when_it_ended(tmp_path):
    """The watchdog fires while the interpreter's lock is held: here by a
    C call made through ``ctypes.PyDLL``, which keeps it for 1.2 s."""
    import ctypes
    import threading
    import time

    path = str(tmp_path / "stalls.txt")
    stop = threading.Event()
    t = threading.Thread(target=ht._watch, args=(path, stop))
    t0 = time.monotonic()
    t.start()
    time.sleep(0.3)
    ctypes.PyDLL(None).usleep(1_200_000)
    time.sleep(0.3)
    stop.set()
    t.join(timeout=5)
    assert not t.is_alive()
    # the held lock's stall, whatever a loaded machine adds besides
    held = [(ended, stacks) for ended, stacks in ht._stalls(path, t0)
            if "test_a_stall_dumps" in stacks]
    assert len(held) == 1
    ended, stacks = held[0]
    assert 1.4 <= ended <= 3.0
    assert "Timeout" in stacks and "_watch" in stacks


def test_a_tiny_traced_run_writes_its_timeline(tiny, tmp_path, monkeypatch):
    """A traced run of the tiny cell on the CPU: the line carries every
    reading, the longest stall inside the window, the gzipped Chrome
    JSON, and the profile's annotations within 1 ms of the spans."""
    monkeypatch.setattr(ht, "PROFILE_AFTER_S", 0.2)
    monkeypatch.setattr(ht, "PROFILE_FOR_S", 1.0)
    import jax

    compiles = []
    listen = ht._compile_events(compiles)
    try:
        line = ht.run_cell(CELL, 2 ** 31 + 5, 3.0, True, str(tmp_path),
                           compiles, profile=True, c=tiny(CELL),
                           require_tpu=False)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    # set-up compiled the cell's programs; none finished in the window
    assert compiles and line["compiles_in_window"] == []
    assert line["correct"] and line["applied"] >= 3
    assert set(line["e2e"]) >= {"setup_s", "applied_tokens_per_s"}
    assert None not in line["readings"].values()
    assert 0 < line["readings"]["ingest_wait_share"] < 100
    a, b = line["longest_gap_s"]
    assert 0 <= a < b <= 3.5
    assert 1e3 * (b - a) == pytest.approx(
        line["readings"]["publish_gap_ms_max"], abs=5.0)
    assert "server" in line["open_in_gap"]
    with gzip.open(tmp_path / f"{CELL}.{2 ** 31 + 5}.json.gz", "rt") as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert {"recv_wait", "ingest", "stage_dispatch", "flush_dispatch",
            "publish", "fetch_wait", "grad_compute", "send_wait",
            "compile_grad", "server_warmup", "eval", "snapshot"} <= names
    assert line["host_counters"]["major_faults"] >= 0
    assert isinstance(line["stalls"], list)
    spans = line["overlay"]["spans_us"]
    assert "server/recv_wait" in spans
    assert any(name.endswith("/grad_compute") for name in spans), spans
    assert all(s["max"] < 1000.0 for s in spans.values()), spans
