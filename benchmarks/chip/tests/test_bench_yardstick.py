"""The yardstick itself, on the CPU: files, names, counts, the trace
reduction, and a runner that refuses to run off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TRACE = os.path.join(HERE, "tests", "data", "tiny.xplane.pb")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_has_its_files():
    b = _bench()
    for w in b["workloads"]:
        conf = next(c for c in b["configs"] if c["name"] == w["config"])
        assert os.path.isfile(os.path.join(ROOT, conf["file"]))
        assert os.path.isfile(os.path.join(HERE, "traffic",
                                           f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(HERE, "limits",
                                           f"{w['name']}.json"))
        with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(HERE, "drivers", f"{driver}.py"))
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py")), m["name"]


def test_names_units_and_bounds_follow_the_rules():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in b["workloads"]]:
        assert NAME.match(n), n
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= \
        max(1, len(b["workloads"]) // 2)


def test_each_configuration_builds_its_stated_count():
    import harness

    for c in _bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        # refuses a configuration that builds another count than its own
        cfg = harness.model_config(conf)
        assert cfg.num_layers == conf["set"]["num_groups"]
        assert set(conf["reduced"]) == set(c["reduced"])


def test_flops_and_flush_bytes_match_hand_counts():
    import flops

    with open(os.path.join(HERE, "configs", "h2o-danube-1.8b-4l.json")) as f:
        shape = json.load(f)["shape"]
    d, ff, V = 2560, 6912, 32000
    attn = d * 32 * 80 * 2 + d * 8 * 80 * 2          # wq wo, wk wv
    per_layer = attn + 3 * d * ff                    # + gate, up, down
    assert flops.matmul_params(shape) == 4 * per_layer + d * V
    # every parameter but the embedding rows and the 9 norm scales
    assert flops.matmul_params(shape) == 441_735_680 - V * d - 9 * d
    # 6 per matmul weight, + 6 * 32 heads * 80 * (1024 + 1) per layer
    assert flops.train_flops_per_token(shape, 1024) == \
        6 * 359_792_640 + 4 * 6 * 32 * 80 * 1025
    assert flops.train_flops_per_token(shape, 32) == \
        6 * 359_792_640 + 4 * 6 * 32 * 80 * 33
    # a window narrower than the sequence: query t sees min(t, 4) keys
    narrow = dict(shape, sliding_window=4)
    keys = (1 + 2 + 3 + 4 * 29) / 32
    assert flops.train_flops_per_token(narrow, 32) == \
        6 * 359_792_640 + 4 * 12 * 32 * 80 * keys
    P = 441_735_680
    # K=1 bf16: one row in, f32 master in and out, bf16 copy out
    assert flops.flush_bytes(P, 1, "bf16") == P * (2 + 8 + 2)
    assert flops.flush_bytes(P, 2, "bf16") == P * (4 + 8 + 2)
    assert flops.flush_bytes(P, 2, "f32") == P * (8 + 8 + 4)


def test_a_block_with_no_count_reads_nothing():
    import flops

    with open(os.path.join(HERE, "configs", "h2o-danube-1.8b-4l.json")) as f:
        shape = json.load(f)["shape"]
    other = dict(shape, block_pattern=[["attn", "mlp"], ["unknown", "none"]])
    assert flops.matmul_params(other) is None
    assert flops.train_flops_per_token(other, 32) is None


def test_peaks_know_the_v5e_and_refuse_other_chips():
    import harness

    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks("TPU v4")


def test_trace_reduction_on_a_recorded_trace():
    """A trace recorded on a v5e: two jitted programs run five times
    each between harness spans, with a 10 ms host sleep (span
    ``bench/recv_gradient``) in every round."""
    import xplane

    s = xplane.summarize(TRACE)
    assert s is not None and s.devices == 1
    mods = {k: n for k, n in s.module_n.items()}
    assert sorted(mods.values()) == [5, 5]
    assert 0 < s.busy_s < s.window_s
    # busy is the union of op intervals: no more than the ops' sum
    assert s.busy_s <= sum(s.op_s.values()) + 1e-9
    # five sleeps of 10 ms were idle time on the chip, labelled by the span
    assert s.idle_by_span["bench/recv_gradient"] >= 5 * 0.010
    total_idle = sum(s.idle_by_span.values())
    assert total_idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert 0 < s.idle_share < 1


def test_union_of_intervals():
    import xplane

    assert xplane._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == \
        [(0, 3), (5, 9)]


def test_seed_makes_the_same_rows_and_weights():
    import jax

    import gen

    tr = {"train_rows": 6, "test_rows": 2, "seq": 5}
    big = 2 ** 31 + 99
    a, b = gen.token_rows(big, tr, 50), gen.token_rows(big, tr, 50)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert np.array_equal(a[1], (a[0] + 1) % 50)
    assert not np.array_equal(a[0], gen.token_rows(big + 1, tr, 50)[0])
    tmpl = {"embed": jax.ShapeDtypeStruct((8, 4), np.float32),
            "final_norm": {"scale": jax.ShapeDtypeStruct((4,), np.float32)}}
    w1, w2 = gen.weights(big, tmpl), gen.weights(big, tmpl)
    assert np.array_equal(w1["embed"], w2["embed"])
    assert np.all(np.asarray(w1["final_norm"]["scale"]) == 1)
    feed = gen.worker_batches(a[0], a[1], 1, 2, 4, big)
    x, y = next(feed)
    assert x.shape == (4, 5) and set(map(tuple, x)) <= \
        set(map(tuple, a[0][1::2]))


@pytest.mark.parametrize("alone", [False, True])
def test_runner_refuses_without_a_tpu(tmp_path, alone):
    """Off the chip, and in a directory with only the benchmark's own
    files (no program), the runner exits non-zero and prints no result."""
    cwd = ROOT
    if alone:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "danube4l.k1.t256", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "bench: FAIL:" in out.stderr
    assert ("not in this checkout" if alone else "no TPU") in out.stderr
