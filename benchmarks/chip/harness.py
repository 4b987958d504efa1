"""The benchmark's harness: one cell, one run, one JSON line.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix; each lives in a file of its own under ``configs/`` and
``traffic/``, the traffic names its driver (``drivers/<name>.py``), each
per-layer metric has a reader (``metrics/<name>.py``) and each cell its
limits of ``correct`` (``limits/<cell>.json``).  Adding a cell adds
files; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
import types
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# (phase, seconds since the process started), printed to stderr at the end
PHASES: List[tuple] = []


class Refused(SystemExit):
    """A run that must print no result: exits non-zero with a reason."""

    def __init__(self, msg: str):
        super().__init__(f"bench: FAIL: {msg}")


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str) -> types.ModuleType:
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str) -> Dict[str, Any]:
    """The cell's entry, configuration, traffic, limits and metrics."""
    bench = _json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "entry": entry,
        "config": _json(HERE / "configs" / f"{entry['config']}.json"),
        "traffic": _json(HERE / "traffic" / f"{entry['traffic']}.json"),
        "limits": _json(HERE / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig``: its registry entry with the file's
    ``set`` fields, checked against the file's ``shape`` block and its
    parameter count."""
    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M

    cfg = dataclasses.replace(get_config(conf["registry"]), **conf["set"])
    for key, want in conf["shape"].items():
        got = cfg.resolved_head_dim if key == "head_dim" \
            else getattr(cfg, key)
        if key == "block_pattern":
            got = [list(p) for p in got]
        if got != want:
            raise Refused(f"{conf['name']}: {key} is {got!r} in the "
                          f"program, {want!r} in the configuration file")
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    if n != conf["params"]:
        raise Refused(f"{conf['name']} builds {n:,} parameters, the file "
                      f"states {conf['params']:,}")
    return cfg


def mark(phase: str, t_start: float) -> None:
    """Record that set-up phase ``phase`` ended now."""
    PHASES.append((phase, time.monotonic() - t_start))


def use_cache() -> None:
    """JAX's persistent compilation cache, every program cached and none
    evicted, so only a cell's first run compiles.  The directory is the
    program's rule (``repro.launch._xla_env``): ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` at the checkout's root."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(kind: str) -> Dict[str, float]:
    table = _json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def p95(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 95)) if values else None


# the end-to-end metrics, from what the traffic's driver module recorded
END_TO_END = {
    "setup_s": lambda out: out["setup_s"],
    "applied_tokens_per_s": lambda out: out["applied_tokens"]
    / out["wall_s"],
    "grad_age_p95_ms": lambda out: None if not out["grad_age_s"]
    else 1e3 * p95(out["grad_age_s"]),
    "peak_hbm_gib": lambda out: out["peak_bytes"] / 2 ** 30,
}


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        c: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One run of ``workload``; returns the result object.  Tests pass
    ``require_tpu=False`` and a cell dict ``c`` of their own."""
    import jax.numpy as jnp

    import check
    import flops
    import xplane
    from reference import Ref

    c = c or cell(workload)
    conf, tr = c["config"], c["traffic"]
    device = device_info(c["entry"]["chips"], require_tpu)
    if require_tpu:
        use_cache()
    cfg = model_config(conf)
    ref = Ref(conf["shape"], dtype=jnp.float32)
    ctx = types.SimpleNamespace(
        cfg=cfg, traffic=tr, seed=seed, seconds=seconds, trace=trace,
        t_start=t_start, reference=ref, limits=c["limits"],
        mark=lambda phase: mark(phase, t_start))
    mark("model config", t_start)
    out = load_module("drivers", tr["driver"]).run(ctx)

    place = out["placement"]
    if require_tpu and (place["flush"] != "pallas" or
                        set(place["worker_platforms"].values()) != {"tpu"}):
        raise Refused(f"the run left the chip's path: {place}")
    device["memory_peak_bytes"] = out["peak_bytes"]
    checks = out["checks"]
    correct = bool(checks) and set(checks) == set(c["limits"]) and \
        out["ledger_consistent"] and check.passed(checks)
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": out["attempted"],
                              "failed": out["failed"]}
    metrics: Dict[str, Any] = {}
    if not trace:
        for m in c["end_to_end"]:
            v = END_TO_END[m["name"]](out)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    else:
        summary = None
        path = xplane.find(out["trace_dir"]) if "trace_dir" in out \
            else None
        if path is not None:
            summary = xplane.summarize(path)
        if "trace_dir" in out:
            shutil.rmtree(out["trace_dir"], ignore_errors=True)
        rec = types.SimpleNamespace(
            summary=summary, out=out, traffic=tr, shape=conf["shape"],
            params=conf["params"],
            peaks=peaks(device["kind"]) if require_tpu else None,
            flops_per_token=flops.train_flops_per_token(conf["shape"],
                                                        tr["seq"]))
        for m in c["per_layer"]:
            v = load_module("metrics", m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["device"] = device
            result["breakdown"] = {
                "device_ops": xplane.top(summary.op_s),
                "idle_gaps": xplane.top(summary.idle_by_span)}
        else:
            result["device"] = device
    result["checks"] = checks or {
        k: {"value": None, "limit": v} for k, v in c["limits"].items()}
    return result


def report(result: Dict[str, Any]) -> None:
    """The result as the last line of stdout, and each number compared
    beside its limit as the last lines of stderr, after the times at
    which the run's phases ended."""
    for phase, t in PHASES:
        print(f"phase {phase} ended at {t:.1f} s", file=sys.stderr)
    for name, v in result["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
