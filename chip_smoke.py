"""Chip smoke test: the parameter server's main path, once, on a TPU.

    python chip_smoke.py               # one chip: the cluster backend
    python chip_smoke.py --four-chips  # four chips: the SPMD backend

One chip: ``repro.api`` drives the cluster parameter server exactly as
``python -m repro run --backend cluster`` does, with ``zoo:xlstm`` at
``zoo_scale=1.0`` — the registry's xlstm-350m tier at its published
widths (d_model 1024, vocab 50304, 24 layers, 440,052,880 parameters,
random weights from seed 0).  Two in-process worker threads share the
chip with the server; a ``step`` schedule moves the flush threshold K
from 1 (async) to 2 (the fleet size); the run stops after a budget of
applied gradients, and the test loss must not have risen.  bf16 slabs
with plain SGD (lr 1e-4) keep the server's state (f32 master, bf16
staging rows and published copy) and both workers' gradient programs
inside the chip's 16 GB.  Before that, the Pallas flush kernel is
checked against a float64 host reference.

Four chips: the group-annealed SPMD backend at xlstm-350m's published
widths, ``mode="hybrid"`` annealing the replica count R 4 -> 2 -> 1,
then the same spec with ``mode="sync"`` as the comparison.

Every check that fails exits non-zero with a message and prints no
result.  On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compile cache goes to ``JAX_COMPILATION_CACHE_DIR`` if it is set,
else to ``.jax_cache/`` beside this file, so a second run starts warm.
"""
import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# plain SGD's step size at these widths: the gradient's norm at the
# random init is ~3.2e3 against a parameter norm of ~650, so the
# default lr=0.01 moves the weights 5% per step and the loss diverges
# (11.4 -> 2.5e9 in 48 gradients on a v5e); at 1e-4 a step is 0.05%
LR = 1e-4


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAIL: {msg}")


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def check_flush_kernel() -> None:
    """The Pallas flush on the chip against a float64 host reference,
    at the K and row dtypes the server stages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.hybrid_aggregate import TILE_P, flush_pallas

    rng = np.random.default_rng(0)
    for dtype in (jnp.float32, jnp.bfloat16):
        for k in (1, 2, 4):
            rows = jnp.asarray(rng.standard_normal((k, 8 * TILE_P)), dtype)
            w = jnp.asarray(rng.uniform(0.5, 1.5, k), jnp.float32)
            got = np.asarray(jax.block_until_ready(
                flush_pallas(rows, w, out_dtype=jnp.float32)))
            terms = (np.asarray(w, np.float64)[:, None]
                     * np.asarray(rows.astype(jnp.float32), np.float64))
            # k <= 4 f32 products and sums: within k ulp of f32 of the
            # terms' magnitude (the sum itself may cancel towards 0)
            err = float(np.max(np.abs(got - terms.sum(0))
                               / np.abs(terms).sum(0)))
            if not err < 1e-6:
                fail(f"Pallas flush (K={k}, {jnp.dtype(dtype).name} rows) "
                     f"is off the float64 reference by rel {err:.3g}")
    say("Pallas flush matches the float64 reference "
        "(K in 1,2,4; f32 and bf16 rows)")


def one_chip() -> None:
    import jax

    from repro.api import ExperimentSpec
    from repro.cluster.trainer import ClusterTrainer
    from repro.models.zoo import zoo_workload

    check_flush_kernel()
    spec = ExperimentSpec(
        arch="zoo:xlstm", zoo_scale=1.0, backend="cluster",
        transport="inproc", mode="hybrid", schedule="step:8",
        cluster_workers=2, slab_dtype="bf16", optimizer="sgd", lr=LR,
        batch=8, max_gradients=48,
        # the budget only bounds a failed run: 48 gradients take seconds
        wall_budget_s=120.0,
        # one metric snapshot (t=0): every snapshot holds a published
        # slab on the device until the run ends
        wall_sample_every_s=120.0, smoke=True, seed=0)
    trainer = ClusterTrainer(verbose=True)
    t0 = time.time()
    res = trainer.run(spec)
    total_s = time.time() - t0
    ex = res.extra
    place, acct = ex["placement"], ex["accounting"]
    n_params = sum(int(x.size)
                   for x in jax.tree.leaves(trainer.last_params))
    say(f"cluster run: {n_params:,} params, setup (compile + warm-up) "
        f"{ex['setup_s']:.2f} s, serving window {ex['serve_wall_s']:.3f} "
        f"s, whole run {total_s:.2f} s")
    say(f"placement: {place}")
    if place["platform"] != "tpu":
        fail(f"the server ran on {place['platform']!r}, not the TPU")
    if place["flush"] != "pallas":
        fail(f"the server's flush took the {place['flush']!r} path, "
             "not the compiled Pallas kernel")
    if set(place["worker_platforms"].values()) != {"tpu"}:
        fail(f"workers computed on {place['worker_platforms']}")
    if not ex["telemetry"]["ledger_check"]["consistent"]:
        fail(f"conservation ledger is inconsistent: "
             f"{ex['telemetry']['ledger_check']}")
    if res.num_gradients != acct["applied"] or acct["applied"] <= 0:
        fail(f"num_gradients {res.num_gradients} vs applied "
             f"{acct['applied']}")
    switches = ex["telemetry"]["counters"].get("k_switches", 0)
    say(f"applied {acct['applied']} gradients in {acct['updates']} "
        f"updates ({acct['applied'] / ex['serve_wall_s']:.2f} grads/s "
        f"over the window), {switches} K switch(es); ledger {acct}")
    if switches < 1:
        fail("the flush threshold K never moved from 1 to the fleet size")

    loss_fn, _, (_, _, x_te, y_te), _ = zoo_workload(spec)
    final = float(jax.jit(loss_fn)(trainer.last_params, x_te, y_te))
    first = {k: v[0] for k, v in res.metrics.items()}
    say(f"loss at t=0: train {first['train_loss']:.4f}, test "
        f"{first['test_loss']:.4f}; test loss after {acct['applied']} "
        f"gradients: {final:.4f}")
    if not all(math.isfinite(x) for x in (*first.values(), final)):
        fail(f"non-finite loss: t=0 {first}, final {final}")
    if not final < first["test_loss"] + 1.0:
        fail(f"test loss rose from {first['test_loss']:.4f} to {final:.4f} "
             f"in {acct['applied']} gradients at lr {LR}")
    say(f"peak device bytes: {peak_bytes(jax.devices()[0]):,}")


def four_chips() -> None:
    import jax

    from repro.api import ExperimentSpec, SpmdTrainer

    base = ExperimentSpec(arch="xlstm-350m", backend="spmd", smoke=False,
                          steps=6, batch=8, seq=128, optimizer="sgd",
                          lr=LR,
                          log_every=1, seed=0)
    want_R = {"hybrid": [4, 2, 1], "sync": [1]}
    for mode, spec in (("hybrid", base.with_(mode="hybrid",
                                             schedule="step:2")),
                       ("sync", base.with_(mode="sync", schedule=None))):
        t0 = time.time()
        res = SpmdTrainer(verbose=True).run(spec)
        wall = time.time() - t0
        hist = res.extra["history"]
        seen = [h["replicas"] for h in hist]
        phases = [r for i, r in enumerate(seen) if i == 0 or seen[i - 1] != r]
        losses = [h["loss"] for h in hist]
        say(f"spmd {mode}: {wall:.2f} s, replicas per step {seen}, "
            f"losses {[round(x, 4) for x in losses]}, "
            f"{res.num_gradients} gradients")
        if phases != want_R[mode]:
            fail(f"spmd {mode}: replica phases {phases}, "
                 f"want {want_R[mode]}")
        if any(h["devices"] != 4 for h in hist):
            fail(f"spmd {mode}: params spanned "
                 f"{[h['devices'] for h in hist]} devices, want 4")
        if res.num_gradients != sum(seen):
            fail(f"spmd {mode}: {res.num_gradients} gradients, "
                 f"want {sum(seen)}")
        if not all(math.isfinite(x) for x in losses):
            fail(f"spmd {mode}: non-finite loss {losses}")
    say("peak device bytes per chip: "
        f"{[peak_bytes(d) for d in jax.devices()]}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD hybrid-vs-sync pair on a "
                         "4-chip mesh")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch._xla_env import use_compile_cache
    except ImportError:
        fail(f"the repro package is not beside this script "
             f"({os.path.join(ROOT, 'src', 'repro')} is missing)")
    cache = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's devices are {dev.platform!r} "
             f"({len(devices)} of them)")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        fail(f"{need} chips needed, JAX sees {len(devices)}")
    say(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
