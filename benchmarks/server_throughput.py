"""Server hot-path benchmark: flush paths and end-to-end transports.

The parameter server is the serial resource of the cluster runtime —
every microsecond it spends aggregating is stolen from the whole fleet
at once.  Two sections, one artifact (``BENCH_server.json``):

**Flush grid** — the two implementations of the fused aggregate+apply
on the CI workload (the ``mlp`` classifier the cluster smoke tests
train):

  * **pytree** — the pre-slab ``ParameterServer`` hot path, frozen here
    verbatim: one jitted per-leaf weighted fold per buffer size K,
    precompiled for every K in 1..fleet at construction (O(fleet)
    startup compiles), params re-allocated on every update;
  * **slab** — the live path (:mod:`repro.core.slab`): gradients staged
    into a preallocated (K_max, P) buffer, ONE donated flush executable
    for every K via zero-weight masking.

The grid carries an **optimizer column** ({sgd, adamw} x every
(fleet, K) cell): sgd cells diff the slab path against the frozen
pytree baseline (speedup + acceptance); adamw cells record the fused
flush+optimizer executable — aggregation, moment updates, bias
correction and the parameter step in ONE donated launch — which has no
pre-slab counterpart to diff against.

Reported per (fleet, K, optimizer) cell:

  * ``grads_per_s`` — gradients applied per second over the **full
    server lifecycle**: construction + executable compilation + serving
    ``n_flushes`` flushes of K gradients.  CI cluster runs are
    short-lived servers (seconds of wall budget), so startup compiles
    are real serving time; this is the headline number and the
    acceptance criterion (slab >= 2x pytree at K >= 4).
  * ``startup_s`` / ``serve_s`` — the split, so the trajectory records
    where the time goes;
  * ``p50_ms`` / ``p99_ms`` — steady-state per-flush apply latency
    (compiles excluded), for both paths.

**Transport grid** — the same server driven end-to-end through the
cluster runtime under each transport (``--transport``): ``inproc``
worker threads vs ``proc`` worker processes (own JAX runtimes, socket
slab frames) vs ``host`` — the multi-host path, where the leader binds
a real TCP port and every worker is a separately-launched
``repro join`` process group that rebuilds the workload from spec JSON
fetched in the leader handshake.  Each (fleet, K, transport) cell runs
a real hybrid training burst with ``const:K`` and reports
gradients/sec over the serving window (the clock starts only once the
fleet is ready, so worker-process startup is excluded and the numbers
are comparable).  This is where "does contention actually cost us"
gets a number: thread workers share one GIL/runtime, process workers
genuinely contend on the server alone, and host workers add the full
join/lease/TCP layer the multi-host deployment pays.

**Zoo sweep** — the model-zoo slab path vs parameter count P
(``zoo:transformer`` at a ladder of ``zoo_scale`` widths), in every
``{f32, bf16} x {unsharded, sharded}`` combination: per cell the
staged-flush throughput (the optimizer's saturation point — stage K
rows, one donated flush), and the wire codec throughput
(slab -> frame bytes -> slab, i.e. what the socket hubs pay per
gradient, with ``bytes_per_grad`` recording the 2x bf16 saving).

Emits ``BENCH_server.json`` with a stable schema
(``repro.bench.server/v3``) so future PRs can diff the perf trajectory:

  PYTHONPATH=src python -m benchmarks.server_throughput --quick
  PYTHONPATH=src python -m benchmarks.server_throughput \\
      --transport inproc proc host    # transport grid selection
  PYTHONPATH=src python -m benchmarks.server_throughput --zoo-only \\
      --out BENCH_zoo.json            # just the zoo sweep (make bench-zoo)
  # or: make bench-server   /   python -m repro bench
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.slab import SlabAggregator, slab_codec


# ------------------------------------------------------------- workload

def ci_workload(seed: int = 0):
    """The CI workload: the ``mlp`` classifier params (what
    ``make smoke-cluster`` trains) and a bank of gradient-sized trees."""
    from repro.models.cnn import init_mlp_clf
    params = init_mlp_clf(jax.random.PRNGKey(seed))
    return params


def gradient_bank(params, n: int):
    """n distinct gradient trees (deterministic, gradient-sized)."""
    def one(i):
        ks = jax.random.split(jax.random.PRNGKey(1000 + i),
                              len(jax.tree_util.tree_leaves(params)))
        flat, treedef = jax.tree_util.tree_flatten(params)
        leaves = [0.01 * jax.random.normal(k, x.shape)
                  for k, x in zip(ks, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)
    bank = [one(i) for i in range(n)]
    jax.block_until_ready(bank)
    return bank


# ----------------------------------------------------------- the paths

class PytreePath:
    """The pre-slab server hot path, frozen for comparison: jitted
    per-K fold over gradient pytrees + the O(fleet) precompile loop."""

    name = "pytree"

    def __init__(self, params, fleet: int, lr: float):
        self.lr = lr
        self.params = params

        def _agg_apply(params, grads, weights, scale):
            wsum = jnp.sum(weights)

            def comb(p, *leaves):
                s = weights[0] * leaves[0]
                for w, leaf in zip(weights[1:], leaves[1:]):
                    s = s + w * leaf
                return p - scale * (s / wsum)

            return jax.tree.map(comb, params, *grads)

        self._agg_apply = jax.jit(_agg_apply)
        # the pre-PR startup rule: compile every buffer size the run can
        # reach (K in 1..fleet) before the clock starts
        for k in range(1, max(1, fleet) + 1):
            jax.block_until_ready(self._agg_apply(
                params, (params,) * k, jnp.ones((k,), jnp.float32), 0.0))

    def serve_flush(self, grad_trees: List, weights: np.ndarray,
                    scale: float) -> None:
        self.params = self._agg_apply(
            self.params, tuple(grad_trees),
            jnp.asarray(weights, jnp.float32), scale)
        jax.block_until_ready(self.params)


class SlabPath:
    """The live slab path: stage K rows, one donated flush — with the
    optimizer (sgd | momentum | adamw) fused into the same executable
    when one is named."""

    name = "slab"

    def __init__(self, params, fleet: int, lr: float, optimizer=None):
        self.lr = lr
        self.codec = slab_codec(params)
        self.agg = SlabAggregator(self.codec, params, max(1, fleet),
                                  optimizer=optimizer)
        self.agg.warmup()

    def serve_flush(self, grad_slabs: List, weights: np.ndarray,
                    scale: float) -> None:
        for slot, slab in enumerate(grad_slabs):
            self.agg.stage(slab, slot)
        jax.block_until_ready(self.agg.flush_apply(weights, scale))


# ------------------------------------------------------------ zoo sweep

def bench_zoo_cell(params, kind: str, scale: float, dtype_name: str,
                   shards: int, K: int, n_flushes: int,
                   lr: float = 0.05) -> Dict:
    """One zoo cell: the slab path on a real zoo model's params at one
    (slab dtype, shard count) point — staged-flush throughput plus the
    wire codec cost per gradient."""
    from repro.cluster.mptransport import (_slab_from_payload,
                                           _slab_to_bytes)

    codec = slab_codec(params, dtype_name)
    bank = [codec.encode(g) for g in gradient_bank(params, max(K, 2))]
    jax.block_until_ready(bank)
    rows = [bank[i % len(bank)] for i in range(K)]
    weights = np.ones((K,), np.float32)

    t0 = time.perf_counter()
    agg = SlabAggregator(codec, params, K, shards=shards)
    agg.warmup()
    startup_s = time.perf_counter() - t0
    lat = np.empty(n_flushes)
    t1 = time.perf_counter()
    for i in range(n_flushes):
        f0 = time.perf_counter()
        for slot, slab in enumerate(rows):
            agg.stage(slab, slot)
        jax.block_until_ready(agg.flush_apply(weights, lr * K))
        lat[i] = time.perf_counter() - f0
    serve_s = time.perf_counter() - t1

    # the wire codec: what a socket hub pays per gradient frame
    n_wire = 5
    t2 = time.perf_counter()
    for _ in range(n_wire):
        payload = _slab_to_bytes(np.asarray(rows[0]), dtype_name)
    encode_s = (time.perf_counter() - t2) / n_wire
    t3 = time.perf_counter()
    for _ in range(n_wire):
        _slab_from_payload(payload, 0, dtype_name)
    decode_s = (time.perf_counter() - t3) / n_wire

    n_gradients = n_flushes * K
    return {
        "workload": f"zoo:{kind}", "zoo_scale": scale,
        "P": codec.size, "P_padded": codec.padded_size,
        "dtype": dtype_name, "shards": agg.shards, "K": K,
        "n_flushes": n_flushes,
        "flush": {
            "startup_s": round(startup_s, 4),
            "serve_s": round(serve_s, 4),
            "grads_per_s": round(n_gradients / max(serve_s, 1e-9), 1),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        },
        "wire": {
            "bytes_per_grad": len(payload),
            "encode_gbps": round(len(payload) / max(encode_s, 1e-9)
                                 / 1e9, 3),
            "decode_gbps": round(len(payload) / max(decode_s, 1e-9)
                                 / 1e9, 3),
        },
    }


def run_zoo_sweep(scales, dtypes, shard_opts, K: int,
                  n_flushes: int, kind: str = "transformer") -> Dict:
    """P sweep: the zoo workload at each scale, every dtype x sharding
    combination on the same params."""
    import jax as _jax

    from repro.models import model as M
    from repro.models.zoo import num_params, zoo_config

    grid = []
    for scale in scales:
        cfg = zoo_config(kind, scale)
        params = M.init_params(_jax.random.PRNGKey(0), cfg)
        n = num_params(params)
        for dtype_name in dtypes:
            for shards in shard_opts:
                cell = bench_zoo_cell(params, kind, scale, dtype_name,
                                      shards, K, n_flushes)
                grid.append(cell)
                f = cell["flush"]
                w = cell["wire"]
                print(f"zoo:{kind} x{scale:<5g} P={cell['P']:>9d} "
                      f"{dtype_name:4s} shards={cell['shards']}: "
                      f"flush {f['grads_per_s']:8.1f} g/s "
                      f"(p50 {f['p50_ms']:.2f}ms) | wire "
                      f"{w['bytes_per_grad'] / 1e6:6.2f} MB/grad "
                      f"enc {w['encode_gbps']:.2f} GB/s", flush=True)
        del params
    return {
        "definition": ("flush.grads_per_s = K*n_flushes / serve_s over "
                       "the staged-flush cycle (stage K rows + one "
                       "donated flush); wire.* is the slab<->frame "
                       "codec alone (bytes_per_grad halves at bf16)"),
        "kind": kind, "K": K, "grid": grid,
    }


# ------------------------------------------------- transport end-to-end

def bench_transport_cell(fleet: int, K: int, transport: str,
                         max_gradients: int, budget_s: float) -> Dict:
    """One (fleet, K, transport) cell: a real cluster training burst
    (hybrid, ``const:K``) through the full runtime.  gradients/sec is
    applied gradients over the *serving* window — the fleet-ready
    barrier keeps worker-process startup out of the denominator.

    The ``host`` cell is the full multi-host path: the leader binds a
    real TCP port and each worker is a separately-launched
    ``python -m repro join`` process group that rebuilds the workload
    from spec JSON fetched in the leader handshake."""
    from repro.api import ExperimentSpec
    from repro.cluster.trainer import ClusterTrainer

    spec = ExperimentSpec(
        arch="mlp", backend="cluster", mode="hybrid",
        schedule=f"const:{K}", cluster_workers=fleet,
        wall_budget_s=budget_s, wall_sample_every_s=budget_s,
        batch=32, smoke=True, transport=transport,
        max_gradients=max_gradients, listen="127.0.0.1:0")
    trainer = ClusterTrainer()
    if transport == "host":
        from repro.cluster.hostlink import spawn_join_process
        from repro.cluster.mptransport import worker_process_platform
        platform = worker_process_platform()
        runtime = trainer.build_runtime(spec)
        runtime.join_platform = platform
        # the trainer's 10-minute interactive join window is wrong for
        # a scripted bench: a join group that dies at startup should
        # fail the cell in ~2 minutes, not stall the whole grid
        runtime.proc_ready_timeout_s = 120.0
        joins = [spawn_join_process(runtime.listen_address, workers=1,
                                    platform=platform)
                 for _ in range(fleet)]
        try:
            res = trainer.finish(runtime, spec)
        finally:
            codes = []
            for p in joins:
                try:
                    codes.append(p.wait(timeout=60))
                except Exception:
                    p.kill()
                    codes.append(p.wait())
        if any(codes):
            # a dead join group means the cell was measured with a
            # smaller fleet than its label claims — refuse to record it
            raise RuntimeError(
                f"host bench cell fleet={fleet} K={K}: join process "
                f"exit codes {codes} — the measured fleet was degraded")
    else:
        res = trainer.run(spec)
    a = res.extra["accounting"]
    serve_s = res.extra["serve_wall_s"]
    return {"transport": transport, "fleet": fleet, "K": K,
            "applied": a["applied"], "updates": a["updates"],
            "computed": a["computed"],
            "serve_wall_s": round(serve_s, 3),
            "total_wall_s": round(res.wall_s, 3),
            "grads_per_s": round(a["applied"] / max(serve_s, 1e-9), 1),
            # where the gradients were computed: a worker process under
            # an accelerator parent computes on the CPU
            "worker_platforms": sorted(set(
                res.extra["placement"]["worker_platforms"].values()))}


def run_transport_grid(fleets, ks, transports, max_gradients: int,
                       budget_s: float):
    rows = []
    for fleet in fleets:
        for K in ks:
            if K > fleet:
                continue
            for transport in transports:
                row = bench_transport_cell(fleet, K, transport,
                                           max_gradients, budget_s)
                rows.append(row)
                print(f"fleet={fleet:3d} K={K:3d} "
                      f"{transport:7s}: {row['grads_per_s']:9.1f} g/s "
                      f"({row['applied']} grads in "
                      f"{row['serve_wall_s']:.2f}s serving)", flush=True)
    return rows


# ----------------------------------------------------------- measuring

def bench_cell(params, fleet: int, K: int, n_flushes: int,
               lr: float = 0.05, optimizer: str = "sgd") -> Dict:
    """One (fleet, K, optimizer) cell, same gradients and flush
    sequence for every path.  ``optimizer="sgd"`` runs both the frozen
    pytree baseline and the slab path (the historical comparison, with
    the speedup acceptance); momentum/adamw cells run the slab path
    alone — they measure the *fused flush+update* executable, which has
    no pre-slab counterpart to diff against."""
    from repro.optim import SlabOptimizer

    bank = gradient_bank(params, max(K, 4))
    codec = slab_codec(params)
    bank_slabs = [codec.encode(g) for g in bank]
    jax.block_until_ready(bank_slabs)
    weights = np.ones((K,), np.float32)
    n_gradients = n_flushes * K
    cell: Dict = {"fleet": fleet, "K": K, "optimizer": optimizer,
                  "n_flushes": n_flushes, "n_gradients": n_gradients}
    opt = SlabOptimizer(optimizer)

    paths = [(SlabPath, bank_slabs)]
    if optimizer == "sgd":
        paths.insert(0, (PytreePath, bank))
    for cls, grads in paths:
        rows = [grads[i % len(grads)] for i in range(K)]
        t0 = time.perf_counter()
        path = cls(params, fleet, lr, optimizer=opt) \
            if cls is SlabPath else cls(params, fleet, lr)
        startup_s = time.perf_counter() - t0
        lat = np.empty(n_flushes)
        t1 = time.perf_counter()
        for i in range(n_flushes):
            f0 = time.perf_counter()
            path.serve_flush(rows, weights, lr * K)
            lat[i] = time.perf_counter() - f0
        serve_s = time.perf_counter() - t1
        cell[cls.name] = {
            "startup_s": round(startup_s, 4),
            "serve_s": round(serve_s, 4),
            "grads_per_s": round(n_gradients / (startup_s + serve_s), 1),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        }
    if optimizer == "sgd":
        cell["speedup_grads_per_s"] = round(
            cell["slab"]["grads_per_s"] / cell["pytree"]["grads_per_s"],
            2)
    return cell


def run_grid(fleets, ks, n_flushes: int,
             optimizers=("sgd", "adamw")) -> Dict:
    params = ci_workload()
    codec = slab_codec(params)
    grid = []
    for fleet in fleets:
        for K in ks:
            if K > fleet:
                continue
            for optimizer in optimizers:
                cell = bench_cell(params, fleet, K, n_flushes,
                                  optimizer=optimizer)
                grid.append(cell)
                if optimizer == "sgd":
                    print(f"fleet={fleet:3d} K={K:3d} {optimizer:5s}: "
                          f"pytree {cell['pytree']['grads_per_s']:9.1f}"
                          f" g/s "
                          f"(p50 {cell['pytree']['p50_ms']:.2f}ms) | "
                          f"slab {cell['slab']['grads_per_s']:9.1f} g/s"
                          f" (p50 {cell['slab']['p50_ms']:.2f}ms) | "
                          f"speedup {cell['speedup_grads_per_s']:.2f}x",
                          flush=True)
                else:
                    print(f"fleet={fleet:3d} K={K:3d} {optimizer:5s}: "
                          f"slab {cell['slab']['grads_per_s']:9.1f} g/s"
                          f" (p50 {cell['slab']['p50_ms']:.2f}ms) "
                          f"[fused flush+update]", flush=True)
    # the acceptance cell: K >= 4 sgd cells must show >= 2x; record the
    # worst of them so the pass/fail is the conservative reading
    # (momentum/adamw cells carry no pytree baseline to diff against)
    acc_cells = [c for c in grid
                 if c["K"] >= 4 and c["optimizer"] == "sgd"]
    worst = min(acc_cells, key=lambda c: c["speedup_grads_per_s"]) \
        if acc_cells else None
    report = {
        "schema": "repro.bench.server/v3",
        "workload": "mlp",
        "P": codec.size, "P_padded": codec.padded_size,
        "leaves": len(codec.sizes),
        "definition": ("grads_per_s = n_gradients / (startup_s + "
                       "serve_s); startup includes executable "
                       "compilation (the pre-slab server compiled one "
                       "executable per K in 1..fleet; the slab server "
                       "compiles exactly one)"),
        "grid": grid,
        "acceptance": None if worst is None else {
            "criterion": "slab >= 2x pytree grads/sec at K >= 4",
            "fleet": worst["fleet"], "K": worst["K"],
            "pytree_grads_per_s": worst["pytree"]["grads_per_s"],
            "slab_grads_per_s": worst["slab"]["grads_per_s"],
            "speedup": worst["speedup_grads_per_s"],
            "pass": bool(worst["speedup_grads_per_s"] >= 2.0),
        },
        "env": {"backend": jax.default_backend(),
                "jax": jax.__version__,
                "device_count": jax.device_count()},
    }
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="server throughput: slab vs pytree flush paths, "
                    "plus end-to-end in-proc vs multi-proc transports")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized grid (fleets 4/8, K 1/4)")
    ap.add_argument("--full", action="store_true",
                    help="larger grid (fleets up to 32, K up to 16)")
    ap.add_argument("--fleets", type=int, nargs="*", default=None)
    ap.add_argument("--ks", type=int, nargs="*", default=None)
    ap.add_argument("--flushes", type=int, default=None,
                    help="flushes per cell (default 100; CI runs are "
                         "short-lived servers, so the count is sized "
                         "like a smoke run's update budget)")
    ap.add_argument("--transport", nargs="*", default=None,
                    choices=["inproc", "socket", "proc", "host", "none"],
                    help="transports for the end-to-end grid (default: "
                         "inproc proc host — in-proc vs multi-proc vs "
                         "multi-host joined process groups; 'none' "
                         "skips the section, e.g. for flush-path-only "
                         "iteration)")
    ap.add_argument("--zoo-scales", type=float, nargs="*", default=None,
                    help="zoo sweep: zoo_scale ladder (the P sweep; "
                         "default 0.125 0.25; pass an empty list to "
                         "skip the section)")
    ap.add_argument("--zoo-flushes", type=int, default=20,
                    help="zoo sweep: flushes per cell (default 20 — "
                         "the slabs are MBs, not KBs)")
    ap.add_argument("--zoo-only", action="store_true",
                    help="run only the zoo sweep (make bench-zoo): "
                         "skips the flush and transport grids, so the "
                         "output is NOT a perf-gate --fresh input")
    ap.add_argument("--out", default="BENCH_server.json")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when the acceptance criterion "
                         "(slab >= 2x pytree grads/sec at K >= 4) fails "
                         "— turns the CI step into a perf-regression "
                         "gate, not just a recorder")
    args = ap.parse_args(argv)

    if args.full:
        fleets, ks, n = [4, 8, 16, 32], [1, 4, 8, 16], 200
        t_fleets, t_ks, t_grads, t_budget = [2, 4, 8], [1, 4, 8], 600, 12.0
    elif args.quick:
        fleets, ks, n = [4, 8], [1, 4], 100
        t_fleets, t_ks, t_grads, t_budget = [2, 4], [1, 4], 300, 8.0
    else:
        fleets, ks, n = [4, 8, 16], [1, 4, 8], 100
        t_fleets, t_ks, t_grads, t_budget = [2, 4], [1, 4], 400, 10.0
    # --fleets/--ks override BOTH grids (K > fleet cells are skipped,
    # so a shrunken flush grid cannot silently keep large proc cells)
    fleets = args.fleets if args.fleets else fleets
    ks = args.ks if args.ks else ks
    n = args.flushes if args.flushes else n
    t_fleets = args.fleets if args.fleets else t_fleets
    t_ks = args.ks if args.ks else t_ks
    transports = args.transport if args.transport is not None \
        else ["inproc", "proc", "host"]
    if "none" in transports:
        transports = []
    zoo_scales = args.zoo_scales if args.zoo_scales is not None \
        else [0.125, 0.25]

    if args.zoo_only:
        report = {"schema": "repro.bench.server/v3",
                  "env": {"backend": jax.default_backend(),
                          "jax": jax.__version__,
                          "device_count": jax.device_count()}}
        transports = []
        if not zoo_scales:
            zoo_scales = [0.125, 0.25]
    else:
        report = run_grid(fleets, ks, n)
    if zoo_scales:
        print("\nzoo sweep ({f32,bf16} x {unsharded,sharded} vs P):")
        report["zoo"] = run_zoo_sweep(
            zoo_scales, ["f32", "bf16"],
            [1, max(2, jax.local_device_count())], K=4,
            n_flushes=args.zoo_flushes)
    if transports:
        print(f"\ntransport grid (hybrid const:K, {t_grads} gradients "
              f"per cell, serving window only):")
        report["transports"] = {
            "definition": ("grads_per_s = applied / serve_wall_s; the "
                           "serving window starts at the fleet-ready "
                           "barrier, so worker-process startup (JAX "
                           "import + compile) is excluded and inproc/"
                           "proc cells are comparable"),
            "max_gradients": t_grads,
            "budget_s": t_budget,
            "grid": run_transport_grid(t_fleets, t_ks, transports,
                                       t_grads, t_budget),
        }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    acc = report.get("acceptance")
    if acc:
        print(f"\nacceptance (worst K>=4 cell, fleet={acc['fleet']} "
              f"K={acc['K']}): pytree {acc['pytree_grads_per_s']} g/s, "
              f"slab {acc['slab_grads_per_s']} g/s -> "
              f"{acc['speedup']}x ({'PASS' if acc['pass'] else 'FAIL'})")
    print(f"wrote {args.out}")
    if args.check and acc and not acc["pass"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
