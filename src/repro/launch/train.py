"""SPMD training driver (the ``spmd`` backend of ``repro.api``).

Modes:
  * ``sync``   — standard fully-synchronous data parallelism (the paper's
                 synchronous baseline; also the hybrid schedule's endpoint);
  * ``async``  — group size 1 throughout (per-device local SGD, the SPMD
                 analogue of the asynchronous baseline);
  * ``hybrid`` — the Smooth Switch: reduction-group size annealed by the
                 threshold schedule, replicas merged at phase switches.

The engine is :func:`run_training`, which consumes a declarative
:class:`repro.api.ExperimentSpec` (the same spec the simulator backend
consumes) and returns ``(params, history)``.  The legacy keyword surface
:func:`train` remains as a deprecation shim.

Runs on whatever devices exist (CPU tests use
XLA_FLAGS=--xla_force_host_platform_device_count=8); the same code drives
the production mesh.

Example (the end-to-end driver; equivalently ``python -m repro run
--backend spmd ...``):
  python -m repro.launch.train --arch xlstm-350m --smoke --steps 200 \
      --mode hybrid --schedule step:30
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import save_checkpoint
from repro.configs.registry import ARCH_NAMES, get_config, smoke_variant
from repro.core.spmd_hybrid import (build_phases, make_replica_step,
                                    rejoin_replicas,
                                    replica_param_shardings)
from repro.data.synthetic import token_stream
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.optim import adamw, momentum, sgd
from repro.parallel.partition import param_shardings
from repro.parallel.sharding import axis_rules


def build_hybrid_mesh(rep: int, model: int = 1) -> Mesh:
    n = jax.device_count()
    assert n % (rep * model) == 0, (n, rep, model)
    devices = np.asarray(jax.devices()).reshape(rep, n // (rep * model),
                                                model)
    return Mesh(devices, ("rep", "data", "model"))


def _shard_batch_R(batch, mesh, R):
    def f(x):
        x = np.asarray(x)
        x = x.reshape(R, x.shape[0] // R, *x.shape[1:])
        return jax.device_put(x, NamedSharding(
            mesh, P("rep", "data", *([None] * (x.ndim - 2)))))
    return jax.tree.map(f, batch)


def run_training(spec, ckpt_dir: Optional[str] = None,
                 out_json: Optional[str] = None, verbose: bool = True):
    """Run the SPMD driver from an :class:`repro.api.ExperimentSpec`.

    Returns ``(params_final, history, stats)`` where ``history`` is the
    logged list of per-step metric dicts and ``stats`` carries the
    driver's exact counters (``num_updates``, ``num_gradients`` — one
    gradient per replica per executed step, accumulated as the steps
    run, not reconstructed from the log_every-thinned history).
    ``repro.api.SpmdTrainer`` adapts all of it into the unified
    ``RunResult``.
    """
    from repro.api.schedules import parse_schedule

    cfg = get_config(spec.arch)
    if spec.smoke:
        cfg = dataclasses.replace(smoke_variant(cfg), name=cfg.name)
    assert cfg.frontend is None, "train driver uses token streams"

    n_dev = jax.device_count()
    if n_dev % spec.mesh_model != 0:
        raise ValueError(f"mesh_model={spec.mesh_model} must divide the "
                         f"device count ({n_dev})")
    data_axis = n_dev // spec.mesh_model
    # the per-replica optimizer comes from the spec — the same
    # optimizer/beta1/beta2/weight_decay fields the server-side slab
    # optimizer reads, so one spec names the update rule on every
    # backend.  (Historically this driver hard-coded AdamW; pass
    # optimizer="adamw" for that behavior.)
    if spec.optimizer == "adamw":
        opt = adamw(spec.lr, b1=spec.beta1, b2=spec.beta2,
                    weight_decay=spec.weight_decay)
    elif spec.optimizer == "momentum":
        opt = momentum(spec.lr, beta=spec.beta1)
    else:
        opt = sgd(spec.lr)
    stream = token_stream(spec.seed, cfg.vocab_size, spec.batch, spec.seq)

    # --- schedule -> group-size phases
    if spec.mode == "sync":
        phases = [(0, data_axis)]
    elif spec.mode == "async":
        phases = [(0, 1)]
    else:
        sched = parse_schedule(spec.schedule, data_axis)
        phases = [(p.t_start, p.group_size)
                  for p in build_phases(sched, spec.steps, data_axis)]

    params = M.init_params(jax.random.PRNGKey(spec.seed), cfg)

    def loss_fn(p, b):
        return M.loss_fn(p, b, cfg)

    def opt_update(grads, state, p):
        return opt.update(grads, state, p)

    history = []
    t0 = time.time()
    tokens_done = 0
    grads_done = 0
    params_R = None
    step = 0
    steps = spec.steps

    def merged(R_new: int, alpha: float = 1.0):
        """The current phase's ``params_R`` merged on its ``mesh`` and
        regrouped into ``R_new`` replicas on the mesh for ``R_new`` (the
        merge runs the same fused slab flush the parameter server
        applies)."""
        # the last step has finished before the merge's collectives
        # start: XLA-CPU's in-process communicator deadlocks if modules
        # with collectives interleave
        jax.block_until_ready(params_R)
        return rejoin_replicas(
            params_R, R_new, mesh=mesh, alpha=alpha,
            out_shardings=replica_param_shardings(
                params, build_hybrid_mesh(R_new, spec.mesh_model)))

    for idx, (t_start, g) in enumerate(phases):
        t_end = phases[idx + 1][0] if idx + 1 < len(phases) else steps
        R = max(1, data_axis // g)
        if params_R is None:
            # each device receives only its shard of the broadcast
            host = jax.device_get(params)
            params_R = jax.device_put(
                jax.tree.map(lambda x: np.broadcast_to(x[None],
                                                       (R,) + x.shape),
                             host),
                replica_param_shardings(
                    params, build_hybrid_mesh(R, spec.mesh_model)))
        else:
            # Phase switch (the paper's buffer flush): merge replicas and
            # change the group factor, as one program on the device mesh
            params_R = merged(R, alpha=spec.merge_alpha)
        mesh = build_hybrid_mesh(R, spec.mesh_model)
        devices = len(set().union(*(x.sharding.device_set
                                    for x in jax.tree.leaves(params_R))))
        with axis_rules(mesh):
            opt_R = jax.jit(jax.vmap(opt.init))(params_R)
            jax.block_until_ready((params_R, opt_R))
            replica_step = make_replica_step(loss_fn, opt_update)
            step_fn = jax.jit(replica_step, donate_argnums=(0, 1))

            while step < t_end:
                b = next(stream)
                b_R = _shard_batch_R(b, mesh, R)
                params_R, opt_R, metrics = step_fn(params_R, opt_R, b_R)
                tokens_done += spec.batch * spec.seq
                grads_done += R     # one gradient per replica this step
                if step % spec.log_every == 0 or step == t_end - 1:
                    div = float(metrics["divergence"]) if R > 1 else 0.0
                    # the executable reports its own replica axis; it must
                    # agree with the R this phase launched
                    assert int(metrics["replicas"]) == R, \
                        (int(metrics["replicas"]), R)
                    rec = {"step": step, "group_size": g, "replicas": R,
                           "devices": devices,
                           "loss": float(metrics["loss"]),
                           "divergence": div,
                           "wall_s": round(time.time() - t0, 2),
                           "tokens": tokens_done}
                    history.append(rec)
                    if verbose:
                        print(f"step {step:5d}  g={g:3d} R={R:3d} "
                              f"loss={rec['loss']:.4f} div={div:.3e}",
                              flush=True)
                step += 1

            jax.block_until_ready((params_R, opt_R))
            if ckpt_dir:
                one = jax.tree.map(lambda x: np.asarray(x[0]),
                                   jax.device_get(merged(1)))
                save_checkpoint(os.path.join(ckpt_dir, f"step_{step}"),
                                one, step, extra={"arch": spec.arch,
                                                  "mode": spec.mode})

    # final merge for the returned model
    params_final = jax.tree.map(lambda x: np.asarray(x[0]),
                                jax.device_get(merged(1)))
    stats = {"num_updates": step, "num_gradients": grads_done}
    if out_json:
        with open(out_json, "w") as f:
            json.dump({"arch": spec.arch, "mode": spec.mode,
                       "spec": spec.to_dict(), "stats": stats,
                       "history": history}, f, indent=2)
    return params_final, history, stats


def _legacy_schedule_spec(schedule_kind: str, step_size: int,
                          steps: int) -> str:
    """Map the old (schedule_kind, step_size) kwargs onto a spec string —
    the branch the old driver hard-coded (``step`` took a step size while
    every other family took the step horizon)."""
    if schedule_kind == "step":
        return f"step:{step_size}"
    return f"{schedule_kind}:horizon={steps}"


def train(arch: str, steps: int, mode: str, batch: int, seq: int,
          lr: float, schedule_kind: str, step_size: int, smoke: bool,
          merge_alpha: float = 1.0, log_every: int = 10,
          ckpt_dir: Optional[str] = None, seed: int = 0,
          out_json: Optional[str] = None):
    """Deprecated keyword surface; use ``repro.api`` (ExperimentSpec ->
    run()) or :func:`run_training` directly."""
    from repro.api.spec import ExperimentSpec

    warnings.warn(
        "repro.launch.train.train(...) is deprecated; build a "
        "repro.api.ExperimentSpec and call repro.api.run() or "
        "run_training()", DeprecationWarning, stacklevel=2)
    spec = ExperimentSpec(
        arch=arch, backend="spmd", mode=mode,
        schedule=_legacy_schedule_spec(schedule_kind, step_size, steps)
        if mode == "hybrid" else None,
        seed=seed, lr=lr, batch=batch, steps=steps, seq=seq,
        merge_alpha=merge_alpha, smoke=smoke, log_every=log_every)
    params, history, _ = run_training(spec, ckpt_dir=ckpt_dir,
                                      out_json=out_json)
    return params, history   # the legacy (params, history) contract


def main(argv=None):
    from repro.api.spec import ExperimentSpec

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="xlstm-350m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mode", choices=("sync", "async", "hybrid"),
                    default="hybrid")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", default="step",
                    help='schedule spec, e.g. "step:30" or '
                         '"cosine:horizon=200" (a bare family name combines '
                         "with --step-size/--steps, legacy style)")
    ap.add_argument("--step-size", type=int, default=30,
                    help="legacy: step size when --schedule is a bare "
                         "family name")
    ap.add_argument("--merge-alpha", type=float, default=1.0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out-json", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    schedule = args.schedule
    if schedule and ":" not in schedule:
        schedule = _legacy_schedule_spec(schedule, args.step_size,
                                         args.steps)
    try:
        spec = ExperimentSpec(
            arch=args.arch, backend="spmd", mode=args.mode,
            schedule=schedule if args.mode == "hybrid" else None,
            seed=args.seed, lr=args.lr, batch=args.batch, steps=args.steps,
            seq=args.seq, merge_alpha=args.merge_alpha, smoke=args.smoke)
    except ValueError as e:
        ap.error(str(e))     # clean CLI error, as the old choices= gave
    run_training(spec, ckpt_dir=args.ckpt_dir, out_json=args.out_json)


if __name__ == "__main__":
    main()
