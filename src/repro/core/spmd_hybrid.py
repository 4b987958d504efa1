"""Group-size-annealed data parallelism — the TPU-native Smooth Switch.

The paper's threshold K(t) ("how many gradients aggregate per update")
maps onto SPMD as the *reduction-group size* of data parallelism:

  * the data-parallel mesh axis is factored into R replica groups of size
    g = axis/R.  Parameters carry an explicit leading replica axis of size
    R, sharded over the ``rep`` mesh axis, so each group owns an
    independent replica (sharded FSDP-style *within* the group);
  * a train step computes per-replica gradients with ``jax.vmap`` over the
    replica axis — XLA reduces batch gradients only *inside* each group
    (the SPMD analogue of "K gradients aggregated per update");
  * groups evolve independently ("async": divergence ≙ staleness) until a
    **merge**, where replicas are averaged (all-reduce over ``rep``) — the
    analogue of the paper's buffer flush;
  * the threshold schedule anneals g: 1 → axis (R: axis → 1), finishing in
    standard fully-synchronous data parallelism.

Memory honesty: a replica group of size g holds params/optimizer sharded
over only g×model chips, so per-chip bytes scale with 1/g.  Big models
therefore have a g_min below which the hybrid phase cannot start — reported
by `min_group_size` and recorded in EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.schedule import ThresholdSchedule, group_size_phases


def factored_mesh(devices: np.ndarray, rep: int, axis_names=("rep", "data",
                                                             "model")):
    """Reshape a (data, model) device grid into (rep, data/rep, model)."""
    d, m = devices.shape[-2], devices.shape[-1]
    flat = devices.reshape(-1, d, m)
    pods = flat.shape[0]
    assert (pods * d) % rep == 0, (pods, d, rep)
    grid = devices.reshape(rep, (pods * d) // rep, m)
    return Mesh(grid, axis_names)


def replicate_params(params, R: int):
    """Add the leading replica axis (same initial values in every group)."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (R,) + p.shape),
                        params)


def merge_replicas(params_R, alpha: float = 1.0):
    """Flush: average replicas (all-reduce over ``rep`` once sharded).

    alpha < 1 gives a partial (Lookahead-style) merge — a beyond-paper
    extension: θ_r ← α·mean + (1-α)·θ_r.

    This is the per-leaf reference; the train driver's phase switches
    use :func:`merge_replicas_slab`, which routes the same reduction
    through the slab aggregation path (the Pallas flush kernel on TPU).
    """
    def m(p):
        mean = jnp.mean(p, axis=0, keepdims=True)
        return alpha * jnp.broadcast_to(mean, p.shape) + (1 - alpha) * p
    return jax.tree.map(m, params_R)


def merge_replicas_slab(params_R, alpha: float = 1.0, *,
                        mesh: Optional[Mesh] = None,
                        use_pallas: Optional[bool] = None,
                        interpret: Optional[bool] = None):
    """The hybrid flush on the slab path: replicas are encoded into an
    ``(R, P)`` slab matrix and averaged by the same fused weighted
    reduction the parameter server's flush uses
    (:func:`repro.kernels.ops.hybrid_flush` → ``flush_pallas`` on TPU,
    the jnp reference elsewhere), then decoded and α-blended exactly
    like :func:`merge_replicas`.

    With ``mesh`` (traced under ``jit`` with the replicas laid out over
    the mesh, see :func:`rejoin_replicas`) the slab matrix is spread
    along P over every device of the mesh, and each device reduces all
    R rows of its own P range: the reduction is elementwise along P, so
    the result is the same, and no device ever holds R whole
    replicas."""
    from repro.core.slab import slab_codec
    from repro.kernels import ops
    from repro.kernels.hybrid_aggregate import TILE_P

    codec = slab_codec(jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype), params_R))
    R = jax.tree.leaves(params_R)[0].shape[0]
    rows = jax.vmap(codec.encode)(params_R)          # (R, P_pad)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"

    def flush(r):
        return ops.hybrid_flush(r, jnp.ones((R,), jnp.float32),
                                use_pallas=use_pallas, interpret=interpret)

    if mesh is None:
        total = flush(rows)
    else:
        # every device gets a whole number of kernel tiles of P
        spread = P(None, mesh.axis_names)
        rows = jnp.pad(rows, ((0, 0),
                              (0, -rows.shape[1] % (mesh.size * TILE_P))))
        rows = jax.lax.with_sharding_constraint(
            rows, NamedSharding(mesh, spread))
        total = jax.shard_map(flush, mesh=mesh, in_specs=spread,
                              out_specs=P(mesh.axis_names),
                              check_vma=False)(rows)
        # one gather of the mean slab, then every device decodes it
        # locally (slicing a P-spread slab per leaf would reshard each)
        total = jax.lax.with_sharding_constraint(
            total, NamedSharding(mesh, P()))[:codec.padded_size]
    mean_tree = codec.decode(total / R)

    def m(mean_leaf, p):
        mean_b = jnp.broadcast_to(mean_leaf[None], p.shape)
        return alpha * mean_b + (1 - alpha) * p
    return jax.tree.map(m, mean_tree, params_R)


def rejoin_replicas(params_R, R_new: int, *, mesh: Mesh, out_shardings,
                    alpha: float = 1.0):
    """A phase switch on the device mesh: merge the replicas of
    ``params_R`` (laid out over ``mesh``) with
    :func:`merge_replicas_slab`, regroup them into ``R_new``
    (:func:`reshard_replicas`), and place the result on
    ``out_shardings`` (which may belong to another mesh over the same
    devices) — one program, with no host round trip and no device
    holding more than its share of the replicas.  One replica at
    ``alpha=1`` is its own mean, exactly, so only the regrouping runs."""
    if jax.tree.leaves(params_R)[0].shape[0] == 1 and alpha == 1.0:
        merge = lambda p: p  # noqa: E731
    else:
        merge = functools.partial(merge_replicas_slab, alpha=alpha,
                                  mesh=mesh)
    return jax.jit(lambda p: reshard_replicas(merge(p), R_new),
                   out_shardings=out_shardings)(params_R)


def reshard_replicas(params_R, R_new: int):
    """Change the replica count at a phase switch: merge down (average
    consecutive groups) or split up (broadcast copies)."""
    R_old = jax.tree.leaves(params_R)[0].shape[0]
    if R_new == R_old:
        return params_R
    if R_new < R_old:
        assert R_old % R_new == 0
        f = R_old // R_new
        return jax.tree.map(
            lambda p: jnp.mean(p.reshape((R_new, f) + p.shape[1:]), axis=1),
            params_R)
    assert R_new % R_old == 0
    f = R_new // R_old
    return jax.tree.map(
        lambda p: jnp.repeat(p, f, axis=0), params_R)


def make_replica_step(loss_fn: Callable, opt_update: Callable):
    """Build train_step(params_R, opt_R, batch_R) -> (params, opt, metrics).

    loss_fn(params, batch) -> (loss, metrics); opt_update(grads, opt,
    params) -> (updates, new_opt).  Everything is vmapped over the leading
    replica axis, so under a ("rep","data","model") mesh the gradient
    all-reduce stays inside each replica group.
    """
    def one(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        updates, new_opt = opt_update(grads, opt_state, params)
        new_params = jax.tree.map(lambda p, u: p + u, params, updates)
        return new_params, new_opt, loss, metrics

    def step(params_R, opt_R, batch_R):
        new_p, new_o, loss, metrics = jax.vmap(one)(params_R, opt_R, batch_R)
        # divergence computed inside the same executable: a second eager
        # SPMD module with collectives can interleave with the next step's
        # module across device threads and deadlock XLA-CPU's in-process
        # communicator (and costs an extra launch on TPU).
        # "replicas" is the gradient count of this step (one gradient per
        # replica group), reported by the executable itself so the
        # driver's exact num_gradients accounting is grounded in what
        # actually ran, not in what the host believes it launched.
        return new_p, new_o, {"loss": jnp.mean(loss),
                              "loss_per_replica": loss,
                              "replicas": jnp.asarray(loss.shape[0],
                                                      jnp.int32),
                              "divergence": replica_divergence(new_p), **{
            k: jnp.mean(v) for k, v in metrics.items()}}

    return step


@dataclasses.dataclass
class HybridPhase:
    t_start: int
    group_size: int
    num_replicas: int


def build_phases(schedule: ThresholdSchedule, horizon: int,
                 data_axis: int, g_min: int = 1) -> List[HybridPhase]:
    """Threshold schedule -> [(t_start, g, R)] with g clamped to >= g_min."""
    phases = []
    for t_start, g in group_size_phases(schedule, horizon, data_axis):
        g = max(g, g_min)
        R = data_axis // g
        if phases and phases[-1].group_size == g:
            continue
        phases.append(HybridPhase(t_start, g, R))
    if not phases or phases[0].t_start > 0:
        phases.insert(0, HybridPhase(0, max(g_min, 1),
                                     data_axis // max(g_min, 1)))
    return phases


def min_group_size(param_bytes: int, opt_bytes: int, model_axis: int,
                   hbm_per_chip: int = 16 * 2 ** 30,
                   act_budget_frac: float = 0.5) -> int:
    """Smallest replica-group size whose per-chip state fits in HBM."""
    budget = hbm_per_chip * (1 - act_budget_frac)
    g = 1
    while (param_bytes + opt_bytes) / (g * model_axis) > budget:
        g *= 2
    return g


def replica_param_shardings(params_template, mesh):
    """Shardings for replicated params: leading replica axis over ``rep``,
    inner dims per the logical partition rules (FSDP over ``data`` within
    each group, tensor over ``model``) — sanitized for divisibility."""
    from repro.parallel.partition import (param_logical_tree,
                                          sanitize_sharding)
    from repro.parallel.sharding import axis_rules, logical_spec

    with axis_rules(mesh):
        logical = param_logical_tree(params_template)

        def to_sharding(names, leaf):
            spec = logical_spec(names)
            full = P("rep", *spec)
            return sanitize_sharding(NamedSharding(mesh, full),
                                     (0,) + tuple(leaf.shape))

        flat_n = jax.tree.leaves(
            logical, is_leaf=lambda v: isinstance(v, tuple) and all(
                isinstance(e, (str, type(None))) for e in v))
        flat_p, treedef = jax.tree_util.tree_flatten(params_template)
        shardings = [to_sharding(n, p) for n, p in zip(flat_n, flat_p)]
        return jax.tree_util.tree_unflatten(treedef, shardings)


def replica_divergence(params_R) -> jnp.ndarray:
    """Mean L2 distance of replicas from their mean — the SPMD analogue of
    the paper's staleness (how far apart the groups have drifted)."""
    def d(p):
        mean = jnp.mean(p, axis=0, keepdims=True)
        return jnp.sum(jnp.square(p - mean))
    total = sum(jax.tree.leaves(jax.tree.map(d, params_R)))
    return jnp.sqrt(total)
