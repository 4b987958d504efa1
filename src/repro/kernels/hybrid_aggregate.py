"""Pallas TPU kernel: hybrid gradient-buffer flush.

The Smooth Switch flush aggregates K buffered gradient slabs into one
update with staleness weights (repro.core.buffer.aggregate_flush).  On TPU
this is a memory-bound fused weighted reduction:

    out[p] = Σ_k w[k] · g[k, p]      (+ optional fused momentum update)

Reading K gradient copies from HBM once and writing one slab keeps the op
at the HBM roofline instead of K separate axpy passes (K× fewer output
writes, no intermediate slabs).  Tiling: the parameter dimension is tiled
in (8, 128)-aligned VMEM blocks; the K axis stays resident per tile.

Layout: gradients are flattened & concatenated to (K, P); P is padded to
the tile size by the ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_P = 8 * 128 * 8          # parameter elements per tile (VMEM-sized)


def _flush_kernel(w_ref, g_ref, o_ref):
    """w: (K, 1) fp32 in SMEM-ish VMEM; g: (K, TILE_P); o: (TILE_P,)."""
    g = g_ref[...].astype(jnp.float32)            # (K, tile)
    w = w_ref[...].astype(jnp.float32)            # (K, 1)
    o_ref[...] = jnp.sum(g * w, axis=0).astype(o_ref.dtype)


def flush_pallas(grads: jax.Array, weights: jax.Array, *,
                 out_dtype=None, tile_p: int = TILE_P,
                 interpret: bool = False) -> jax.Array:
    """grads: (K, P) with P % tile_p == 0; weights: (K,) fp32 (normalized
    by the caller).  Returns (P,) weighted sum in ``out_dtype`` (default
    grads.dtype).  bf16 rows are upcast per tile in VMEM, so a bf16
    staging buffer is read as-is and never widened in HBM."""
    K, P = grads.shape
    assert P % tile_p == 0, (P, tile_p)
    w2 = weights.reshape(K, 1).astype(jnp.float32)
    return pl.pallas_call(
        _flush_kernel,
        grid=(P // tile_p,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, tile_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((tile_p,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((P,), out_dtype or grads.dtype),
        interpret=interpret,
    )(w2, grads)


def flush_pallas_sharded(grad_chunks, weights: jax.Array, *,
                         tile_p: int = TILE_P,
                         interpret: bool = False):
    """Sharded flush entry point: ``grad_chunks`` is a sequence of
    ``(K, P_i)`` staging chunks (each ``P_i % tile_p == 0`` — the
    tile-aligned P-split of one ``(K, P)`` slab, see
    :func:`repro.core.slab.shard_chunks`).  Each chunk is reduced by its
    own :func:`flush_pallas` call, so under ``jax.jit`` a fleet of
    equal-shaped chunks shares **one** compiled executable per distinct
    chunk shape — the single-donated-executable property, per chunk.
    The reduction is elementwise along P, so the concatenated result is
    bitwise identical to an unsharded flush of the whole slab."""
    return [flush_pallas(g, weights, tile_p=tile_p, interpret=interpret)
            for g in grad_chunks]


def _flush_momentum_kernel(w_ref, beta_ref, g_ref, m_ref, o_ref, new_m_ref):
    """Fused flush + momentum: m' = β·m + Σ w·g ; out = m'."""
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    beta = beta_ref[0]
    agg = jnp.sum(g * w, axis=0)
    m_new = beta * m_ref[...].astype(jnp.float32) + agg
    new_m_ref[...] = m_new.astype(new_m_ref.dtype)
    o_ref[...] = m_new.astype(o_ref.dtype)


def flush_momentum_pallas(grads: jax.Array, weights: jax.Array,
                          momentum: jax.Array, beta: float, *,
                          out_dtype=None, tile_p: int = TILE_P,
                          interpret: bool = False):
    """Fused flush+momentum.  Returns (update, new_momentum); the update
    is in ``out_dtype`` (default grads.dtype)."""
    K, P = grads.shape
    assert P % tile_p == 0
    w2 = weights.reshape(K, 1).astype(jnp.float32)
    beta_arr = jnp.full((1,), beta, jnp.float32)
    return pl.pallas_call(
        _flush_momentum_kernel,
        grid=(P // tile_p,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((K, tile_p), lambda i: (0, i)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((tile_p,), lambda i: (i,)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P,), out_dtype or grads.dtype),
            jax.ShapeDtypeStruct((P,), momentum.dtype),
        ],
        interpret=interpret,
    )(w2, beta_arr, grads, momentum)


def _flush_adamw_kernel(w_ref, h_ref, g_ref, p_ref, m_ref, v_ref,
                        new_p_ref, new_m_ref, new_v_ref, *,
                        b1, b2, eps, weight_decay):
    """Fused flush + AdamW step, one HBM pass per tile.

    ``w`` is pre-normalized (the reduction yields the *mean* gradient);
    ``h = (bc1, bc2, scale)`` carries the traced scalars — the bias
    corrections ``1 - b^count`` (count-dependent, so they can't be
    baked static) and the learning-rate scale."""
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    bc1, bc2, scale = h_ref[0], h_ref[1], h_ref[2]
    mean_g = jnp.sum(g * w, axis=0)
    m_new = b1 * m_ref[...].astype(jnp.float32) + (1 - b1) * mean_g
    v_new = b2 * v_ref[...].astype(jnp.float32) \
        + (1 - b2) * mean_g * mean_g
    p = p_ref[...].astype(jnp.float32)
    upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) \
        + weight_decay * p
    new_p_ref[...] = (p - scale * upd).astype(new_p_ref.dtype)
    new_m_ref[...] = m_new.astype(new_m_ref.dtype)
    new_v_ref[...] = v_new.astype(new_v_ref.dtype)


def flush_adamw_pallas(grads: jax.Array, weights: jax.Array,
                       params: jax.Array, mu: jax.Array, nu: jax.Array,
                       bc1, bc2, scale, *, b1: float, b2: float,
                       eps: float, weight_decay: float,
                       tile_p: int = TILE_P, interpret: bool = False):
    """Fused flush+AdamW.  Returns (new_params, new_mu, new_nu) — the
    moments stay in ``mu``/``nu``'s dtype (f32 on the slab path)."""
    K, P = grads.shape
    assert P % tile_p == 0
    w2 = weights.reshape(K, 1).astype(jnp.float32)
    h = jnp.stack([jnp.asarray(bc1, jnp.float32),
                   jnp.asarray(bc2, jnp.float32),
                   jnp.asarray(scale, jnp.float32)])
    kern = functools.partial(_flush_adamw_kernel, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay)
    return pl.pallas_call(
        kern,
        grid=(P // tile_p,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((3,), lambda i: (0,)),
            pl.BlockSpec((K, tile_p), lambda i: (0, i)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((tile_p,), lambda i: (i,)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P,), params.dtype),
            jax.ShapeDtypeStruct((P,), mu.dtype),
            jax.ShapeDtypeStruct((P,), nu.dtype),
        ],
        interpret=interpret,
    )(w2, h, grads, params, mu, nu)
