"""Pallas TPU kernel: hybrid gradient-buffer flush.

The Smooth Switch flush aggregates K buffered gradient slabs into one
update with staleness weights (repro.core.buffer.aggregate_flush).  On TPU
this is a memory-bound fused weighted reduction:

    out[p] = Σ_k w[k] · g[k, p]      (+ optional fused momentum update)

Reading K gradient copies from HBM once and writing one slab keeps the op
at the HBM roofline instead of K separate axpy passes (K× fewer output
writes, no intermediate slabs).  Tiling: the parameter dimension is tiled
in (8, 128)-aligned VMEM blocks; the K axis stays resident per tile.

Layout: gradients are flattened slabs of P elements, P padded to the
tile size (repro.core.slab).  Each kernel takes them in one of two forms
with one body: stacked into a (K, P) matrix (the SPMD merge), or as K
separate (P,) rows (the parameter server, which holds each worker's slab
where it landed instead of copying it into a matrix).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_P = 8 * 128 * 8          # parameter elements per tile (VMEM-sized)


def _gradient_blocks(grads, weights, tile_p: int):
    """The gradient operands of a flush kernel and their blocks, weights
    first.  ``grads`` is either one ``(K, P)`` matrix, read in
    ``(K, tile_p)`` blocks beside ``(K, 1)`` weights (the stacked form),
    or a sequence of K ``(P,)`` rows, each read in ``(tile_p,)`` blocks
    beside ``(K,)`` weights (the rows form: rows held in separate
    buffers are read where they are, never stacked).  Returns
    ``(P, row dtype, operands, block specs)``."""
    if isinstance(grads, (list, tuple)):
        K, (P,) = len(grads), grads[0].shape
        w = weights.reshape(K).astype(jnp.float32)
        specs = ([pl.BlockSpec((K,), lambda i: (0,))]
                 + [pl.BlockSpec((tile_p,), lambda i: (i,))] * K)
        return P, grads[0].dtype, [w, *grads], specs
    K, P = grads.shape
    w = weights.reshape(K, 1).astype(jnp.float32)
    specs = [pl.BlockSpec((K, 1), lambda i: (0, 0)),
             pl.BlockSpec((K, tile_p), lambda i: (0, i))]
    return P, grads.dtype, [w, grads], specs


def _weighted_sum(w_ref, g_refs):
    """Σ_k w[k]·g[k] over one tile, in f32 (bf16 rows are upcast here,
    in VMEM).  The stacked form's one ``(K, tile)`` block is reduced
    along K; the rows form's K ``(tile,)`` blocks are folded in row
    order, the order of the jnp fallback in ``repro.core.slab``."""
    if len(g_refs) == 1 and len(g_refs[0].shape) == 2:
        g = g_refs[0][...].astype(jnp.float32)            # (K, tile)
        return jnp.sum(g * w_ref[...].astype(jnp.float32), axis=0)
    acc = w_ref[0] * g_refs[0][...].astype(jnp.float32)
    for k in range(1, len(g_refs)):
        acc = acc + w_ref[k] * g_refs[k][...].astype(jnp.float32)
    return acc


def _flush_kernel(w_ref, *refs):
    """w: weights; the gradient blocks (either form); o: (TILE_P,)."""
    *g_refs, o_ref = refs
    o_ref[...] = _weighted_sum(w_ref, g_refs).astype(o_ref.dtype)


def flush_pallas(grads, weights: jax.Array, *,
                 out_dtype=None, tile_p: int = TILE_P,
                 interpret: bool = False) -> jax.Array:
    """grads: a (K, P) matrix or a sequence of K (P,) rows, with
    P % tile_p == 0; weights: (K,) fp32 (normalized by the caller).
    Returns (P,) weighted sum in ``out_dtype`` (default the rows'
    dtype).  bf16 rows are upcast per tile in VMEM, so bf16 staging is
    read as-is and never widened in HBM."""
    P, dtype, operands, in_specs = _gradient_blocks(grads, weights, tile_p)
    assert P % tile_p == 0, (P, tile_p)
    return pl.pallas_call(
        _flush_kernel,
        grid=(P // tile_p,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_p,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((P,), out_dtype or dtype),
        interpret=interpret,
    )(*operands)


def _flush_momentum_kernel(w_ref, *refs):
    """Fused flush + momentum: m' = β·m + Σ w·g ; out = m'."""
    *g_refs, beta_ref, m_ref, o_ref, new_m_ref = refs
    m_new = beta_ref[0] * m_ref[...].astype(jnp.float32) \
        + _weighted_sum(w_ref, g_refs)
    new_m_ref[...] = m_new.astype(new_m_ref.dtype)
    o_ref[...] = m_new.astype(o_ref.dtype)


def flush_momentum_pallas(grads, weights: jax.Array,
                          momentum: jax.Array, beta: float, *,
                          out_dtype=None, tile_p: int = TILE_P,
                          interpret: bool = False):
    """Fused flush+momentum over a (K, P) matrix or K (P,) rows (see
    :func:`flush_pallas`).  Returns (update, new_momentum); the update
    is in ``out_dtype`` (default the rows' dtype)."""
    P, dtype, operands, in_specs = _gradient_blocks(grads, weights, tile_p)
    assert P % tile_p == 0
    beta_arr = jnp.full((1,), beta, jnp.float32)
    return pl.pallas_call(
        _flush_momentum_kernel,
        grid=(P // tile_p,),
        in_specs=in_specs + [
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((tile_p,), lambda i: (i,)),
            pl.BlockSpec((tile_p,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P,), out_dtype or dtype),
            jax.ShapeDtypeStruct((P,), momentum.dtype),
        ],
        interpret=interpret,
    )(*operands, beta_arr, momentum)


def _flush_adamw_kernel(w_ref, *refs, b1, b2, eps, weight_decay):
    """Fused flush + AdamW step, one HBM pass per tile.

    ``w`` is pre-normalized (the reduction yields the *mean* gradient);
    ``h = (bc1, bc2, scale)`` carries the traced scalars — the bias
    corrections ``1 - b^count`` (count-dependent, so they can't be
    baked static) and the learning-rate scale."""
    (*g_refs, h_ref, p_ref, m_ref, v_ref,
     new_p_ref, new_m_ref, new_v_ref) = refs
    bc1, bc2, scale = h_ref[0], h_ref[1], h_ref[2]
    mean_g = _weighted_sum(w_ref, g_refs)
    m_new = b1 * m_ref[...].astype(jnp.float32) + (1 - b1) * mean_g
    v_new = b2 * v_ref[...].astype(jnp.float32) \
        + (1 - b2) * mean_g * mean_g
    p = p_ref[...].astype(jnp.float32)
    upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) \
        + weight_decay * p
    new_p_ref[...] = (p - scale * upd).astype(new_p_ref.dtype)
    new_m_ref[...] = m_new.astype(new_m_ref.dtype)
    new_v_ref[...] = v_new.astype(new_v_ref.dtype)


def flush_adamw_pallas(grads, weights: jax.Array,
                       params: jax.Array, mu: jax.Array, nu: jax.Array,
                       bc1, bc2, scale, *, b1: float, b2: float,
                       eps: float, weight_decay: float,
                       tile_p: int = TILE_P, interpret: bool = False):
    """Fused flush+AdamW over a (K, P) matrix or K (P,) rows (see
    :func:`flush_pallas`).  Returns (new_params, new_mu, new_nu) — the
    moments stay in ``mu``/``nu``'s dtype (f32 on the slab path)."""
    P, _, operands, in_specs = _gradient_blocks(grads, weights, tile_p)
    assert P % tile_p == 0
    h = jnp.stack([jnp.asarray(bc1, jnp.float32),
                   jnp.asarray(bc2, jnp.float32),
                   jnp.asarray(scale, jnp.float32)])
    kern = functools.partial(_flush_adamw_kernel, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay)
    return pl.pallas_call(
        kern,
        grid=(P // tile_p,),
        in_specs=in_specs + [
            pl.BlockSpec((3,), lambda i: (0,)),
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
    )(*operands, h, params, mu, nu)
