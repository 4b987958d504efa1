"""Public jit'd wrappers around the Pallas kernels.

On CPU (this container) Pallas lowers only in interpret mode, so every op
takes `interpret=None` → auto (interpret iff not on TPU).  `use_pallas=
False` falls back to the jnp reference — the default for the dry-run,
where the TPU kernels are represented by their XLA-fused references.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hybrid_aggregate import (flush_adamw_pallas,
                                            flush_momentum_pallas,
                                            flush_pallas)
from repro.kernels.rmsnorm import rmsnorm_pallas


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ flat utils
# Thin wrappers over the slab codec (repro.core.slab) — the canonical
# pytree ⇄ tile-aligned-slab layout shared by the cluster transport, the
# simulator, and these kernels.

def tree_to_flat(grads_trees: List) -> jax.Array:
    """Stack K gradient pytrees into a (K, P_padded) slab matrix (P
    padded to the kernel tile; repro.core.slab layout).  The slab wire
    dtype is float32: narrower float leaves (bf16/f16) are widened, and
    the codec rejects integer or wider-than-32-bit leaves."""
    from repro.core.slab import slab_codec
    codec = slab_codec(grads_trees[0])
    return jnp.stack([codec.encode(t) for t in grads_trees])


def flat_to_tree(flat: jax.Array, like) -> object:
    """Decode one f32 slab back into ``like``'s structure (leaves cast
    back to their template dtypes — exact for <= 32-bit floats)."""
    from repro.core.slab import slab_codec
    return slab_codec(like).decode(flat)


# ------------------------------------------------------------------- ops

@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def hybrid_flush(grads: jax.Array, weights: jax.Array, *,
                 use_pallas: bool = True,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Weighted aggregation of K flattened gradient slabs: (K,P),(K)->(P)."""
    if not use_pallas:
        return ref.flush_ref(grads, weights)
    return flush_pallas(grads, weights,
                        interpret=_auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("beta", "use_pallas", "interpret"))
def hybrid_flush_momentum(grads, weights, momentum, beta: float, *,
                          use_pallas: bool = True,
                          interpret: Optional[bool] = None):
    if not use_pallas:
        return ref.flush_momentum_ref(grads, weights, momentum, beta)
    return flush_momentum_pallas(grads, weights, momentum, beta,
                                 interpret=_auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "weight_decay",
                                    "use_pallas", "interpret"))
def hybrid_flush_adamw(grads, weights, params, mu, nu, bc1, bc2, scale,
                       *, b1: float, b2: float, eps: float,
                       weight_decay: float, use_pallas: bool = True,
                       interpret: Optional[bool] = None):
    """Fused aggregate + AdamW step: (K,P) staging rows + pre-normalized
    weights + f32 param/moment slabs -> (new_params, new_mu, new_nu).
    ``bc1``/``bc2`` are traced bias corrections (``1 - b^count``)."""
    if not use_pallas:
        return ref.flush_adamw_ref(grads, weights, params, mu, nu,
                                   bc1, bc2, scale, b1=b1, b2=b2,
                                   eps=eps, weight_decay=weight_decay)
    return flush_adamw_pallas(grads, weights, params, mu, nu, bc1, bc2,
                              scale, b1=b1, b2=b2, eps=eps,
                              weight_decay=weight_decay,
                              interpret=_auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("eps", "use_pallas", "interpret",
                                    "block_rows"))
def rmsnorm(x, scale, eps: float = 1e-5, *, use_pallas: bool = True,
            block_rows: int = 256, interpret: Optional[bool] = None):
    """x: (..., D)."""
    if not use_pallas:
        return ref.rmsnorm_ref(x, scale, eps)
    lead = x.shape[:-1]
    D = x.shape[-1]
    flat = x.reshape(-1, D)
    N = flat.shape[0]
    rows = min(block_rows, N)
    while N % rows:
        rows //= 2
    y = rmsnorm_pallas(flat, scale, eps, block_rows=max(rows, 1),
                       interpret=_auto_interpret(interpret))
    return y.reshape(*lead, D)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "q_block",
                                    "kv_block", "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_block: int = 128,
                    kv_block: int = 128, use_pallas: bool = True,
                    interpret: Optional[bool] = None):
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_block=q_block,
        kv_block=kv_block, interpret=_auto_interpret(interpret))
