"""Pallas flush kernel (hybrid_aggregate) validation — interpret-mode
execution vs the pure-jnp oracles, swept over shapes/dtypes, plus the
zero-weight masking contract the slab aggregation path relies on.

This file is the CI anchor for the gradient hot path: it runs with
``interpret=True`` on CPU on every push, so the kernel that carries the
server's flush traffic on TPU is exercised everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.hybrid_aggregate import TILE_P

I = dict(interpret=True)


@pytest.mark.parametrize("K", [1, 2, 7, 25])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flush_shapes_dtypes(K, dtype):
    P = TILE_P * (1 if K > 2 else 2)
    g = jax.random.normal(jax.random.PRNGKey(K), (K, P)).astype(dtype)
    w = jax.random.uniform(jax.random.PRNGKey(K + 1), (K,), jnp.float32)
    w = w / jnp.sum(w)
    out = ops.hybrid_flush(g, w, **I)
    want = ref.flush_ref(g, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("beta", [0.0, 0.9])
def test_flush_momentum(beta):
    K, P = 4, TILE_P
    g = jax.random.normal(jax.random.PRNGKey(0), (K, P))
    w = jnp.full((K,), 1.0 / K)
    m = jax.random.normal(jax.random.PRNGKey(1), (P,))
    u, m2 = ops.hybrid_flush_momentum(g, w, m, beta, **I)
    ur, mr = ref.flush_momentum_ref(g, w, m, beta)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ur), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(mr), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("wd", [0.0, 0.01])
@pytest.mark.parametrize("count", [1, 10])
def test_flush_adamw(wd, count):
    """Fused aggregate+AdamW kernel vs the pure-jnp oracle: params and
    both moment slabs, including bias correction and decoupled weight
    decay."""
    from repro.optim import bias_correction
    K, P = 4, TILE_P
    b1, b2, eps, scale = 0.9, 0.95, 1e-8, 0.01
    g = jax.random.normal(jax.random.PRNGKey(0), (K, P))
    w = jnp.full((K,), 1.0 / K)
    p = jax.random.normal(jax.random.PRNGKey(1), (P,))
    m = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (P,))
    v = 0.01 * jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (P,)))
    bc1, bc2 = bias_correction(count, b1, b2)
    got = ops.hybrid_flush_adamw(g, w, p, m, v, bc1, bc2, scale,
                                 b1=b1, b2=b2, eps=eps, weight_decay=wd,
                                 **I)
    want = ref.flush_adamw_ref(g, w, p, m, v, bc1, bc2, scale,
                               b1=b1, b2=b2, eps=eps, weight_decay=wd)
    for got_a, want_a, name in zip(got, want, ("params", "mu", "nu")):
        np.testing.assert_allclose(np.asarray(got_a), np.asarray(want_a),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


@settings(max_examples=20, deadline=None)
@given(K=st.integers(1, 8), seed=st.integers(0, 2 ** 16),
       uniform=st.booleans())
def test_flush_property_conservation(K, seed, uniform):
    """Property: with uniform weights the flush equals the mean; the flush
    is linear in the weights (paper's aggregation semantics)."""
    P = TILE_P
    g = jax.random.normal(jax.random.PRNGKey(seed), (K, P))
    if uniform:
        w = jnp.full((K,), 1.0 / K)
        out = ops.hybrid_flush(g, w, **I)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.mean(g, 0)),
                                   rtol=1e-5, atol=1e-5)
    else:
        w = jax.random.uniform(jax.random.PRNGKey(seed + 1), (K,)) + 0.1
        o1 = ops.hybrid_flush(g, w, **I)
        o2 = ops.hybrid_flush(g, 2.0 * w, **I)
        np.testing.assert_allclose(np.asarray(o2), 2 * np.asarray(o1),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [1, 3, 6])
def test_flush_zero_weight_masking(k):
    """The slab server's one-executable contract: rows past k carry
    weight 0 and contribute exactly nothing, even when they hold stale
    garbage from earlier flushes."""
    K_max, P = 6, TILE_P
    g = jax.random.normal(jax.random.PRNGKey(k), (K_max, P))
    garbage = g.at[k:].set(1e30)              # stale rows, finite junk
    w = jnp.zeros((K_max,), jnp.float32).at[:k].set(
        jax.random.uniform(jax.random.PRNGKey(k + 7), (k,)) + 0.1)
    out = ops.hybrid_flush(garbage, w, **I)
    want = ref.flush_ref(g[:k], w[:k])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flush_matches_buffer_oracle():
    """The kernel implements repro.core.buffer.aggregate_flush."""
    from repro.core.buffer import aggregate_flush
    trees = [{"a": jax.random.normal(jax.random.PRNGKey(i), (300,)),
              "b": jax.random.normal(jax.random.PRNGKey(i + 9), (11, 7))}
             for i in range(3)]
    w = np.array([0.2, 0.5, 0.3])
    want = aggregate_flush(trees, w)
    mat = ops.tree_to_flat(trees)
    out_flat = ops.hybrid_flush(mat, jnp.asarray(w / w.sum()), **I)
    got = ops.flat_to_tree(out_flat, trees[0])
    for k in ("a", "b"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("kernel", ["flush", "momentum", "adamw"])
def test_rows_form_matches_stacked_form(kernel, K):
    """Each kernel takes its K gradient rows either stacked in a (K, P)
    matrix or as K separate (P,) buffers (the server's held slabs): the
    two forms compute the same flush, to the rounding of a K-axis
    reduction against a row-by-row fold."""
    from repro.kernels.hybrid_aggregate import (flush_adamw_pallas,
                                                flush_momentum_pallas,
                                                flush_pallas)
    P = 2 * TILE_P
    g = jax.random.normal(jax.random.PRNGKey(K), (K, P)).astype(
        jnp.bfloat16)
    w = jax.random.uniform(jax.random.PRNGKey(K + 1), (K,)) + 0.1
    w = w / jnp.sum(w)
    p = jax.random.normal(jax.random.PRNGKey(2), (P,))
    m = 0.1 * p
    v = 0.01 * jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (P,)))

    def call(grads):
        if kernel == "flush":
            return (flush_pallas(grads, w, out_dtype=jnp.float32, **I),)
        if kernel == "momentum":
            return flush_momentum_pallas(grads, w, m, 0.9,
                                         out_dtype=jnp.float32, **I)
        return flush_adamw_pallas(grads, w, p, m, v, 0.1, 0.05, 0.01,
                                  b1=0.9, b2=0.95, eps=1e-8,
                                  weight_decay=0.01, **I)

    for got, want in zip(call([g[i] for i in range(K)]), call(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)
