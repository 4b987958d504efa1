"""Inputs and weights from ``--seed``: the benchmark's own generator.

The program under test receives only what is made here.  The same seed
gives the same rows, the same weights and the same batch order; any
whole number up to 2**63 is a seed.
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, Tuple

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng((int(seed), *stream))


def token_rows(seed: int, traffic: Dict[str, Any], vocab: int):
    """``(x_tr, y_tr, x_te, y_te)`` int32 rows of ``traffic["seq"]``
    tokens.  The label is the next symbol, ``(token + 1) mod vocab``,
    the zoo's task, copied here so the traffic cannot move with the
    program."""
    n_tr, n_te, seq = traffic["train_rows"], traffic["test_rows"], \
        traffic["seq"]
    x = rng(seed, 1).integers(0, vocab, (n_tr + n_te, seq), dtype=np.int32)
    y = ((x.astype(np.int64) + 1) % vocab).astype(np.int32)
    return x[:n_tr], y[:n_tr], x[n_tr:], y[n_tr:]


def worker_batches(x: np.ndarray, y: np.ndarray, worker_id: int,
                   num_workers: int, batch: int, seed: int,
                   generation: int = 0) -> Iterator[Tuple[np.ndarray,
                                                          np.ndarray]]:
    """The cluster runtime's feed, as documented for
    ``repro.data.pipeline.shard_iterator``: worker ``w`` draws ``batch``
    rows with replacement from rows ``w, w+W, w+2W, ...`` with numpy's
    generator seeded ``(seed, w, generation)``.  The reference replays a
    worker's i-th batch from this copy."""
    idx = np.arange(worker_id, x.shape[0], num_workers)
    r = np.random.default_rng((seed, worker_id, generation))
    while True:
        take = r.choice(idx, size=batch, replace=True)
        yield x[take], y[take]


def _leaf_init(path: str, shape, dtype, key):
    """One leaf: norm scales 1, biases 0 (forget-gate bias 3), every
    other weight normal with std ``fan_in ** -0.5``.  Leaves under
    ``groups`` carry the group axis first."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    body = shape[1:] if path.startswith("groups/") else shape
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    if name in ("b", "conv_b", "bq", "bk", "bv"):
        return jnp.zeros(shape, dtype)
    if name == "b_if":
        return jnp.zeros(shape, dtype).at[..., 1].set(3.0)
    if name == "wo":                       # (H, hd, d): both leading in
        fan_in = body[0] * body[1]
    elif name == "r_h":                    # (H, dh, 4, dh)
        fan_in = body[1]
    elif name == "embed":                  # rows are looked up; as a tied
        fan_in = body[1]                   # head it contracts d_model
    else:
        fan_in = body[0]
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def weights(seed: int, template) -> Any:
    """Weights in the program's parameter layout (``template``: a tree of
    ``ShapeDtypeStruct``), made on the device in one jitted call in the
    dtype each leaf is trained in."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in p) for p, _ in flat]
    seed32 = int(rng(seed, 2).integers(0, 2 ** 31 - 1))

    # the key is an argument, not a constant of the program: one compile
    # serves every seed
    def make(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf_init(path, leaf.shape, leaf.dtype, k)
                  for path, (_, leaf), k in zip(paths, flat, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(jax.random.PRNGKey(seed32))
