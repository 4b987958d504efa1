"""What decides ``correct`` in a training cell.

The program's first three updates are replayed by the plain reference
(``reference.py``) on the same weights, the same rows and the same
schedule: which worker's i-th batch went into which update, computed
against which parameter version.  Two numbers are compared by their
worst leaf:

* ``grad1_gap``: the gradient of the first update as the optimizer got
  it, ``(w0 - w1) / lr`` read from the server's float32 master after one
  update, against the reference's sum of that update's gradients;
* ``change3_gap``: the parameters' change after three updates,
  ``w3 - w0``, against the reference's.

A leaf's gap is ``| |prog| - |ref| |`` over the larger of its reference
norm and the median leaf's.  Leaves whose reference gradient is under a
thousandth of the median leaf's are left out of both: they move by
round-off alone.

A third number is exact: ``publish_mismatch``, the elements in which
the copy published to the workers after updates 1 and 3 differs from
the server's float32 master at that point cast to the slab dtype.  Three
updates move a weight far less than half a bfloat16 step, so a stale
publish changes the workers' gradients too little for the first two
numbers to see.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

STEPS = 3
QUIET = 1e-3


def worst_leaf_gap(prog: Sequence[float], ref: Sequence[float],
                   keep: np.ndarray) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    denom = np.maximum(ref, np.median(ref[keep]))
    return float(np.max(np.abs(prog - ref)[keep] / denom[keep]))


def kept_leaves(ref_grad1: Sequence[float]) -> np.ndarray:
    ref = np.asarray(ref_grad1, np.float64)
    return ref >= QUIET * np.median(ref)


def leaf_norms_fn():
    """jitted tree -> (n_leaves,) float32 L2 norms."""
    import jax
    import jax.numpy as jnp

    def f(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32))))
                          for t in jax.tree.leaves(tree)])
    return jax.jit(f)


def replay(grad: Callable, w0, flushes: List[List[Tuple[int, int, int]]],
           batch_of: Callable[[int, int], Tuple], lr: float,
           wire_dtype) -> Dict[str, np.ndarray]:
    """The reference's first ``STEPS`` updates of plain SGD, following
    ``flushes``: for update v, the ``(worker, seq, version)`` of each
    gradient in it.  A gradient is taken at the parameters of its
    version as the wire carries them (rounded to ``wire_dtype``) on
    ``batch_of(worker, seq)``; an update subtracts ``lr`` times the sum
    of its gradients from the float32 parameters.

    Returns the per-leaf norms of the first update's summed gradient
    (``grad1``) and of ``w3 - w0`` (``change3``)."""
    import jax
    import jax.numpy as jnp

    norms = leaf_norms_fn()
    f32 = lambda t: t.astype(jnp.float32)                 # noqa: E731
    wire = jax.jit(lambda tree: jax.tree.map(
        lambda t: f32(t).astype(wire_dtype), tree))
    sgd = jax.jit(lambda p, g: jax.tree.map(lambda a, b: a - lr * b, p, g))
    w = jax.tree.map(f32, w0)
    seen = {0: wire(w)}
    out: Dict[str, np.ndarray] = {}
    for v, members in enumerate(flushes[:STEPS], start=1):
        total = None
        for worker, seq, version in members:
            x, y = batch_of(worker, seq)
            _, g = grad(seen[version], x, y)
            total = g if total is None else jax.tree.map(jnp.add, total, g)
        if v == 1:
            out["grad1"] = np.asarray(norms(total))
        w = sgd(w, total)
        seen[v] = wire(w)
    out["change3"] = np.asarray(norms(jax.tree.map(
        lambda a, b: a - f32(b), w, w0)))
    return out


def verdict(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            lr: float, limits: Dict[str, float]) -> Dict[str, Dict]:
    """``{name: {"value", "limit"}}`` for each number compared.  ``prog``
    holds the program's per-leaf ``|w1 - w0|`` (``update1``) and
    ``|w3 - w0|`` (``change3``) and, read from a run, the published
    copy's mismatched elements (``publish``)."""
    keep = kept_leaves(ref["grad1"])
    values = {
        "grad1_gap": worst_leaf_gap(prog["update1"] / lr, ref["grad1"],
                                    keep),
        "change3_gap": worst_leaf_gap(prog["change3"], ref["change3"],
                                      keep),
    }
    if "publish" in prog:
        values["publish_mismatch"] = prog["publish"]
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def passed(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
