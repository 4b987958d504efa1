"""Work a step needs, from a configuration's ``shape`` block.

``train_flops_per_token``: the multiply-adds (x2) that forward and
backward passes need per token: 6 x the parameters that enter a matrix
product (every projection, the untied or tied head; not the embedding
lookup, norms or biases), plus each block's products over the context
(attention's scores and weighted values).  Recomputation under remat is
not counted.  Each block of ``block_pattern`` counts itself in
``blocks/<name>.py`` (``matmul_params(shape)`` per layer and, where it
has one, ``context_flops(shape, seq)`` per token); a block with no such
file makes the count None, and the metrics that need it read nothing.

``flush_bytes``: what any flush-and-apply of ``k_live`` gradient rows
must move: the live rows in the staging dtype, the f32 master read and
written, and the published copy in the slab dtype.
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict, Optional

DTYPE_BYTES = {"f32": 4, "bf16": 2}
BLOCKS = Path(__file__).resolve().parent / "blocks"


def _blocks(shape: Dict[str, Any]):
    """The block modules of one group, in order; None if one has none."""
    import harness

    mods = []
    for pair in shape["block_pattern"]:
        for name in pair:
            if name == "none":
                continue
            if not (BLOCKS / f"{name}.py").is_file():
                return None
            mods.append(harness.load_module("blocks", name))
    return mods


def matmul_params(shape: Dict[str, Any]) -> Optional[int]:
    mods = _blocks(shape)
    if mods is None:
        return None
    per_group = sum(m.matmul_params(shape) for m in mods)
    return per_group * shape["num_groups"] \
        + shape["d_model"] * shape["vocab_size"]          # + the head


def train_flops_per_token(shape: Dict[str, Any],
                          seq: int) -> Optional[float]:
    mods = _blocks(shape)
    if mods is None:
        return None
    ctx = sum(m.context_flops(shape, seq) for m in mods
              if hasattr(m, "context_flops"))
    return 6.0 * matmul_params(shape) + shape["num_groups"] * ctx


def flush_bytes(params: int, k_live: int, slab_dtype: str) -> int:
    b = DTYPE_BYTES[slab_dtype]
    return k_live * params * b + 2 * params * 4 + params * b
