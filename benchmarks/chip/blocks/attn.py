"""Block ``attn``: grouped-query self-attention, causal, optionally
windowed.  The projections ``wq``, ``wk``, ``wv``, ``wo`` enter matrix
products; the scores and the weighted values are products over the
context a query sees."""


def matmul_params(shape):
    d, hd = shape["d_model"], shape["head_dim"]
    return d * shape["num_heads"] * hd * 2 + d * shape["num_kv_heads"] * hd * 2


def context_flops(shape, seq):
    """Forward and backward FLOPs per token of ``q k^T`` and ``p v``: 2
    products x 2 FLOPs per multiply-add x 3 (forward, two backward) per
    head channel and key seen; query t sees min(t, window) keys."""
    window = shape.get("sliding_window") or seq
    mean_keys = sum(min(t, window) for t in range(1, seq + 1)) / seq
    return 12.0 * shape["num_heads"] * shape["head_dim"] * mean_keys
