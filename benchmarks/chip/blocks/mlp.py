"""Block ``mlp``: the feed-forward layer, gated (``swiglu``: gate, up,
down) or plain (up, down)."""


def matmul_params(shape):
    n_mats = 3 if shape["mlp_act"] == "swiglu" else 2
    return n_mats * shape["d_model"] * shape["d_ff"]
