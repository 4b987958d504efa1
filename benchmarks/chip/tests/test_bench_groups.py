"""The harness takes a configuration whose group holds several layers of
different kinds: xlstm-350m's published group of three mLSTM layers and
one sLSTM layer, at a width a CPU test trains in seconds, through the
async cell's traffic, limits and checks."""
import time

import pytest

from conftest import tiny_cell

CELL = "danube4l.k1.t256"
SECONDS = 2.0
TRAFFIC = {"seq": 16, "batch": 2, "train_rows": 16, "test_rows": 2}
SLSTM_FAULT = (
    "the program's sLSTM mixes heads into its gates "
    "(models/xlstm.py:207-208, PERF.md section 7); the published one, "
    "and the reference, keep each head's recurrence to itself")


def _xlstm():
    """xlstm-350m as a configuration file states it: the registry entry,
    with the fields the reference builds from in ``shape``."""
    from repro.configs.registry import get_config

    base = get_config("xlstm-350m")
    return {"name": "xlstm-350m-tiny", "registry": "xlstm-350m", "set": {},
            "reduced": [], "shape": {
                "block_pattern": [list(p) for p in base.block_pattern],
                "xlstm_proj_factor": base.xlstm_proj_factor,
                "xlstm_conv": base.xlstm_conv, "norm": base.norm,
                "norm_eps": base.norm_eps}}


@pytest.mark.parametrize("heads", [
    1,
    pytest.param(4, marks=pytest.mark.xfail(strict=True,
                                            reason=SLSTM_FAULT)),
])
def test_a_group_of_recurrent_layers_runs_correct(heads):
    import harness

    c = tiny_cell(CELL, config=_xlstm(), setting={
        "num_groups": 1, "d_model": 64, "vocab_size": 256,
        "num_heads": heads, "num_kv_heads": heads, "tie_embeddings": True,
        "dtype": "float32", "remat": "none"}, **TRAFFIC)
    assert harness.model_config(c["config"]).num_layers == 4
    res = harness.run(CELL, 2 ** 31 + 11, SECONDS, False, time.monotonic(),
                      require_tpu=False, c=c)
    assert res["attempted"] >= 3
    assert res["checks"]["publish_mismatch"]["value"] == 0
    assert res["correct"], res["checks"]
