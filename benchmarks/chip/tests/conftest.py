"""Shared pieces of the benchmark's CPU tests: the benchmark's own
modules on the path, and a cell shrunk to a size a test run holds."""
import copy
import dataclasses
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the danube family at a width a CPU test can train in seconds; every
# other field is the cell's own
TINY = {"num_groups": 1, "d_model": 64, "vocab_size": 256, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "d_ff": 96}


def tiny_cell(workload: str, config=None, setting=TINY, **traffic):
    """The cell of ``workload`` with its configuration, or ``config`` in
    its place, cut to ``setting`` and its parameter count recomputed;
    ``traffic`` overrides the traffic's fields."""
    import jax

    import harness
    from repro.configs.registry import get_config
    from repro.models import model as M

    c = copy.deepcopy(harness.cell(workload))
    conf = c["config"] = copy.deepcopy(config) if config else c["config"]
    conf["set"] = dict(conf["set"], **setting)
    conf["shape"].update(setting)
    cfg = dataclasses.replace(get_config(conf["registry"]), **conf["set"])
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    conf["params"] = sum(x.size for x in jax.tree.leaves(shapes))
    c["traffic"].update(traffic)
    return c


@pytest.fixture
def tiny():
    return tiny_cell
