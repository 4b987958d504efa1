"""The plain reference against the program's model, at small widths on
the CPU: loss and every leaf's gradient agree to float32 rounding.

The sLSTM case runs at one head: with more, the program's recurrent
term mixes heads into gates (``PERF.md``, Open questions), and the
reference follows the published block-diagonal form."""
import dataclasses

import numpy as np
import pytest

CASES = {
    "danube": ("h2o-danube-1.8b", 16,
               dict(num_groups=2, num_heads=4, num_kv_heads=2, head_dim=16,
                    d_ff=96)),
    "danube-window": ("h2o-danube-1.8b", 96,
                      dict(num_groups=1, num_heads=4, num_kv_heads=2,
                           head_dim=16, d_ff=96, sliding_window=40)),
    "mlstm-4-heads": ("xlstm-350m", 128,
                      dict(num_groups=1, num_heads=4, num_kv_heads=4,
                           block_pattern=(("mlstm", "none"),))),
    "slstm-1-head": ("xlstm-350m", 16,
                     dict(num_groups=1, num_heads=1, num_kv_heads=1,
                          block_pattern=(("slstm", "none"),))),
}


def _shape(cfg):
    return dict(d_model=cfg.d_model, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                sliding_window=cfg.sliding_window,
                xlstm_proj_factor=cfg.xlstm_proj_factor,
                xlstm_conv=cfg.xlstm_conv, norm_eps=cfg.norm_eps,
                num_groups=cfg.num_groups,
                block_pattern=[list(p) for p in cfg.block_pattern],
                tie_embeddings=cfg.tie_embeddings)


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_the_program_at_small_widths(case):
    import jax

    import gen
    from reference import Ref
    from repro.configs.registry import get_config
    from repro.models import model as M

    name, seq, sizes = CASES[case]
    cfg = dataclasses.replace(get_config(name), d_model=64, vocab_size=128,
                              dtype="float32", remat="none", **sizes)
    seed = 2 ** 31 + 17
    tmpl = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    params = gen.weights(seed, tmpl)
    x = gen.rng(seed, 1).integers(0, 128, (2, seq), dtype=np.int32)
    y = (x + 1) % 128
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: M.loss_fn(
            p, {"tokens": x, "labels": y}, cfg)[0])(params)
    ref_loss, ref_grads = Ref(_shape(cfg)).grad(params, x, y)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)
