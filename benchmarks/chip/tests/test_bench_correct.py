"""What decides ``correct``, at a size a CPU test run holds: a sound run
passes, each fault a one-chip training cell can have fails, and the
control (the reference one precision down) fails."""
import numpy as np
import pytest

CELL = "danube4l.k2.t2048"
SECONDS = 5.0


def _run(tiny, **traffic):
    import time

    import harness

    c = tiny(CELL, **traffic)
    return harness.run(CELL, 2 ** 31 + 5, SECONDS, False, time.monotonic(),
                       require_tpu=False, c=c)


def test_a_sound_run_is_correct(tiny):
    res = _run(tiny)
    assert res["correct"], res["checks"]
    assert res["checks"]["publish_mismatch"]["value"] == 0
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 3 and res["failed"] == 0


def test_a_step_that_leaves_the_state_unchanged_fails(tiny, monkeypatch):
    from repro.core.slab import SlabAggregator

    monkeypatch.setattr(SlabAggregator, "flush_apply",
                        lambda self, weights, scale: self._pub)
    res = _run(tiny)
    assert not res["correct"]
    assert res["checks"]["change3_gap"]["value"] == pytest.approx(1.0)


def test_a_stale_publish_fails(tiny, monkeypatch):
    """The server applies every update but keeps publishing the first
    parameters: the workers' gradients barely change, the published
    copy does."""
    from repro.core.slab import SlabAggregator

    real = SlabAggregator.flush_apply
    first = {}

    def stale(self, weights, scale):
        first.setdefault("pub", self._pub)
        real(self, weights, scale)
        return first["pub"]

    monkeypatch.setattr(SlabAggregator, "flush_apply", stale)
    res = _run(tiny)
    assert not res["correct"]
    assert res["checks"]["publish_mismatch"]["value"] > 0


def test_half_of_the_batch_left_out_fails(tiny, monkeypatch):
    from repro.models import model as M

    full = M.loss_fn

    def half(params, batch, cfg, **kw):
        n = batch["tokens"].shape[0] // 2
        return full(params, {k: v[:n] for k, v in batch.items()}, cfg, **kw)

    monkeypatch.setattr(M, "loss_fn", half)
    assert not _run(tiny)["correct"]


def test_a_gradient_altered_where_it_is_produced_fails(tiny, monkeypatch):
    from repro.cluster import worker

    init = worker.Worker.__init__

    def altered(self, *a, grad_fn, **kw):
        def g(p, x, y):
            out = grad_fn(p, x, y)
            return out.at[:out.shape[0] // 4].multiply(2)
        init(self, *a, grad_fn=g, **kw)

    monkeypatch.setattr(worker.Worker, "__init__", altered)
    assert not _run(tiny)["correct"]


def test_the_control_and_the_planted_faults_fail(tiny):
    """The reference put in the program's place one precision below the
    configuration's bfloat16 (every product's operands in fp8), on half
    of each batch, or with a gradient altered, reads above the cell's
    limits on a canonical schedule of three updates."""
    import check
    import control

    c = tiny(CELL)
    rows = list(control.readings(c, 3))
    assert {r["stand_in"] for r in rows} == {"control", "half_batch",
                                            "altered"}
    for r in rows:
        checks = {k: {"value": r[k], "limit": v}
                  for k, v in c["limits"].items() if k in r}
        assert not check.passed(checks), r


def test_the_canonical_schedule():
    import control

    assert control.schedule(2, 1) == [[(0, 1, 0)], [(1, 1, 1)],
                                      [(0, 2, 2)]]
    assert control.schedule(2, 2) == [[(0, 1, 0), (1, 1, 0)],
                                      [(0, 2, 1), (1, 2, 1)],
                                      [(0, 3, 2), (1, 3, 2)]]


def test_worst_leaf_gap_measures_against_the_larger_norm():
    import check

    ref = np.array([1.0, 2.0, 4.0, 1e-6])
    keep = check.kept_leaves(ref)
    assert keep.tolist() == [True, True, True, False]
    # leaf 0: |1.5 - 1| / max(1, median 2) = 0.25; the quiet leaf is out
    assert check.worst_leaf_gap([1.5, 2.0, 4.0, 5.0], ref, keep) == 0.25
