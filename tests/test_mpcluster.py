"""End-to-end tests for the multi-process cluster transports
(``spec.transport = "socket" | "proc"``).

The expensive scenarios live here (each ``proc`` run spawns real
worker processes that import JAX and compile before connecting —
seconds per fleet), separate from the thread-mode cluster tests in
``tests/test_cluster.py``:

  * the acceptance scenario — a 2-process hybrid run completes with the
    conservation ledger holding exactly, survives one SIGKILL+respawn
    fault, and reports torn frames instead of corrupting accounting;
  * cross-process bitwise parity — the same sync spec under a gradient
    budget produces bit-identical final parameters on ``inproc`` and
    ``proc`` (slab frames round-trip f32 bitwise; per-worker data
    streams and worker-id-ordered rounds are deterministic);
  * the ``socket`` transport (threads over TCP slab frames) as a drop-in
    on the normal runtime, checkpoint restore propagation included.
"""
import numpy as np
import pytest

from repro.api import ExperimentSpec, FaultPlan, run
from repro.cluster.trainer import ClusterTrainer


def _spec(**kw):
    base = dict(arch="mlp", backend="cluster", mode="hybrid",
                schedule="step:40", cluster_workers=2, wall_budget_s=1.5,
                wall_sample_every_s=0.5, batch=16, smoke=True)
    base.update(kw)
    return ExperimentSpec(**base)


def _check_conservation(res):
    a = res.extra["accounting"]
    assert a["computed"] == (a["applied"] + a["dropped"] + a["buffered"]
                             + a["pending_round"] + a["in_flight"]), a
    assert res.num_gradients == a["applied"]
    assert a["computed"] == sum(a["computed_per_worker"].values())
    return a


# ---------------------------------------------------------------- spec

def test_spec_transport_field_round_trip():
    spec = _spec(transport="proc")
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError, match="transport"):
        _spec(transport="carrier-pigeon")


def test_proc_runtime_requires_spec_dict():
    """ClusterRuntime can't spawn worker processes without the spec the
    children rebuild the workload from — fail at construction, not as a
    hung fleet."""
    from repro.cluster.runtime import ClusterRuntime
    with pytest.raises(ValueError, match="spec_dict"):
        ClusterRuntime(lambda p, x, y: 0.0, None, (None,) * 4,
                       mode="async", transport_kind="proc")


# ------------------------------------------------- socket (threads/TCP)

def test_socket_transport_run_completes_with_exact_ledger():
    res = run(_spec(transport="socket"))
    assert res.backend == "cluster" and res.grid_unit == "wall_s"
    a = _check_conservation(res)
    assert a["applied"] > 0 and res.num_updates > 0


def test_socket_transport_sync_restore_resyncs(tmp_path):
    """A mid-run checkpoint restore rolls the version backwards *over
    the socket broadcast*; sync workers must resync and accounting must
    stay exact — the cross-address-space version of the in-proc restore
    test."""
    spec = _spec(mode="sync", schedule=None, transport="socket",
                 wall_budget_s=2.0,
                 faults=FaultPlan(checkpoint_every_s=0.4,
                                  restore_at_s=1.0))
    res = ClusterTrainer(ckpt_dir=str(tmp_path)).run(spec)
    a = _check_conservation(res)
    kinds = [e["event"] for e in res.extra["events"]]
    assert "restore" in kinds and "checkpoint" in kinds
    assert a["applied"] > 0


# ------------------------------------------------------ proc (processes)

def test_proc_acceptance_kill_respawn_exact_ledger():
    """The acceptance scenario: a 2-process hybrid run completes, one
    worker is SIGKILLed mid-run and respawned (fresh process, fresh JAX
    runtime, fresh stream generation), and the conservation ledger
    holds to the gradient — a frame torn by the SIGKILL is discarded
    and reported, never miscounted."""
    res = run(_spec(transport="proc", wall_budget_s=10.0,
                    wall_sample_every_s=2.0,
                    faults=FaultPlan(kill=((1, 1.0),),
                                     respawn_after_s=0.5)))
    a = _check_conservation(res)
    assert res.num_gradients == a["applied"] > 0
    kinds = [e["event"] for e in res.extra["events"]]
    assert kinds.count("kill") == 1 and kinds.count("respawn") == 1
    # SIGKILL was physical (the event records it) and both generations
    # of worker 1 talked to the server
    kill_ev = next(e for e in res.extra["events"] if e["event"] == "kill")
    assert kill_ev["sigkill"] is True
    assert a["computed_per_worker"]["1"] > 0
    assert a["torn_frames"] >= 0          # present, and never negative
    # worker processes compute on the CPU, and the result says so
    assert res.extra["placement"]["worker_platforms"] == {"0": "cpu",
                                                          "1": "cpu"}


def test_proc_sync_kill_respawn_barrier_keeps_moving():
    """Sync + proc + SIGKILL/respawn: the barrier must keep completing
    rounds with the survivors while the respawned child is still
    importing JAX — membership is driven by the connection (register
    on HELLO, deregister on connection death), not by the spawn, so a
    worker that cannot yet contribute never blocks a round."""
    res = run(_spec(mode="sync", schedule=None, transport="proc",
                    wall_budget_s=8.0, wall_sample_every_s=2.0,
                    faults=FaultPlan(kill=((1, 1.0),),
                                     respawn_after_s=0.5)))
    a = _check_conservation(res)
    kinds = [e["event"] for e in res.extra["events"]]
    assert kinds.count("kill") == 1 and kinds.count("respawn") == 1
    assert a["applied"] > 0 and res.num_updates > 0


def test_proc_sync_mid_run_restore_resyncs_across_processes(tmp_path):
    """Checkpoints round-trip across the process boundary: the server
    (parent) snapshots and restores mid-run; the rolled-back version +
    bumped restore epoch cross the socket to the worker processes,
    which resync to the restored round instead of stalling the barrier;
    accounting stays exact."""
    spec = _spec(mode="sync", schedule=None, transport="proc",
                 wall_budget_s=5.0, wall_sample_every_s=1.0,
                 faults=FaultPlan(checkpoint_every_s=0.5,
                                  restore_at_s=1.5))
    res = ClusterTrainer(ckpt_dir=str(tmp_path)).run(spec)
    a = _check_conservation(res)
    events = res.extra["events"]
    kinds = [e["event"] for e in events]
    assert "checkpoint" in kinds and "restore" in kinds
    restore_t = next(e["t"] for e in events if e["event"] == "restore")
    assert restore_t < res.extra["serve_wall_s"]
    assert a["applied"] > 0 and res.num_updates > 0


def test_proc_bitwise_parity_with_inproc():
    """Same sync spec + gradient budget, run once with worker threads
    and once with worker processes: final parameters must be bitwise
    identical.  This is the guarantee that moving workers out of the
    address space changed the physics (GIL, staleness, death) and
    nothing else — slab frames carry f32 bitwise, rounds aggregate in
    worker-id order, shards are deterministic."""
    base = dict(mode="sync", schedule=None, wall_budget_s=30.0,
                wall_sample_every_s=10.0, max_gradients=12)
    finals = {}
    for transport in ("inproc", "proc"):
        trainer = ClusterTrainer()
        res = trainer.run(_spec(transport=transport, **base))
        a = _check_conservation(res)
        assert a["applied"] == 12 and res.num_updates == 6
        finals[transport] = trainer.last_params
    for key in finals["inproc"]:
        assert np.array_equal(np.asarray(finals["inproc"][key]),
                              np.asarray(finals["proc"][key])), key


def test_proc_adamw_sigkill_restore_exact_ledger_no_nan_moments(tmp_path):
    """The slab-resident optimizer under the full fault gauntlet: an
    adamw run over real worker processes takes a SIGKILL+respawn,
    checkpoints on a cadence (moment slabs + update count riding the
    npz), and restores mid-run.  The conservation ledger must hold to
    the gradient, the moments must come out finite (a restore that
    resurrected stale or torn moment state would NaN within a few
    flushes), and the optimizer's update count must both persist in the
    checkpoint and keep advancing after the restore."""
    from repro.checkpoint import latest_step, load_opt_state

    spec = _spec(transport="proc", optimizer="adamw", beta1=0.9,
                 beta2=0.95, weight_decay=0.01,
                 wall_budget_s=8.0, wall_sample_every_s=2.0,
                 faults=FaultPlan(kill=((1, 1.0),), respawn_after_s=0.5,
                                  checkpoint_every_s=0.5,
                                  restore_at_s=2.0))
    trainer = ClusterTrainer(ckpt_dir=str(tmp_path))
    runtime = trainer.build_runtime(spec)
    res = trainer.finish(runtime, spec)
    a = _check_conservation(res)
    kinds = [e["event"] for e in res.extra["events"]]
    assert "checkpoint" in kinds and "restore" in kinds
    assert kinds.count("kill") == 1 and kinds.count("respawn") == 1
    assert a["applied"] > 0 and res.num_updates > 0
    # the live server's moments after the whole gauntlet: finite, f32,
    # and the count matches the updates actually applied since restore
    st = runtime.server.snapshot_opt_state()
    assert st is not None
    for name in ("mu", "nu"):
        assert st[name].dtype == np.float32
        assert np.isfinite(st[name]).all(), name
    assert st["count"] > 0
    # the on-disk checkpoints carry the optimizer state too
    step = latest_step(str(tmp_path))
    assert step is not None
    on_disk = load_opt_state(str(tmp_path / f"step_{step}"))
    assert on_disk is not None and on_disk["count"] > 0
    assert np.isfinite(on_disk["mu"]).all()
    assert np.isfinite(on_disk["nu"]).all()
    # the telemetry seam: one optimizer step per fused flush, exactly
    tel = res.extra["telemetry"]
    assert tel["counters"]["optimizer_steps"] == a["updates"]
    assert tel["histograms"]["flush_dispatch_s"]["count"] == a["updates"]
