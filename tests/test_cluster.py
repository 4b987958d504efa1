"""Tests for the wall-clock cluster backend (``repro.cluster``):
transport semantics, FaultPlan validation + spec round-trip, all three
policies on the runtime, fault injection (stragglers, kill/respawn),
exact gradient accounting (conservation + determinism guards), and the
CLI surface.

Budgets are deliberately small (a second or two per run): the point is
exercising real concurrency and exact accounting, not convergence.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.api import ExperimentSpec, FaultPlan, RunResult, run
from repro.cluster.faults import parse_fault_pairs
from repro.cluster.trainer import ClusterTrainer
from repro.cluster.transport import GradientMsg, InProcTransport, ParamsMsg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cluster_spec(**kw):
    base = dict(arch="mlp", backend="cluster", mode="hybrid",
                schedule="step:40", cluster_workers=3, wall_budget_s=1.2,
                wall_sample_every_s=0.4, batch=16, smoke=True)
    base.update(kw)
    return ExperimentSpec(**base)


def _check_conservation(res):
    """The conservation ledger holds EXACTLY — computed == applied +
    dropped + buffered + pending_round + in_flight, to the gradient —
    and num_gradients is the server's applied counter.  The runtime
    guarantees exactness by snapshotting only after the transport has
    quiesced (no approximate mid-run qsize() feeds the ledger)."""
    a = res.extra["accounting"]
    assert a["computed"] == (a["applied"] + a["dropped"] + a["buffered"]
                             + a["pending_round"] + a["in_flight"]), a
    assert res.num_gradients == a["applied"]
    assert a["computed"] == sum(a["computed_per_worker"].values())
    return a


# ------------------------------------------------------------ FaultPlan

def test_fault_plan_validation():
    plan = FaultPlan(stragglers=((0, 0.1),), kill=((1, 2.0),),
                     respawn_after_s=0.5)
    assert plan.straggle_s(0) == 0.1 and plan.straggle_s(2) == 0.0
    assert plan.kill_events() == [(2.0, 1)]
    assert not plan.empty and FaultPlan().empty
    # JSON gives lists of lists; construction coerces back to tuples
    assert FaultPlan(stragglers=[[0, 0.1]]) == FaultPlan(
        stragglers=((0, 0.1),))
    with pytest.raises(ValueError, match="stragglers"):
        FaultPlan(stragglers=((-1, 0.1),))
    with pytest.raises(ValueError, match="respawn_after_s"):
        FaultPlan(respawn_after_s=-1.0)


def test_parse_fault_pairs():
    assert parse_fault_pairs("0:0.2, 3:0.5") == ((0, 0.2), (3, 0.5))
    with pytest.raises(ValueError, match="WORKER:SECONDS"):
        parse_fault_pairs("3")
    with pytest.raises(ValueError):
        parse_fault_pairs("a:b")


def test_cluster_spec_json_round_trip():
    spec = _cluster_spec(
        max_gradients=100,
        faults=FaultPlan(stragglers=((0, 0.05),), kill=((1, 0.5),),
                         respawn_after_s=0.25, checkpoint_every_s=0.5))
    back = ExperimentSpec.from_json(spec.to_json())
    assert back == spec
    assert isinstance(back.faults, FaultPlan)
    assert back.faults.kill == ((1, 0.5),)
    with pytest.raises(ValueError, match="cluster_workers"):
        _cluster_spec(cluster_workers=0)
    with pytest.raises(ValueError, match="max_gradients"):
        _cluster_spec(max_gradients=-1)


# ------------------------------------------------------------ transport

def test_inproc_transport_semantics():
    t = InProcTransport(grad_capacity=2)
    assert t.fetch_params(timeout=0) is None          # nothing published
    t.publish_params(ParamsMsg(3, {"w": 1}))
    assert t.fetch_params(min_version=2, timeout=0).version == 3
    assert t.fetch_params(min_version=4, timeout=0.01) is None  # barrier
    assert t.send_gradient(GradientMsg(0, "g0", 3, 1))
    assert t.send_gradient(GradientMsg(1, "g1", 3, 1))
    assert not t.send_gradient(GradientMsg(2, "g2", 3, 1),
                               timeout=0.01)          # backpressure
    assert t.pending_gradients() == 2
    assert t.recv_gradient(timeout=0).worker_id == 0  # FIFO
    assert t.recv_gradient(timeout=0).worker_id == 1
    assert t.recv_gradient(timeout=0) is None


def test_inproc_timeout_none_blocks_both_sides():
    """The timeout contract is uniform: ``None`` means block on BOTH
    sides (recv_gradient(None) used to mean get_nowait — the opposite
    of the send side), ``<= 0`` polls."""
    t = InProcTransport(grad_capacity=1)
    # send side: None blocks until the queue has room
    assert t.send_gradient(GradientMsg(0, "g0", 0, 1))
    done = []
    th = threading.Thread(
        target=lambda: done.append(
            t.send_gradient(GradientMsg(0, "g1", 0, 2))),  # timeout=None
        daemon=True)
    th.start()
    th.join(0.2)
    assert th.is_alive(), "send_gradient(timeout=None) must block"
    assert t.recv_gradient(timeout=0).seq == 1     # make room
    th.join(2.0)
    assert not th.is_alive() and done == [True]
    # recv side: None blocks until a gradient arrives
    out = []
    th = threading.Thread(target=lambda: out.append(t.recv_gradient()),
                          daemon=True)
    th.start()
    th.join(0.2)
    assert not th.is_alive() and out[0].seq == 2   # g1 was waiting
    th = threading.Thread(target=lambda: out.append(t.recv_gradient()),
                          daemon=True)
    th.start()
    th.join(0.2)
    assert th.is_alive(), "recv_gradient(timeout=None) must block"
    t.send_gradient(GradientMsg(0, "g2", 0, 3))
    th.join(2.0)
    assert not th.is_alive() and out[1].seq == 3
    # <= 0 always polls
    assert t.recv_gradient(timeout=0) is None
    assert t.recv_gradient(timeout=-1) is None


def test_server_death_never_strands_workers(monkeypatch):
    """Regression (worker hang on server death): if the server dies
    mid-run, the runtime must still propagate shutdown to every worker
    stop event — a worker blocked in the bounded-send retry loop would
    otherwise spin forever."""
    from repro.cluster.server import ParameterServer

    def boom(self, msg):
        raise RuntimeError("server died mid-ingest")

    monkeypatch.setattr(ParameterServer, "ingest", boom)
    with pytest.raises(RuntimeError, match="server died mid-ingest"):
        run(_cluster_spec(wall_budget_s=5.0))
    deadline = time.time() + 5.0
    while time.time() < deadline:
        alive = [t.name for t in threading.enumerate()
                 if t.name.startswith("worker-") and t.is_alive()]
        if not alive:
            break
        time.sleep(0.05)
    assert not alive, f"workers outlived the dead server: {alive}"


# ------------------------------------------------- the three policies

@pytest.mark.parametrize("mode,schedule", [
    ("async", None), ("sync", None), ("hybrid", "step:40"),
])
def test_cluster_policies_produce_wall_clock_runresult(mode, schedule):
    res = run(_cluster_spec(mode=mode, schedule=schedule))
    assert res.backend == "cluster" and res.grid_unit == "wall_s"
    assert set(res.metrics) == {"train_loss", "test_loss", "test_acc"}
    assert len(res.grid) >= 2            # wall-clock metric grid
    assert res.grid == tuple(sorted(res.grid))
    for series in res.metrics.values():
        assert len(series) == len(res.grid)
    assert res.num_updates > 0 and res.num_gradients > 0
    avg = res.averaged()
    assert set(avg) == set(res.metrics)
    assert all(np.isfinite(v) for v in avg.values())
    assert res.schedule == (schedule if mode == "hybrid" else None)
    _check_conservation(res)
    # a cluster RunResult round-trips like any other
    assert RunResult.from_json(res.to_json()) == res


def test_cluster_hybrid_more_grads_than_updates():
    """Once K(t) > 1 the hybrid folds several gradients per update."""
    res = run(_cluster_spec(schedule="step:10"))
    assert res.num_gradients > res.num_updates > 0
    _check_conservation(res)


def test_cluster_result_records_placement():
    """The result says where the work ran: the server's device, which
    flush path its aggregator took, and the platform of every worker."""
    import jax
    res = run(_cluster_spec())
    place = res.extra["placement"]
    backend = jax.default_backend()
    assert place["platform"] == backend
    assert place["device_kind"] == jax.devices()[0].device_kind
    # off the TPU the flush is the jnp fold, never the Pallas interpreter
    assert place["flush"] == ("pallas" if backend == "tpu" else "jnp")
    assert place["worker_platforms"] == {str(w): backend for w in range(3)}
    assert res.extra["setup_s"] > 0


@pytest.mark.parametrize("backend,warns", [("cpu", False), ("tpu", True)])
def test_worker_processes_compute_on_cpu_and_say_so(monkeypatch, backend,
                                                    warns):
    """A same-host worker process always computes on the CPU; under an
    accelerator parent that is announced, never silent."""
    import warnings

    import jax
    from repro.cluster.mptransport import worker_process_platform
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        assert worker_process_platform() == "cpu"
    assert [w.category for w in seen] == ([RuntimeWarning] if warns else [])


def test_unknown_cluster_workload():
    with pytest.raises(ValueError, match="unknown cluster workload"):
        ClusterTrainer().run(_cluster_spec(arch="resnet"))


# ------------------------------------------------------ fault injection

def test_cluster_straggler_slows_one_worker():
    res = run(_cluster_spec(
        mode="async", schedule=None,
        faults=FaultPlan(stragglers=((0, 0.2),))))
    a = _check_conservation(res)
    per = a["computed_per_worker"]
    straggler, healthy = per["0"], max(per["1"], per["2"])
    assert straggler < healthy / 3, per


def test_cluster_hybrid_kill_and_respawn_completes():
    """The acceptance scenario: a hybrid run whose FaultPlan kills and
    respawns a worker completes, and the reported num_gradients exactly
    matches the server's applied-gradient counter."""
    res = run(_cluster_spec(
        wall_budget_s=2.2,
        faults=FaultPlan(kill=((1, 0.7),), respawn_after_s=0.3)))
    a = _check_conservation(res)
    assert res.num_gradients == a["applied"] > 0
    kinds = [e["event"] for e in res.extra["events"]]
    assert kinds.count("kill") == 1 and kinds.count("respawn") == 1
    # the respawned generation contributed again after the kill
    assert a["computed_per_worker"]["1"] > 0


def test_cluster_sync_mid_run_restore_keeps_accounting(tmp_path):
    """A mid-run restore rolls the server's version *backwards*; sync
    workers must resync to the restored round (not stall on the old
    one), and every gradient — including round entries discarded by the
    restore and duplicate re-contributions — stays accounted."""
    spec = _cluster_spec(
        mode="sync", schedule=None, wall_budget_s=2.0,
        faults=FaultPlan(checkpoint_every_s=0.4, restore_at_s=1.0))
    res = ClusterTrainer(ckpt_dir=str(tmp_path)).run(spec)
    a = _check_conservation(res)
    kinds = [e["event"] for e in res.extra["events"]]
    assert "restore" in kinds and "checkpoint" in kinds
    restore_t = next(e["t"] for e in res.extra["events"]
                     if e["event"] == "restore")
    assert restore_t < res.wall_s       # training continued after it
    assert a["applied"] > 0


def test_cluster_fault_worker_ids_validated():
    """A plan naming workers outside the fleet is a configuration
    error, not a phantom worker that breaks the sync barrier."""
    with pytest.raises(ValueError, match="worker ids"):
        run(_cluster_spec(faults=FaultPlan(kill=((7, 0.5),))))
    with pytest.raises(ValueError, match="worker ids"):
        run(_cluster_spec(faults=FaultPlan(stragglers=((3, 0.1),))))


def test_cluster_overlapping_kills_fire_on_time():
    """A pending respawn must not postpone later kill events: kills and
    respawns interleave on one wall-clock timeline."""
    res = run(_cluster_spec(
        wall_budget_s=2.0,
        faults=FaultPlan(kill=((0, 0.4), (1, 0.6)),
                         respawn_after_s=0.5)))
    _check_conservation(res)
    events = [(e["event"], e.get("worker")) for e in res.extra["events"]]
    assert events == [("kill", 0), ("kill", 1),
                      ("respawn", 0), ("respawn", 1)], events


def test_cluster_checkpoint_plan_requires_ckpt_dir():
    """The runtime refuses a checkpointing plan without a directory (a
    silent no-op would lose the checkpoints the plan promised); the
    trainer layer instead provisions a temp directory, so a
    checkpointing spec stays runnable from its JSON alone."""
    from repro.cluster.runtime import ClusterRuntime
    with pytest.raises(ValueError, match="ckpt_dir"):
        ClusterRuntime(lambda p, x, y: 0.0, None, (None,) * 4,
                       mode="async",
                       faults=FaultPlan(checkpoint_every_s=0.5))
    res = run(_cluster_spec(faults=FaultPlan(checkpoint_every_s=0.4)))
    kinds = [e["event"] for e in res.extra["events"]]
    assert "ckpt_dir_provisioned" in kinds and "checkpoint" in kinds
    _check_conservation(res)


def test_cluster_sync_survives_worker_kill_without_respawn():
    """Killing a worker mid-run must not deadlock the sync barrier: the
    dead worker is deregistered and rounds continue with the rest."""
    res = run(_cluster_spec(
        mode="sync", schedule=None, wall_budget_s=1.6,
        faults=FaultPlan(kill=((2, 0.4),))))
    _check_conservation(res)
    events = res.extra["events"]
    assert [e["event"] for e in events] == ["kill"]
    assert res.num_updates > 0


# ------------------------------------------------- determinism guards

def test_cluster_async_accounting_deterministic():
    """Two async runs with the same seed reach identical gradient-count
    accounting under a gradient budget, even though apply order (and
    per-worker interleaving) differs between runs."""
    spec = _cluster_spec(mode="async", schedule=None, max_gradients=40,
                         wall_budget_s=30.0)
    first, second = run(spec), run(spec)
    for res in (first, second):
        a = _check_conservation(res)
        assert res.num_gradients == 40 == a["applied"]
    assert first.num_gradients == second.num_gradients
    assert first.num_updates == second.num_updates


def test_cluster_sync_bitwise_reproducible():
    """The sync policy is bitwise reproducible: per-worker batch streams
    are deterministic, rounds aggregate in worker-id order, and the
    gradient budget pins the round count."""
    spec = _cluster_spec(mode="sync", schedule=None, max_gradients=30,
                         wall_budget_s=30.0)
    finals = []
    for _ in range(2):
        trainer = ClusterTrainer()
        res = trainer.run(spec)
        assert res.num_updates == 10      # 10 rounds of 3 workers
        finals.append(trainer.last_params)
    for key in finals[0]:
        assert np.array_equal(np.asarray(finals[0][key]),
                              np.asarray(finals[1][key])), key


# ----------------------------------------------------------------- CLI

def test_cli_cluster_run_with_faults(tmp_path):
    out = str(tmp_path / "res.json")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run(
        [sys.executable, "-m", "repro", "run", "--backend", "cluster",
         "--arch", "mlp", "--cluster-workers", "3", "--wall-budget", "1.5",
         "--wall-sample-every", "0.5", "--mode", "hybrid",
         "--schedule", "step:40", "--straggler", "0:0.1", "--quiet",
         "--out", out],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    res = RunResult.from_json(open(out).read())
    assert res.backend == "cluster" and res.grid_unit == "wall_s"
    assert res.spec["faults"]["stragglers"] == [[0, 0.1]]
    _check_conservation(res)
    summary = json.loads(p.stdout)
    assert summary["num_gradients"] == res.num_gradients


def test_cli_bench_resolves_from_any_cwd(tmp_path):
    """`python -m repro bench` no longer requires the repo root CWD."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run(
        [sys.executable, "-m", "repro", "bench", "--help"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(tmp_path))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "usage" in p.stdout.lower()
