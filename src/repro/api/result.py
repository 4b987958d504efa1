"""The common result every Trainer returns.

:class:`RunResult` supersedes the simulator's ``SimResult`` and the SPMD
driver's ad-hoc ``history`` list of dicts with one shape: a metric grid
(``grid`` in ``grid_unit`` units — virtual seconds for the simulator,
optimizer steps for SPMD, real wall-clock seconds for the cluster
runtime) with aligned per-metric series, plus update / gradient counters
and provenance (the spec that produced it).

``averaged()`` computes the paper's headline statistic — every metric
averaged over the entire training interval — and ``to_json`` /
``from_json`` round-trip the whole thing for experiment artifacts.

``extra`` key contract (``backend="cluster"``) — these keys are stable
and consumers may rely on their *shape*, not just their presence:

  * ``accounting``   — the conservation ledger: ``applied``,
    ``dropped``, ``buffered``, ``pending_round``, ``updates`` (exact,
    to the gradient, on every transport).
  * ``events``       — fault/checkpoint/phase timeline (list of dicts
    with at least ``t`` and ``event``).
  * ``start_version`` — server version at t=0 (non-zero after resume).
  * ``serve_wall_s`` — the serving-window denominator for grads/sec.
  * ``setup_s``      — seconds before the serving clock started:
    worker-gradient compile + server warm-up (+ fleet assembly on
    ``proc``/``host``).
  * ``placement``    — where the work ran: ``platform`` and
    ``device_kind`` of the server's device, ``flush`` (``"pallas"``,
    ``"pallas_interpret"`` or ``"jnp"``), and ``worker_platforms``
    (worker id -> the platform its gradients were computed on;
    ``"unreported"`` for joined hosts).  A ``proc`` worker under an
    accelerator parent computes on ``"cpu"`` — its rate is a CPU rate.
  * ``serving``      — **always present**: ``clients``,
    ``rejected_peers``, ``serve_every``, ``stats_clients``,
    ``per_client``.  Transports without a serving plane report the
    empty shape (``clients == 0`` …) rather than omitting the key, so
    consumers key on *content*, never on key presence.
  * ``telemetry``    — :meth:`repro.obs.telemetry.Telemetry.summary`
    (counters / gauges / histograms / spans_recorded) plus
    ``ledger_check`` cross-checking the counters against
    ``accounting`` (``consistent`` must be True).
  * ``listen``       — resolved ``host:port`` (host transport only).
  * ``trace_path``   — Chrome trace-event JSON path (only when the run
    was traced via ``--trace``).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class RunResult:
    backend: str                       # "sim" | "spmd" | "cluster"
    mode: str                          # "sync" | "async" | "hybrid"
    schedule: Optional[str]            # schedule spec string (hybrid)
    grid_unit: str                     # "virtual_s" | "step" | "wall_s"
    grid: Tuple[float, ...]            # metric sample points
    metrics: Dict[str, Tuple[float, ...]]  # name -> series, len == len(grid)
    num_updates: int = 0               # parameter updates applied
    num_gradients: int = 0             # gradients computed
    wall_s: float = 0.0                # real (host) seconds
    spec: Optional[Dict[str, Any]] = None  # ExperimentSpec.to_dict()
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        for name, series in self.metrics.items():
            if len(series) != len(self.grid):
                raise ValueError(
                    f"metric {name!r} has {len(series)} samples for a "
                    f"grid of {len(self.grid)}")

    # ----------------------------------------------------------- queries
    def averaged(self) -> Dict[str, float]:
        """Paper-style 'averaged over the entire training interval'."""
        return {k: float(sum(v) / len(v))
                for k, v in self.metrics.items() if len(v)}

    def final(self) -> Dict[str, float]:
        """Last sample of each metric."""
        return {k: float(v[-1]) for k, v in self.metrics.items() if len(v)}

    def series(self, name: str) -> Tuple[float, ...]:
        return self.metrics[name]

    # ------------------------------------------------------ serialization
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["grid"] = list(self.grid)
        d["metrics"] = {k: list(v) for k, v in self.metrics.items()}
        d["averaged"] = self.averaged()
        d["final"] = self.final()
        return d

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RunResult":
        d = dict(d)
        d.pop("averaged", None)   # derived on the way out
        d.pop("final", None)
        d["grid"] = tuple(d.get("grid", ()))
        d["metrics"] = {k: tuple(v)
                        for k, v in d.get("metrics", {}).items()}
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "RunResult":
        return cls.from_dict(json.loads(s))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    # ---------------------------------------------------------- builders
    @classmethod
    def from_sim(cls, sim, spec=None, wall_s: float = 0.0) -> "RunResult":
        """Adapt a :class:`repro.core.simulator.SimResult`."""
        return cls(
            backend="sim", mode=sim.mode,
            schedule=getattr(spec, "schedule", None)
            if sim.mode == "hybrid" else None,
            grid_unit="virtual_s", grid=tuple(float(t) for t in sim.times),
            metrics={
                "train_loss": tuple(float(x) for x in sim.train_loss),
                "test_loss": tuple(float(x) for x in sim.test_loss),
                "test_acc": tuple(float(x) for x in sim.test_acc),
            },
            num_updates=int(sim.num_updates),
            num_gradients=int(sim.num_gradients),
            wall_s=float(wall_s),
            spec=spec.to_dict() if spec is not None else None)

    @classmethod
    def from_history(cls, history: Sequence[Dict[str, Any]], spec=None,
                     wall_s: float = 0.0, num_updates: int = 0,
                     num_gradients: int = 0,
                     metric_keys: Tuple[str, ...] = ("loss", "divergence",
                                                     "group_size",
                                                     "replicas")
                     ) -> "RunResult":
        """Adapt the SPMD driver's logged ``history`` (list of dicts)."""
        history = list(history)
        grid = tuple(float(h["step"]) for h in history)
        metrics = {k: tuple(float(h[k]) for h in history)
                   for k in metric_keys if history and k in history[0]}
        mode = getattr(spec, "mode", "hybrid")
        return cls(
            backend="spmd", mode=mode,
            schedule=getattr(spec, "schedule", None)
            if mode == "hybrid" else None,
            grid_unit="step", grid=grid, metrics=metrics,
            num_updates=num_updates, num_gradients=num_gradients,
            wall_s=float(wall_s),
            spec=spec.to_dict() if spec is not None else None,
            extra={"history": history})

    @classmethod
    def from_cluster(cls, cres, spec=None, wall_s: float = 0.0
                     ) -> "RunResult":
        """Adapt a :class:`repro.cluster.runtime.ClusterResult`.

        ``num_gradients`` is the server's applied-gradient counter,
        exactly; the full conservation ledger and the fault/checkpoint
        timeline ride along in ``extra``."""
        mode = cres.mode
        return cls(
            backend="cluster", mode=mode,
            schedule=getattr(spec, "schedule", None)
            if mode == "hybrid" else None,
            grid_unit="wall_s",
            grid=tuple(float(t) for t in cres.times),
            metrics={
                "train_loss": tuple(float(x) for x in cres.train_loss),
                "test_loss": tuple(float(x) for x in cres.test_loss),
                "test_acc": tuple(float(x) for x in cres.test_acc),
            },
            num_updates=int(cres.num_updates),
            num_gradients=int(cres.num_gradients),
            wall_s=float(wall_s),
            spec=spec.to_dict() if spec is not None else None,
            extra={"accounting": dict(cres.accounting),
                   "events": list(cres.events),
                   "start_version": int(cres.start_version),
                   # serving window only (clock starts after the fleet
                   # is ready) — the denominator for gradients/sec that
                   # is comparable across transports, unlike wall_s
                   # which includes worker-process startup
                   "serve_wall_s": float(cres.wall_s),
                   "setup_s": float(cres.setup_s),
                   "placement": dict(cres.placement or {})})
