"""Serving-plane load benchmark: what do readers cost the fleet?

The serving-plane invariant is that read-only SERVE subscribers ride
the training leader for free: ``publish_params`` swaps a pointer, each
client's pushes are coalesced by a per-connection writer, and a slow
reader wedges only its own socket.  This benchmark puts a number on
"for free": the same async training run (mlp, host transport, one
joined worker group) under {0, 2, 8} concurrent serve clients, each
client hammering inference probes against every pushed params version.

Reported per cell:

  * ``train.grads_per_s`` — applied gradients over the serving window
    (fleet-ready barrier to shutdown).  The clients here run *in the
    leader's process* hammering JAX probes, so this column prices the
    worst case — co-located readers stealing leader CPU; remote
    readers cost only push bandwidth, and the wire-level invariant
    (a stalled reader never blocks a flush) is enforced by the
    conformance tests, not this number;
  * ``clients[].qps`` — inference requests per second per client;
  * ``clients[].staleness`` — per-request ``p50``/``p99``/``max`` of
    (leader's live params version − version the request ran against),
    in versions.  This is the staleness-vs-throughput readout: raising
    ``serve_every`` trades staleness for less push bandwidth;
  * ``serving`` — the leader's own per-client push accounting
    (``RunResult.extra["serving"]``), so pushes/skips are recorded
    from both ends of the wire.

Emits ``BENCH_serve.json`` (schema ``repro.bench.serve/v1``):

  PYTHONPATH=src python -m benchmarks.serve_load --quick
  # or: make bench-serve   /   python -m repro bench
"""
from __future__ import annotations

import argparse
import json
import threading
import time
from typing import Dict, List

import numpy as np


def _client_loop(address, spec, runtime, stop, record, idx):
    """One serve client: subscribe, probe every pushed version, record
    per-request staleness against the leader's live counter."""
    from repro.serve.client import ServeClient
    from repro.serve.workload import build_infer_adapter
    try:
        client = ServeClient(address, connect_timeout=120.0)
    except Exception as e:                      # leader gone already
        record["error"] = f"connect failed: {e}"
        return
    try:
        adapter = build_infer_adapter(spec)
        last_version = None
        params = None
        lat: List[float] = []
        stale: List[int] = []
        t_first = None
        while not stop.is_set():
            msg = client.wait_params(min_version=0, timeout=0.25)
            if msg is None:
                if client.closed.is_set():
                    break
                continue
            if t_first is None:
                t_first = time.monotonic()
            if msg.version != last_version:
                params = adapter.decode(msg.params)
                last_version = msg.version
            t0 = time.monotonic()
            adapter.run(params, len(lat))
            lat.append(time.monotonic() - t0)
            server = getattr(runtime, "server", None)
            live = getattr(server, "version", msg.version)
            stale.append(max(0, int(live) - int(msg.version)))
        wall = (time.monotonic() - t_first) if t_first else 0.0
        record.update({
            "client": idx,
            "requests": len(lat),
            "qps": round(len(lat) / max(wall, 1e-9), 2),
            "req_p50_ms": round(
                float(np.percentile(lat, 50)) * 1e3, 2) if lat else None,
            "staleness": {
                "p50": float(np.percentile(stale, 50)),
                "p99": float(np.percentile(stale, 99)),
                "max": int(max(stale)),
            } if stale else None,
            "last_version": last_version,
        })
    finally:
        client.close()


def bench_cell(n_clients: int, budget_s: float, serve_every: int,
               platform=None) -> Dict:
    """One cell: a real host-transport training run (one joined worker
    process) with ``n_clients`` in-process serve-client threads probing
    every push."""
    from repro.api import ExperimentSpec
    from repro.cluster.hostlink import spawn_join_process
    from repro.cluster.trainer import ClusterTrainer

    spec = ExperimentSpec(
        arch="mlp", backend="cluster", mode="async", smoke=True,
        cluster_workers=1, wall_budget_s=budget_s,
        wall_sample_every_s=budget_s, batch=16,
        transport="host", listen="127.0.0.1:0",
        serve_every=serve_every)
    trainer = ClusterTrainer()
    runtime = trainer.build_runtime(spec)
    runtime.proc_ready_timeout_s = 180.0
    runtime.join_platform = platform
    join = spawn_join_process(runtime.listen_address, workers=1,
                              platform=platform)
    stop = threading.Event()
    records: List[Dict] = [{} for _ in range(n_clients)]
    threads = [threading.Thread(
        target=_client_loop,
        args=(runtime.listen_address, spec, runtime, stop, records[i], i),
        daemon=True) for i in range(n_clients)]
    for t in threads:
        t.start()
    try:
        res = trainer.finish(runtime, spec)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        try:
            join.wait(timeout=60)
        except Exception:
            join.kill()
    a = res.extra["accounting"]
    serve_s = res.extra["serve_wall_s"]
    return {
        "clients": n_clients,
        "serve_every": serve_every,
        "train": {
            "applied": a["applied"],
            "serve_wall_s": round(serve_s, 3),
            "grads_per_s": round(a["applied"] / max(serve_s, 1e-9), 1),
            "worker_platforms": sorted(set(
                res.extra["placement"]["worker_platforms"].values())),
        },
        "client_stats": [r for r in records if r],
        "serving": res.extra.get("serving"),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="serving-plane load: training throughput and "
                    "per-client staleness under {0,2,8} serve clients")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: {0,2} clients, short budget")
    ap.add_argument("--clients", type=int, nargs="*", default=None,
                    help="override the client-count grid")
    ap.add_argument("--budget", type=float, default=None,
                    help="training wall budget per cell (seconds)")
    ap.add_argument("--serve-every", type=int, default=1,
                    help="leader-side push downsampling (the "
                         "staleness-vs-throughput knob)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    grid_clients = args.clients if args.clients is not None \
        else ([0, 2] if args.quick else [0, 2, 8])
    budget = args.budget if args.budget else (8.0 if args.quick else 12.0)

    from repro.cluster.mptransport import worker_process_platform
    platform = worker_process_platform()

    cells = []
    for n in grid_clients:
        cell = bench_cell(n, budget, args.serve_every, platform)
        cells.append(cell)
        cl = cell["client_stats"]
        qps = ", ".join(f"{c.get('qps', 0)}" for c in cl) or "-"
        st = cl[0]["staleness"] if cl and cl[0].get("staleness") \
            else None
        print(f"clients={n}: train "
              f"{cell['train']['grads_per_s']:.1f} g/s | qps [{qps}]"
              + (f" | staleness p50 {st['p50']} p99 {st['p99']}"
                 if st else ""), flush=True)

    base = cells[0]["train"]["grads_per_s"] if cells else None
    report = {
        "schema": "repro.bench.serve/v1",
        "workload": "mlp",
        "definition": ("train.grads_per_s = applied / serve_wall_s "
                       "(fleet-ready barrier to shutdown); staleness "
                       "in versions = leader's live params version - "
                       "version the request ran against, sampled per "
                       "request"),
        "budget_s": budget,
        "grid": cells,
        "baseline_grads_per_s": base,
        "worst_train_ratio": None if not base else round(
            min(c["train"]["grads_per_s"] for c in cells)
            / max(base, 1e-9), 3),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
