"""``repro.obs`` — the telemetry plane.

One lock-cheap in-process event bus (:class:`~repro.obs.telemetry.
Telemetry`: counters / gauges / histograms always on, spans that are
always ``jax.profiler`` annotations and are ring-buffered when
tracing) threaded through the cluster runtime, the parameter server,
the workers, the in-process transport and the socket hubs, plus three
export surfaces:

  * :func:`~repro.obs.trace.write_chrome_trace` — Chrome
    trace-event / Perfetto JSON (``--trace out.json`` /
    ``python -m repro trace``), one track per server / runtime /
    sampler / worker / wire, on the profiler's clock;
  * ``RunResult.extra["telemetry"]`` — the structured metrics summary
    (staleness, wire bytes, flush-dispatch / publish / publish-gap /
    queue-wait / ingest-wait percentiles) cross-checked against the
    conservation ledger;
  * the read-only ``STATS`` wire frame + :mod:`repro.obs.top`
    (``python -m repro top HOST:PORT``) — live remote introspection of
    a running ``--listen`` leader, riding the serve-peer admission
    path (never in the barrier or the ledger).

:mod:`repro.obs.top` is imported lazily (it pulls in the cluster wire
code, which itself depends on this package).
"""
from repro.obs.telemetry import NULL, NullTelemetry, Telemetry
from repro.obs.trace import chrome_trace, write_chrome_trace

__all__ = ["NULL", "NullTelemetry", "Telemetry", "chrome_trace",
           "write_chrome_trace"]
