"""Unified tracing + metrics for the serving stack (docs/OBSERVABILITY.md).

Built on the paper's own idea: threads record events and latency samples
**privately** (thread-local trace buffers, thread-local histogram shards)
and **publish on flush** at safepoints -- so observability adds no
cross-thread traffic on the hot path, exactly as publish-on-ping
reservations add none until a reclaimer pings.

* :class:`~repro.obs.trace.Tracer` -- Chrome-trace/Perfetto JSON spans for
  the full request lifecycle, SMR ping passes (with one child span per
  reader slot), and block lifecycle instants, across two clock domains
  (wall for real serving threads, simulated cycles for gen/vec runs).
* :class:`~repro.obs.metrics.MetricsRegistry` -- log-bucketed histograms
  (p50/p99/p999/max) for TTFT, per-token latency, prefill queue wait, ping
  stall, and reclaim-pass duration.
* :class:`~repro.obs.slo.SLOTracker` -- SLO attainment and goodput
  accounting (SLO-meeting tokens/s, per-tenant, windowed over the run)
  plus the :class:`~repro.obs.slo.TimeSeriesSampler` that exports gauge
  trajectories (queue depth, resident KV bytes, ping-stall p99) as
  time-series rows.
"""

from repro.obs.metrics import Counter, Histogram, MetricsRegistry, \
    summary_keys
from repro.obs.slo import SLOSpec, SLOTracker, TimeSeriesSampler, \
    engine_probes
from repro.obs.trace import NULL_TRACER, PID_SIM, PID_WALL, Tracer, \
    validate_trace

__all__ = ["Counter", "Histogram", "MetricsRegistry", "NULL_TRACER",
           "PID_SIM", "PID_WALL", "SLOSpec", "SLOTracker",
           "TimeSeriesSampler", "Tracer", "engine_probes", "summary_keys",
           "validate_trace"]
