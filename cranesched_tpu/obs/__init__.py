"""Observability layer: dependency-free metrics, cycle tracing, per-job
lifecycle tracing with SLOs, and the scheduler watchdog.

- ``metrics.py``   process-wide registry of counters / gauges /
                   histograms with Prometheus text exposition and a
                   stdlib HTTP endpoint (no prometheus_client dep).
- ``trace.py``     bounded ring of structured per-cycle traces, the
                   cycle thread's partitioning clock (``CycleClock``:
                   the parts of a period sum to it) and the
                   jax.profiler span helper used around solve closures.
- ``jobtrace.py``  event-sourced per-job timelines (one span per
                   lifecycle edge, ctld + craned clock domains) and the
                   derived latency histograms / exemplars.
- ``slo.py``       sliding-window p50/p99 targets over trace edges with
                   multi-window burn-rate gauges and a breach counter.
- ``flight.py``    stall forensics: always-on flight recorder (phase
                   ring + stall sentry with all-thread stack dumps)
                   and the persistent XLA compilation cache with
                   hit/miss counters.
- ``fedobs.py``    federation-wide merge: scatter-gather metric
                   aggregation and the cluster-level SLO engine over
                   per-shard summaries (exact burn-rate merge).

See ARCHITECTURE.md ("Observability" and "Per-job tracing and SLOs")
for the metric naming scheme and the timeline schema.
"""

from cranesched_tpu.obs.fedobs import (  # noqa: F401
    ClusterSlo,
    cluster_doc,
    merge_metric_snapshots,
    merge_slo_tables,
)
from cranesched_tpu.obs.flight import (  # noqa: F401
    FlightRecorder,
    dump_all_stacks,
    enable_xla_cache,
    xla_cache_stats,
)
from cranesched_tpu.obs.jobtrace import (  # noqa: F401
    FED_EDGES,
    SPAN_EDGES,
    JobTraceRecorder,
    render_waterfall,
)
from cranesched_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    serve_metrics,
)
from cranesched_tpu.obs.slo import (  # noqa: F401
    SloEngine,
    SloSpec,
)
from cranesched_tpu.obs.trace import (  # noqa: F401
    CycleTraceRing,
    solve_span,
)
