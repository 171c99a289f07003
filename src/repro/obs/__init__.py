"""Observability: metrics registry, per-query phase tracing, model residual.

The serving pipeline's latency decomposition (paper §4–§5: queueing,
slave top-k, master merge) as a live, exported signal:

- :mod:`repro.obs.registry`   — counters, gauges, fixed log-bucketed
  latency histograms (p50/p95/p99 without storing samples); a no-op
  :class:`NullRegistry` is the process default, so instrumentation is
  zero-cost until :func:`enable` is called;
- :mod:`repro.obs.trace`      — :class:`QuerySpan`, the per-query phase
  record the scheduler populates, a folding aggregator, and
  :class:`PhaseClock`, which times a batch's phases and writes them as
  ``odys.<phase>`` profiler annotations;
- :mod:`repro.obs.residual`   — the online Formula (18) monitor comparing
  measured response against the fitted hybrid model;
- :mod:`repro.obs.exposition` — Prometheus text + JSON rendering, both
  behind ``python -m repro.obs``.

See ``src/repro/obs/README.md`` for the metric catalog, the span schema,
and overhead notes.
"""
from repro.obs.exposition import to_json, to_prometheus  # noqa: F401
from repro.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    disable,
    enable,
    get_registry,
    set_registry,
)
from repro.obs.residual import ModelResidualMonitor  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    PHASES,
    WALL_PHASES,
    PhaseAggregator,
    PhaseClock,
    QuerySpan,
)
