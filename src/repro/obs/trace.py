"""Per-query phase tracing: the paper's latency decomposition, per ticket.

ODYS's §4–§5 analysis decomposes response time into queueing, slave, and
master-merge phases.  A :class:`QuerySpan` records that decomposition for
every admitted query as it moves through the serving pipeline
(:mod:`repro.serving.scheduler`); finished spans feed the per-phase
latency histograms and the model-residual monitor
(:mod:`repro.obs.residual`).

Span phases (:data:`PHASES`), in pipeline order, children indented under
their parent (a child's time is part of its parent's):

- ``admission_wait``   — submit → the batch former pops the query's bucket
  (the queueing + formation-deadline component; scheduler clock domain, so
  virtual seconds under :meth:`MasterScheduler.replay`);
- ``formation_wait``   — batch formed → service start on the routed set
  (the set-availability wait; scheduler clock domain);
- ``cache_lookup``     — result-cache probe at admission (wall domain);
- ``schedule``         — ``MasterScheduler._dispatch``'s entry → the
  executor call: batch formation, routing, the dispatch-time cache
  recheck (wall domain);

  - ``route``          — multi-set router decision (wall domain);

- ``slave_dispatch``   — host-side batch construction + device dispatch of
  the jitted query program (wall domain):

  - ``batch_build``    — ``make_query_batch``, the padded query arrays;
  - ``delta_publish``  — only on a batch whose delta snapshot version
    differs from the placed one:

    - ``delta_rebuild`` — ``DeltaWriter.host_publish``, the host gather:
      the term slabs and documents written since the writer's placed
      snapshot, packed into one patch buffer (on the writer's first
      publish, after a rebase, or past the last patch bucket, the whole
      ``DeltaWriter.host_delta`` snapshot instead);
    - ``delta_place``   — ``DeltaWriter.device_delta``: the patch's
      host-to-device copy and the dispatch of its scatter into a copy of
      the placed snapshot (or the full snapshot's placement), then
      ``device_put`` onto the mesh.  No host sync is added: the phase
      times the calls, which return once the runtime has taken the host
      arrays;

  - ``launch``         — the call of the jitted
    ``distributed_query_topk``/``replicated_query_topk`` until it returns;

- ``master_merge``     — the batch-boundary sync: the wait for the device
  batch, which fuses slave top-k and the master merge in one jitted
  program.  Device work is timed **only** here, at the batch boundary —
  no host syncs are added inside the Pallas hot path (wall domain);
- ``finalize``         — host-side result extraction (wall domain).

Two clock domains, by design: the waits are measured on the scheduler's
injectable clock (coherent under virtual-time replay), the service phases
on a real monotonic wall clock (:data:`WALL_PHASES` labels which is
which).  Batch-level phases (schedule and everything after it) are
attributed to every query in the batch via batch membership — each
co-batched span carries the full batch duration plus ``batch_queries`` so
aggregators can normalize per query when they want throughput rather
than latency.

**The same phases on the profiler's clock.**  A :class:`PhaseClock` times
one batch's wall-domain phases and, at exactly the same boundaries, opens
a ``jax.profiler.TraceAnnotation`` named ``odys.<phase>`` that carries the
batch id as the event stat ``batch``.  A profiler trace therefore nests
the phases as the list above does, under an ``odys.step`` event that
spans the whole of ``MasterScheduler._dispatch`` (its self time is the
scheduler's bookkeeping after the executor returns).  Mutations are
annotated as ``odys.mutation_apply`` with the writer ``version`` they
produced.  Annotations record only while a profiler trace runs, and are
opened only when the scheduler traces (``MasterScheduler.trace``, on iff
the registry is live): with tracing off, each phase boundary costs one
branch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

from jax.profiler import TraceAnnotation

from repro.obs.registry import MetricsRegistry, get_registry

__all__ = [
    "ANNOTATION_PREFIX",
    "PHASES",
    "WALL_PHASES",
    "PhaseAggregator",
    "PhaseClock",
    "QuerySpan",
    "annotation",
]

PHASES = (
    "admission_wait",
    "formation_wait",
    "cache_lookup",
    "schedule",
    "route",
    "slave_dispatch",
    "batch_build",
    "delta_publish",
    "delta_rebuild",
    "delta_place",
    "launch",
    "master_merge",
    "finalize",
)

#: Phases measured on the real monotonic wall clock; the rest are in the
#: scheduler's (possibly virtual) clock domain.
WALL_PHASES = frozenset(PHASES) - {"admission_wait", "formation_wait"}

#: Name prefix of the program's profiler annotations.
ANNOTATION_PREFIX = "odys."


def annotation(name: str, **stats) -> TraceAnnotation:
    """The profiler annotation ``odys.<name>`` carrying ``stats`` as event
    stats; it records an event only while a profiler trace runs."""
    return TraceAnnotation(ANNOTATION_PREFIX + name, **stats)


class PhaseClock:
    """One batch's wall-domain phases, timed and annotated together.

    :meth:`open` starts a phase's timer and its ``odys.<phase>`` profiler
    annotation (stat ``batch`` = ``batch_id``); :meth:`close` ends both and
    adds the duration to :attr:`phases`.  Phases nest: closing a phase
    first closes any phase opened inside it that is still open, so an
    early return or an exception inside a parent never leaves a child
    annotation dangling once the parent closes.
    """

    __slots__ = ("batch_id", "phases", "_clock", "_open")

    def __init__(self, batch_id: int, clock: Callable[[], float] = time.perf_counter):
        self.batch_id = batch_id
        self.phases: dict[str, float] = {}
        self._clock = clock
        self._open: list[tuple[str, TraceAnnotation, float]] = []

    def open(self, phase: str) -> None:
        ann = annotation(phase, batch=self.batch_id)
        ann.__enter__()
        self._open.append((phase, ann, self._clock()))

    def close(self, phase: str) -> float:
        """End ``phase`` (and whatever is still open inside it); returns
        its duration in seconds."""
        while True:
            name, ann, t0 = self._open.pop()
            dt = self._clock() - t0
            ann.__exit__(None, None, None)
            self.phases[name] = self.phases.get(name, 0.0) + dt
            if name == phase:
                return dt


@dataclasses.dataclass
class QuerySpan:
    """One query's phase decomposition (attached to its ``QueryTicket``).

    ``submit_time``/``finish_time`` are in the scheduler's clock domain;
    ``phases`` mixes domains as documented above (:data:`WALL_PHASES`).
    ``batch_queries`` is the number of real queries the span's batch
    served — the batch-membership attribution factor.  ``pad_fraction``
    is the share of the batch that was inert padding clones (0.0 for a
    full bucket): the denominator context for the kernel-side
    ``odys_kernel_grid_occupancy`` gauge and the Formula (17) residual —
    a padded batch *should* show low dense-grid occupancy.
    """

    qid: int
    submit_time: float
    phases: dict[str, float] = dataclasses.field(default_factory=dict)
    from_cache: bool = False
    set_id: int | None = None
    batch_id: int | None = None
    batch_queries: int = 1
    pad_fraction: float = 0.0
    finish_time: float | None = None

    def add(self, phase: str, dt: float) -> None:
        self.phases[phase] = self.phases.get(phase, 0.0) + dt

    @property
    def done(self) -> bool:
        return self.finish_time is not None

    @property
    def response_time(self) -> float:
        assert self.finish_time is not None
        return self.finish_time - self.submit_time

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PhaseAggregator:
    """Fold finished spans into measured per-phase means.

    Usable standalone (``fold`` + ``means``) or wired as a scheduler
    ``span_sink``; when built on a live registry it keeps one
    ``odys_phase_mean_seconds{phase=...}`` gauge per phase current, plus
    an ``odys_spans_folded_total`` counter.
    """

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry if registry is not None else get_registry()
        self._sum: dict[str, float] = {}
        self._n: dict[str, int] = {}
        self._gauges = {
            p: reg.gauge(
                "odys_phase_mean_seconds",
                help="running mean of the span phase, per phase label",
                phase=p,
            )
            for p in PHASES
        }
        self._folded = reg.counter(
            "odys_spans_folded_total", help="finished spans aggregated"
        )

    def fold(self, span: QuerySpan) -> None:
        self._folded.inc()
        for phase, dt in span.phases.items():
            self._sum[phase] = self._sum.get(phase, 0.0) + dt
            self._n[phase] = self._n.get(phase, 0) + 1
            g = self._gauges.get(phase)
            if g is not None:
                g.set(self._sum[phase] / self._n[phase])

    # ``sink`` aliases ``fold`` so an aggregator drops straight into the
    # scheduler's span_sink slot.
    sink: Callable = fold

    def mean(self, phase: str) -> float:
        n = self._n.get(phase, 0)
        return self._sum.get(phase, 0.0) / n if n else float("nan")

    def means(self) -> dict[str, float]:
        return {p: self.mean(p) for p in self._n}
