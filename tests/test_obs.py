"""Observability layer: registry semantics, exposition, span tracing
through the real serving pipeline (both backends, live and virtual time),
the online model-residual monitor, and the zero-cost-disabled contract."""
import json
import math
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import jax

try:  # property tests degrade to skips in bare envs; plain tests still run
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.faults import SetHealth
from repro.core.index import build_sharded_index
from repro.core.perfmodel import estimation_error
from repro.data.corpus import CorpusConfig, generate_corpus
from repro.obs.exposition import dump_json, to_json, to_prometheus
from repro.obs.registry import (
    DEFAULT_BUCKETS,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    get_registry,
    set_registry,
)
from repro.obs.residual import ModelResidualMonitor
from repro.obs.trace import (
    PHASES,
    WALL_PHASES,
    PhaseAggregator,
    PhaseClock,
    QuerySpan,
)
from repro.serving.router import HealthAwareRouter
from repro.serving.scheduler import MasterScheduler
from repro.serving.search import SearchService

BACKENDS = ("jnp", "pallas")


@pytest.fixture(scope="module")
def setup():
    corpus = generate_corpus(
        CorpusConfig(n_docs=200, vocab_size=80, mean_doc_len=20,
                     n_sites=6, seed=29)
    )
    sharded, meta = build_sharded_index(corpus, 1)
    mesh = jax.make_mesh((1,), ("data",))
    return corpus, sharded, meta, mesh


def make_service(setup, backend="jnp", **kw):
    corpus, sharded, meta, mesh = setup
    kw.setdefault("window", 512)
    kw.setdefault("k", 10)
    kw.setdefault("t_max", 2)
    kw.setdefault("t_max_buckets", (2,))
    kw.setdefault("batch_size", 2)
    return SearchService(
        sharded, meta, mesh, ns=1, backend=backend,
        interpret=True if backend == "pallas" else None, **kw,
    )


def fake_executor(queries, t_max, k, set_id):
    return [f"r{i}" for i in range(len(queries))]


# ---------------------------------------------------------------- registry


def test_registry_instruments_accumulate():
    reg = MetricsRegistry()
    c = reg.counter("c_total", help="h")
    c.inc()
    c.inc(2.5)
    assert reg.counter("c_total").value == 3.5  # same instrument, same key
    g = reg.gauge("g", x="1")
    g.set(7)
    g.dec(3)
    assert reg.gauge("g", x="1").value == 4.0
    assert reg.gauge("g", x="2").value == 0.0   # distinct label series
    h = reg.histogram("h_seconds")
    h.observe(1e-6)
    h.observe(3.0)
    assert h.count == 2 and h.sum == pytest.approx(3.000001)


def test_registry_kind_clash_raises():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("m")


def test_registry_collect_sorted_and_labeled():
    reg = MetricsRegistry()
    reg.counter("b_total", phase="z")
    reg.counter("b_total", phase="a")
    reg.gauge("a_gauge")
    got = list(reg.collect())
    assert [name for name, *_ in got] == ["a_gauge", "b_total"]
    _, _, _, series = got[1]
    assert [lab["phase"] for lab, _ in series] == ["a", "z"]


def test_null_registry_is_inert_singletons():
    reg = NullRegistry()
    assert not reg.enabled
    c1 = reg.counter("x_total")
    c2 = reg.counter("y_total", any="label")
    assert c1 is c2                     # shared no-op singleton
    c1.inc(100)
    assert c1.value == 0.0
    reg.gauge("g").set(9)
    reg.histogram("h").observe(1.0)
    assert list(reg.collect()) == []    # exposition of disabled = empty
    assert to_prometheus(reg) == "\n"


def test_process_default_registry_swap():
    prev = set_registry(MetricsRegistry())
    try:
        assert get_registry().enabled
    finally:
        set_registry(prev)
    assert not get_registry().enabled   # tests run with the null default


# -------------------------------------------------------------- histograms


def _quantile_bounds_hold(samples, q):
    """The bucket estimate must land in the same bucket as the exact
    order statistic, i.e. within the factor-2 bucket base."""
    h = Histogram()
    for v in samples:
        h.observe(v)
    est = h.quantile(q)
    exact = sorted(samples)[max(0, math.ceil(q * len(samples)) - 1)]
    # same-bucket agreement: est's bucket upper bound >= exact, and the
    # previous bound < exact (unless either clamps the ladder ends)
    if exact <= DEFAULT_BUCKETS[0]:
        assert est <= DEFAULT_BUCKETS[0]
    elif exact > DEFAULT_BUCKETS[-1]:
        assert est == DEFAULT_BUCKETS[-1]
    else:
        assert exact / 2 <= est <= exact * 2


def test_histogram_quantile_matches_sorted_samples_plain():
    rng = np.random.default_rng(0)
    for q in (0.5, 0.95, 0.99):
        for scale in (1e-5, 1e-3, 0.1):
            samples = list(rng.exponential(scale, size=200))
            _quantile_bounds_hold(samples, q)


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        st.lists(
            st.floats(min_value=1e-7, max_value=200.0,
                      allow_nan=False, allow_infinity=False),
            min_size=1, max_size=100,
        ),
        st.sampled_from([0.5, 0.9, 0.95, 0.99]),
    )
    def test_histogram_quantile_property(samples, q):
        _quantile_bounds_hold(samples, q)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_histogram_quantile_property():
        pass


def test_histogram_empty_is_nan():
    h = Histogram()
    assert math.isnan(h.quantile(0.5)) and math.isnan(h.mean())


# -------------------------------------------------------------- exposition


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("odys_c_total", help="a counter").inc(2)
    h = reg.histogram("odys_h_seconds", phase="route")
    h.observe(1.5e-6)
    h.observe(5e-6)
    txt = to_prometheus(reg)
    assert "# TYPE odys_c_total counter" in txt
    assert "odys_c_total 2" in txt
    # cumulative le buckets: 2e-6 holds one sample, 8e-6 both
    assert 'odys_h_seconds_bucket{le="2e-06",phase="route"} 1' in txt
    assert 'odys_h_seconds_bucket{le="8e-06",phase="route"} 2' in txt
    assert 'odys_h_seconds_bucket{le="+Inf",phase="route"} 2' in txt
    assert 'odys_h_seconds_count{phase="route"} 2' in txt


def test_json_exposition_has_quantiles_and_no_nan():
    reg = MetricsRegistry()
    h = reg.histogram("odys_h_seconds")
    for v in (1e-4, 2e-4, 4e-4, 8e-4):
        h.observe(v)
    reg.histogram("odys_empty_seconds")  # empty → null, not NaN
    doc = to_json(reg)
    assert doc["format"] == "repro.obs/v1"
    series = doc["metrics"]["odys_h_seconds"]["series"][0]
    assert set(series["quantiles"]) == {"p50", "p95", "p99"}
    assert series["count"] == 4
    json.loads(dump_json(reg))  # allow_nan=False round-trips


# ------------------------------------------------- span tracing (pipeline)


def test_spans_not_allocated_without_registry():
    sch = MasterScheduler(fake_executor, batch_size=2)
    t = sch.submit([1, 2])
    sch.drain()
    assert not sch.trace and t.span is None


def test_span_cache_miss_then_hit_paths():
    reg = MetricsRegistry()
    sch = MasterScheduler(fake_executor, batch_size=2, cache_size=8,
                          registry=reg)
    assert sch.trace
    miss = sch.submit([1, 2])
    sch.drain()
    hit = sch.submit([1, 2])
    assert hit.from_cache and hit.span.from_cache and hit.span.done
    assert set(hit.span.phases) == {"cache_lookup"}
    assert miss.span.done and not miss.span.from_cache
    for p in ("admission_wait", "formation_wait", "cache_lookup",
              "route", "slave_dispatch"):
        assert p in miss.span.phases, p
    assert miss.span.set_id == 0 and miss.span.batch_queries == 1
    assert reg.counter("odys_cache_hits_total").value == 1


def test_span_routed_dispatch_multi_set():
    reg = MetricsRegistry()
    sch = MasterScheduler(fake_executor, batch_size=1, cache_size=0,
                          n_sets=2, registry=reg)
    tickets = [sch.submit([i]) for i in range(4)]
    sch.drain()
    sets = {t.span.set_id for t in tickets}
    assert sets == {0, 1}               # router spread across both sets
    assert all(t.span.batch_id is not None for t in tickets)
    assert reg.counter("odys_set_batches_total", set="0").value == 2
    assert reg.counter("odys_set_batches_total", set="1").value == 2


def test_span_clock_domains_with_injected_clocks():
    """Waits are measured on the scheduler clock, service on wall_clock."""
    sched_t = [100.0]
    wall_t = [0.0]

    def sched_clock():
        sched_t[0] += 1.0       # +1 virtual second per observation
        return sched_t[0]

    def wall_clock():
        wall_t[0] += 0.001      # +1ms wall per observation
        return wall_t[0]

    reg = MetricsRegistry()
    sch = MasterScheduler(fake_executor, batch_size=1, cache_size=0,
                          registry=reg, clock=sched_clock,
                          wall_clock=wall_clock)
    t = sch.submit([1])
    sch.drain()
    span = t.span
    # scheduler-domain phases tick in whole virtual seconds
    assert span.phases["admission_wait"] >= 1.0
    # wall-domain phases tick in milliseconds — the virtual clock's
    # seconds never bleed into them
    for p in WALL_PHASES & set(span.phases):
        assert span.phases[p] < 0.1, (p, span.phases[p])


@pytest.mark.parametrize("backend", BACKENDS)
def test_spans_through_real_engine(setup, backend):
    reg = MetricsRegistry()
    sink = []
    svc = make_service(setup, backend, cache_size=16, registry=reg,
                       span_sink=sink.append)
    t_miss = svc.submit([3, 9])
    t_short = svc.submit([4])
    svc.drain()
    t_hit = svc.submit([3, 9])
    for t in (t_miss, t_short, t_hit):
        assert t.done and t.span is not None and t.span.done
    # the executor decomposed service into the three wall phases
    for p in ("slave_dispatch", "master_merge", "finalize"):
        assert p in t_miss.span.phases, p
        assert t_miss.span.phases[p] >= 0.0
    assert t_hit.span.from_cache
    assert len(sink) == 3               # every finished span reached the sink
    txt = to_prometheus(reg)
    assert "odys_phase_seconds_bucket" in txt


def test_spans_under_virtual_time_replay(setup):
    reg = MetricsRegistry()
    svc = make_service(setup, cache_size=0, registry=reg, batch_size=2)
    svc.scheduler.max_wait = 0.05
    lam = 40.0
    rng = np.random.default_rng(1)
    arrivals = np.cumsum(rng.exponential(1.0 / lam, size=12))
    trace = [(float(a), [int(rng.integers(0, 50))], None) for a in arrivals]
    svc.search([(terms, site) for _, terms, site in trace[:2]])  # warm
    tickets = svc.scheduler.replay(trace)
    for t in tickets:
        span = t.span
        assert span.done
        # virtual timeline: submit/finish are trace-relative seconds,
        # not wall perf_counter epochs
        assert 0.0 <= span.submit_time <= arrivals[-1] + 1.0
        assert span.response_time >= 0.0
        # coherent decomposition: scheduler-domain waits are bounded by
        # the virtual response; wall service may exceed it only via the
        # measured-batch term itself
        waits = (span.phases.get("admission_wait", 0.0)
                 + span.phases.get("formation_wait", 0.0))
        assert waits <= span.response_time + 1e-9


# ------------------------------------- batch phases, profiler annotations

SERVICE_PHASES = ("batch_build", "launch")
PUBLISH_PHASES = ("delta_publish", "delta_rebuild", "delta_place")


def make_updatable(setup, registry):
    return make_service(setup, cache_size=0, batch_size=2, registry=registry,
                        updatable=True, corpus=setup[0], term_capacity=128,
                        doc_headroom=64)


def _batch_span(svc, queries):
    tickets = [svc.submit(terms, site) for terms, site in queries]
    svc.drain()
    assert all(t.done for t in tickets)
    return tickets[0].span


def test_phase_clock_nests_and_closes_children():
    ticks = iter(range(100))
    clock = PhaseClock(4, clock=lambda: float(next(ticks)))
    clock.open("step")          # t=0
    clock.open("schedule")      # t=1
    clock.open("route")         # t=2
    assert clock.close("route") == 1.0                  # t=3
    clock.close("step")         # closes schedule (t=4), then step (t=5)
    assert clock.phases == {"route": 1.0, "schedule": 3.0, "step": 5.0}
    assert clock.batch_id == 4


def test_publish_phases_only_on_the_batch_after_a_mutation(setup):
    svc = make_updatable(setup, MetricsRegistry())
    first = _batch_span(svc, [([3], None), ([4], None)])
    assert set(PUBLISH_PHASES) <= set(first.phases)    # version 0 is placed
    quiet = _batch_span(svc, [([3], None), ([5], None)])
    assert not set(PUBLISH_PHASES) & set(quiet.phases)
    svc.insert([(np.array([3, 9], np.int32), 1)])
    after = _batch_span(svc, [([3, 9], None), ([9], None)])
    assert set(PUBLISH_PHASES) <= set(after.phases)
    again = _batch_span(svc, [([3, 9], None), ([4], None)])
    assert not set(PUBLISH_PHASES) & set(again.phases)
    assert svc.registry.histogram("odys_mutation_apply_seconds").count == 1


def test_child_phases_fit_inside_their_parents(setup):
    svc = make_updatable(setup, MetricsRegistry())
    _batch_span(svc, [([3], None), ([4], None)])
    svc.delete([7])
    span = _batch_span(svc, [([3], 2), ([9], None)])
    ph = span.phases
    for p in ("schedule", "route", "slave_dispatch", *SERVICE_PHASES,
              *PUBLISH_PHASES, "master_merge", "finalize"):
        assert p in ph and ph[p] >= 0.0, p
    tol = 1e-4
    assert ph["batch_build"] + ph["delta_publish"] + ph["launch"] <= (
        ph["slave_dispatch"] + tol)
    assert ph["delta_rebuild"] + ph["delta_place"] <= ph["delta_publish"] + tol
    assert ph["route"] <= ph["schedule"] + tol
    assert set(ph) <= set(PHASES)


def _odys_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = list(Path(log_dir).rglob("*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("odys.")]


def test_phases_are_written_on_the_profiler_clock(setup, tmp_path):
    svc = make_updatable(setup, MetricsRegistry())
    _batch_span(svc, [([3], None), ([4], None)])       # compile, place v0
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.insert([(np.array([3, 9], np.int32), 2)])
        spans = [_batch_span(svc, [([3, 9], None), ([9], 2)]),
                 _batch_span(svc, [([3], None), ([4], None)])]
    finally:
        jax.profiler.stop_trace()
    events = _odys_events(tmp_path)
    names = {n for n, *_ in events}
    assert {"odys.step", "odys.schedule", "odys.route", "odys.slave_dispatch",
            "odys.batch_build", "odys.delta_publish", "odys.delta_rebuild",
            "odys.delta_place", "odys.launch", "odys.master_merge",
            "odys.finalize", "odys.mutation_apply"} <= names
    (mut,) = [e for e in events if e[0] == "odys.mutation_apply"]
    assert mut[3]["version"] == svc.writer.version
    steps = {e[3]["batch"]: e for e in events if e[0] == "odys.step"}
    assert sorted(steps) == [s.batch_id for s in spans]
    for name, t0, t1, stats in events:
        if name == "odys.mutation_apply":
            continue
        step = steps[stats["batch"]]                    # every one carries it
        assert step[1] <= t0 <= t1 <= step[2], name
    publishing = {e[3]["batch"] for e in events if e[0] == "odys.delta_publish"}
    assert publishing == {spans[0].batch_id}


def test_no_annotation_or_span_with_the_registry_off(setup, tmp_path,
                                                      monkeypatch):
    import repro.serving.scheduler as scheduler_mod

    def refuse(*a, **k):
        raise AssertionError("a PhaseClock was allocated with tracing off")

    svc = make_updatable(setup, None)
    assert not svc.scheduler.trace
    monkeypatch.setattr(scheduler_mod, "PhaseClock", refuse)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.insert([(np.array([3, 9], np.int32), 2)])
        tickets = [svc.submit([3, 9]), svc.submit([9], 2)]
        svc.drain()
    finally:
        jax.profiler.stop_trace()
    assert all(t.done and t.span is None for t in tickets)
    assert svc.scheduler.batch_clock is None
    assert _odys_events(tmp_path) == []


# ------------------------------------------------------------ aggregation


def _span(qid, phases, submit=0.0, finish=1.0, from_cache=False):
    s = QuerySpan(qid=qid, submit_time=submit, from_cache=from_cache)
    for p, dt in phases.items():
        s.add(p, dt)
    s.finish_time = finish
    return s


def test_phase_aggregator_means_and_gauges():
    reg = MetricsRegistry()
    agg = PhaseAggregator(registry=reg)
    agg.fold(_span(0, {"route": 0.1, "finalize": 0.3}))
    agg.sink(_span(1, {"route": 0.3}))   # sink aliases fold
    assert agg.mean("route") == pytest.approx(0.2)
    assert agg.mean("finalize") == pytest.approx(0.3)
    assert math.isnan(agg.mean("master_merge"))
    assert reg.gauge("odys_phase_mean_seconds",
                     phase="route").value == pytest.approx(0.2)
    assert reg.counter("odys_spans_folded_total").value == 2


def test_residual_monitor_matches_offline_projection(setup):
    """The online Formula (18) gauge equals the offline bench computation
    (same Calibration.projected_response path) on the same samples."""
    from repro.core.calibrate import calibrate_from_engine

    corpus, sharded, meta, mesh = setup
    cal = calibrate_from_engine(sharded, meta, mesh, ns=1, k_values=(10,),
                                window=256, q=2, reps=2)
    lam, batch_size, max_wait = 50.0, 2, 0.01
    reg = MetricsRegistry()
    mon = ModelResidualMonitor(cal, batch_size=batch_size,
                               max_wait=max_wait, lam=lam, registry=reg)
    responses = [0.002, 0.004, 0.003, 0.005]
    for i, r in enumerate(responses):
        mon.sink(_span(i, {}, submit=i / lam, finish=i / lam + r))
    mon.sink(_span(99, {}, from_cache=True))   # excluded from the window
    out = mon.update()
    measured = float(np.mean(responses))
    projected = cal.projected_response(
        lam, batch_size=batch_size, max_wait=max_wait)
    assert out["measured"] == pytest.approx(measured)
    assert out["projected"] == pytest.approx(projected)
    assert out["error"] == pytest.approx(
        estimation_error(projected, measured))
    assert reg.gauge("odys_model_residual").value == pytest.approx(
        out["error"])
    assert reg.counter("odys_model_spans_skipped_total").value == 1


def test_residual_monitor_nan_before_samples():
    mon = ModelResidualMonitor(None, batch_size=2)  # cal unused before data
    out = mon.update()
    assert math.isnan(out["error"]) and out["n"] == 0


# --------------------------------------------------- faults & health router


def test_set_health_notifies_on_actual_transitions_only():
    health = SetHealth.all_alive(2)
    events = []
    health.subscribe(lambda sid, alive: events.append((sid, alive)))
    health.fail(1)
    health.fail(1)        # already dead: no event
    health.recover(1)
    health.recover(0)     # already alive: no event
    assert events == [(1, False), (1, True)]
    health.unsubscribe(health.listeners[0])
    health.fail(0)
    assert len(events) == 2


def test_health_router_exports_transitions():
    reg = MetricsRegistry()
    router = HealthAwareRouter(2)
    router.bind_registry(reg)
    assert reg.gauge("odys_set_alive", set="0").value == 1.0
    router.fail(0)
    router.recover(0)
    router.fail(1)
    assert reg.counter("odys_set_health_transitions_total",
                       to="dead").value == 2
    assert reg.counter("odys_set_health_transitions_total",
                       to="alive").value == 1
    assert reg.gauge("odys_set_alive", set="1").value == 0.0


# ------------------------------------------------------- disabled contract


def test_disabled_registry_identical_results(setup):
    q = [([3], None), ([3, 9], None), ([1], 2), ([3], None)]
    svc_off = make_service(setup, cache_size=16)          # null default
    svc_on = make_service(setup, cache_size=16,
                          registry=MetricsRegistry())
    off = [(h.docids, h.n_hits) for h in svc_off.search(q)]
    on = [(h.docids, h.n_hits) for h in svc_on.search(q)]
    assert off == on
    assert not svc_off.scheduler.trace
    assert svc_on.scheduler.trace


# ------------------------------------------------------------- bench gate


def test_check_bench_ignores_unknown_keys(tmp_path):
    payload = {
        "suite": "updates",
        "metrics": {
            "streamed_over_staged_fill0": {"value": 1.0, "note": ""},
            "streamed_over_staged_fill50": {"value": 1.1, "note": ""},
            "streamed_over_staged_fill100": {"value": 0.9, "note": ""},
            "phase_slave_dispatch": {"value": 123.0, "note": "new emitter"},
            "some_future_metric": {"value": 7.0, "note": ""},
        },
    }
    (tmp_path / "BENCH_updates.json").write_text(json.dumps(payload))
    script = Path(__file__).resolve().parents[1] / "scripts" / "check_bench.py"
    proc = subprocess.run(
        [sys.executable, str(script), str(tmp_path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ignoring 2 unrecognized" in proc.stdout
