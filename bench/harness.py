"""Run one cell once: make the crawl, build and place the index through the
program, warm up the cell's shapes, drive ``SearchService`` with the cell's
traffic for the window, then check every answer against the plain
reference.

Everything runs in this one process and thread.  The open loop submits each
query (and applies each mutation) when it falls due, and steps the
scheduler (``MasterScheduler.step``: form a batch, run it, hand back its
tickets) whenever queries are waiting; a query is timed from its scheduled
arrival to its ticket's completion.  The closed loop keeps a fixed number
of queries in flight, submitting the next as each completes.
"""
from __future__ import annotations

import dataclasses
import gc
import resource
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext

import numpy as np

import jax

from bench import corpus as bench_corpus
from bench import reference, spec
from bench import trace as bench_trace
from bench.traffic import Query, Traffic

#: Seconds of the window that a ``--trace 1`` run records on the profiler.
TRACE_SECONDS = 5.0
#: The open loop's schedule runs this long past the window, so that a
#: mutation near the window's close still meets a later batch.
TAIL_SECONDS = 5.0
#: Past the window's close, how long the loop waits for the last answers.
GRACE_SECONDS = 60.0


@dataclasses.dataclass
class Batch:
    n_applied: int               # mutations acknowledged before its dispatch
    answers: list                # (query, docids, n_hits) of its real queries
    t_start: float
    t_end: float
    traced: bool
    drivers: list | None = None  # per query, each slave's (base, delta) driver lengths


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader is given."""

    device_kind: str
    window: int                  # the configuration's posting window
    merged: bool                 # merge-on-read (an ingest configuration)
    spans: list                  # QuerySpans of the traced batches' queries
    batches: list[Batch]         # every batch dispatched in the window
    trace: bench_trace.Trace | None


def use_compile_cache() -> str:
    """The program's checkout compile cache, holding every program, even
    those that compile in under a second (so a second run compiles none)."""
    from repro.launch.compile_cache import use_checkout_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return use_checkout_cache()


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _repro_corpus(c: bench_corpus.HostCorpus):
    from repro.data.corpus import Corpus

    s = c.shape
    return Corpus(doc_offsets=c.doc_offsets, doc_terms=c.doc_terms,
                  doc_site=c.doc_site, n_docs=s.n_docs,
                  vocab_size=s.vocab_size, n_sites=s.n_sites)


def _pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class _Annotate:
    """``jax.profiler.TraceAnnotation`` while tracing, nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.on else nullcontext()


@dataclasses.dataclass
class Built:
    svc: object                  # the SearchService under test
    host: bench_corpus.HostCorpus
    traffic: Traffic
    setup: dict                  # seconds of each set-up step


def build(cell: spec.Cell, *, seed: int, trace: bool, service_hook=None) -> Built:
    """Make the crawl, build and place the index, warm up every shape the
    cell's traffic uses.  ``service_hook``, for tests, is called with the
    service before the warm-up and may break it."""
    from repro.core.index import build_sharded_index
    from repro.obs.registry import MetricsRegistry
    from repro.serving.search import SearchService

    spec.check_cell(cell.config, cell.chips)
    cfg, svc_cfg = cell.config, cell.config["service"]
    ingest = cfg.get("ingest")
    setup = {}
    t = time.perf_counter()
    shape = bench_corpus.CorpusShape.from_config(cfg)
    host = bench_corpus.generate(shape, seed)
    setup["corpus_s"] = time.perf_counter() - t

    t = time.perf_counter()
    index, meta = build_sharded_index(_repro_corpus(host), svc_cfg["ns"])
    jax.block_until_ready(index)
    setup["index_build_s"] = time.perf_counter() - t

    t = time.perf_counter()
    mesh = jax.make_mesh((svc_cfg["ns"],), ("data",))
    svc = SearchService(
        index, meta, mesh, ns=svc_cfg["ns"], window=svc_cfg["window"],
        t_max=svc_cfg["t_max"], batch_size=svc_cfg["batch_size"],
        cache_size=svc_cfg["cache_size"], backend=svc_cfg["backend"],
        strategy=svc_cfg["strategy"], merge=svc_cfg["merge"],
        updatable=ingest is not None,
        corpus=_repro_corpus(host) if ingest is not None else None,
        term_capacity=ingest["term_capacity"] if ingest else 256,
        doc_headroom=ingest["doc_headroom"] if ingest else 1024,
        # a live registry gives every ticket its QuerySpan
        registry=MetricsRegistry() if trace else None,
    )
    del index
    jax.block_until_ready(svc.index)
    setup["place_s"] = time.perf_counter() - t
    if service_hook is not None:
        service_hook(svc)

    traffic = Traffic(cell.traffic, vocab_size=shape.vocab_size,
                      n_sites=shape.n_sites, n_docs=shape.n_docs)
    t = time.perf_counter()
    warm_rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    for q in traffic.warmup_queries(warm_rng):
        svc.submit(list(q.terms), q.site, k=q.k)
    svc.drain()
    setup["warmup_s"] = time.perf_counter() - t
    peak_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    log(f"set-up: host peak resident {peak_kib} KiB")
    return Built(svc, host, traffic, setup)


def measure(cell: spec.Cell, built: Built, *, seed: int, seconds: float,
            trace: bool, t_process: float) -> tuple[dict, dict]:
    """Drive the built service with the cell's traffic for the window;
    returns the loop's record and the device's description."""
    loop = _open_loop if built.traffic.loop == "open" else _closed_loop
    res = loop(built.svc, built.traffic, seed=seed, seconds=seconds,
               trace=trace, t_process=t_process)
    devs = jax.devices()[:cell.chips]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs]
    log(f"device peaks (B), chip by chip: {peaks}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": max(peaks)}
    return res, device


def verify(cell: spec.Cell, res: dict, host: bench_corpus.HostCorpus, *,
           window: int, mutations: bool = True) -> int:
    """Check every answer the run served against the reference at
    ``window`` over the configuration's set of ``ns`` slaves (without
    ``mutations``: over the crawl as built, ignoring the acknowledged
    mutations); returns how many differ, and records each batch's driver
    lengths on it."""
    t = time.perf_counter()
    served = [(b.n_applied, b.answers) for b in res["batches"]]
    slaves = reference.slave_lists(
        host.doc_offsets, host.doc_terms, host.doc_site, host.shape.vocab_size,
        terms={t for _, rows in served for q, _, _ in rows for t in q.terms},
        window=window, ns=cell.config["service"]["ns"])
    n_checked, n_bad, drivers = reference.check(
        slaves, served, res["mutations"], window=window,
        ingest=mutations and cell.config.get("ingest") is not None)
    for b, d in zip(res["batches"], drivers):
        b.drivers = d
    log(f"reference (window {window}{'' if mutations else ', no mutations'}): "
        f"{n_checked} answers checked in "
        f"{time.perf_counter() - t:.1f} s, {n_bad} differ")
    return n_bad


def run(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
        t_process: float, service_hook=None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    built = build(cell, seed=seed, trace=trace, service_hook=service_hook)
    setup, host = built.setup, built.host
    res, device = measure(cell, built, seed=seed, seconds=seconds, trace=trace,
                          t_process=t_process)
    del built                    # the program's state goes before the reference
    gc.unfreeze()
    gc.collect()
    n_bad = verify(cell, res, host, window=cell.config["service"]["window"])
    ingest = cell.config.get("ingest")
    svc_cfg = cell.config["service"]
    dev = jax.devices()[0]

    checks = {
        "answers_differing": (n_bad, 0),
        "queries_unanswered": (res["unanswered"], 0),
    }
    if ingest is not None:
        checks["mutations_never_visible"] = (res["unseen"], 0)
    correct = all(v <= lim for v, lim in checks.values())

    if trace:
        ctx = Context(dev.device_kind, svc_cfg["window"],
                      ingest is not None, res["spans"], res["batches"],
                      res["trace"])
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        tr = res["trace"]
        device["busy_s"] = bench_trace.busy_ns(tr) / 1e9
        device["window_s"] = tr.window_ns / 1e9
    else:
        metrics = {m["name"]: {"value": res["e2e"][m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["unanswered"] + res["unseen"],
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = bench_trace.breakdown(res["trace"])
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    log(f"setup split (s): {setup}")
    for line in res["info"]:
        log(line)
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    return out


class _Tracer:
    """The profiler over the first ``TRACE_SECONDS`` of the window."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.active = False
        self.t_stop = 0.0

    def start(self, now: float) -> None:
        if self.on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active = True
            self.t_stop = now + TRACE_SECONDS

    def maybe_stop(self, now: float) -> None:
        if self.active and now >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False

    def read(self):
        if not self.on:
            return None
        try:
            return bench_trace.load(bench_trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _quiet_heap() -> None:
    """Collect, then freeze what set-up left on the heap (the crawl, the
    index build, the compiled programs, the schedule), so that the window's
    collections scan only what the window allocates.  The collector stays
    on: the program's own garbage is part of what a run measures."""
    gc.collect()
    gc.freeze()


class _Collections:
    """Times the collector's passes while the window runs, for an info
    line: whether a long pause of the loop was the collector's."""

    def __init__(self):
        self.t, self.pauses, self.full = None, [], 0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            self.pauses.append(time.perf_counter() - self.t)
            self.full += info["generation"] == 2

    def start(self) -> "_Collections":
        gc.callbacks.append(self)
        return self

    def stop(self) -> None:
        gc.callbacks.remove(self)

    def line(self) -> str:
        p = self.pauses
        return (f"collector in the window: {len(p)} passes ({self.full} full), longest "
                f"{1e3 * max(p, default=0.0):.1f} ms, {1e3 * sum(p):.1f} ms in all")


def _answers(real, qidx: dict, spans: list | None) -> list:
    """``(query index, docids, n_hits)`` of each completed ticket, popped
    from ``qidx``; the harness keeps no ticket, and tuples of numbers that
    the collector soon stops tracking.  Appends the tickets' spans to
    ``spans`` where given (the traced part of the window)."""
    out = []
    for tk in real:
        out.append((qidx.pop(id(tk)), tuple(tk.result.docids), int(tk.result.n_hits)))
        if spans is not None and tk.span is not None:
            spans.append(tk.span)
    return out


def _with_queries(batches: list[Batch], queries: list) -> None:
    """Replace each answer's query index by its query, once the window is
    over."""
    for b in batches:
        b.answers = [(queries[i], d, n) for i, d, n in b.answers]


def _apply(svc, m) -> None:
    if m.op == "insert":
        svc.insert([(np.asarray(m.terms, np.int32), m.site)])
    elif m.op == "delete":
        svc.delete([m.docid])
    else:
        svc.update([(m.docid, np.asarray(m.terms, np.int32), m.site)])


def _open_loop(svc, traffic: Traffic, *, seed: int, seconds: float, trace: bool,
               t_process: float) -> dict:
    events = traffic.schedule(seed, seconds, tail=TAIL_SECONDS)
    sched = svc.scheduler
    note = _Annotate(trace)
    tracer = _Tracer(trace)
    queries = [e.query for e in events if e.query is not None]
    n_q = len(queries)
    due = np.full(n_q, np.nan)     # scheduled arrival, per query
    sent = np.full(n_q, np.nan)    # submitted
    done_at = np.full(n_q, np.nan)  # answered
    qidx: dict[int, int] = {}     # id(ticket) -> query index, while in flight
    muts: list = []               # [scheduled, mutation, visible_at]
    unseen_idx: list[int] = []
    batches: list[Batch] = []
    spans: list = []
    i, n, qi = 0, len(events), 0
    measured_q = sum(1 for e in events if e.query is not None and e.t < seconds)
    measured_m = sum(1 for e in events if e.mutation is not None and e.t < seconds)
    done_q = 0
    clock = time.perf_counter
    _quiet_heap()
    tracer.start(clock())
    t0 = clock()
    setup_s = t0 - t_process
    deadline = t0 + seconds + GRACE_SECONDS
    collections = _Collections().start()
    while True:
        now = clock()
        tracer.maybe_stop(now)
        rel = now - t0
        while i < n and events[i].t <= rel:
            e = events[i]
            if e.query is not None:
                with note("bench.submit"):
                    tk = svc.submit(list(e.query.terms), e.query.site, k=e.query.k)
                due[qi], sent[qi] = t0 + e.t, clock()
                qidx[id(tk)] = qi
                if tk.done:       # a cache hit completes at submit
                    done_at[qi] = tk.finish_time
                    done_q += qi < measured_q
                    batches.append(Batch(len(muts), _answers([tk], qidx, None),
                                         now, now, False))
                qi += 1
            else:
                with note("bench.mutate"):
                    _apply(svc, e.mutation)
                muts.append([t0 + e.t, e.mutation, None])
                unseen_idx.append(len(muts) - 1)
            i += 1
        if sched.pending():
            applied = len(muts)
            ts = clock()
            with note("bench.step"):
                real = sched.step()
            te = clock()
            answers = _answers(real, qidx, spans if tracer.active else None)
            batches.append(Batch(applied, answers, ts, te, tracer.active))
            for tk, (j, _, _) in zip(real, answers):
                done_at[j] = tk.finish_time
                done_q += j < measured_q
            for j in unseen_idx:
                muts[j][2] = te
            unseen_idx.clear()
            continue
        seen = len(muts) >= measured_m and all(m[2] is not None for m in muts[:measured_m])
        if (done_q >= measured_q and seen) or i >= n or now > deadline:
            break
        wait = t0 + events[i].t - clock()
        if wait > 0:
            with note("bench.wait"):
                if wait > 0.002:
                    time.sleep(wait - 0.001)
                while clock() < t0 + events[i].t:
                    pass
    t_end = clock()
    collections.stop()
    tracer.stop()

    _with_queries(batches, queries)
    lat = ((done_at - due) * 1e3)[:measured_q]
    lat = lat[~np.isnan(lat)]
    late = ((sent - due) * 1e3)[:min(qi, measured_q)]
    lags = [(m[2] - m[0]) * 1e3 for m in muts[:measured_m] if m[2] is not None]
    e2e = {"setup_s": setup_s}
    if lat.size:
        e2e["query_p50_ms"] = _pct(lat, 50)
        e2e["query_p95_ms"] = _pct(lat, 95)
    if lags:
        e2e["fresh_lag_p95_ms"] = _pct(lags, 95)
    info = [
        f"open loop: {measured_q} queries due in {seconds:g} s "
        f"({measured_q / seconds:.1f}/s offered), {lat.size} answered; "
        f"{len(batches)} batches; loop ran {t_end - t0:.2f} s",
        f"generator lateness (ms): p50 {_pct(late, 50):.3f} p99 {_pct(late, 99):.3f} "
        f"max {late.max():.3f}" if late.size else "generator lateness: no queries",
    ]
    info.append(collections.line())
    if muts:
        info.append(f"mutations: {measured_m} due in the window, {len(lags)} "
                    f"visible; {len(muts)} applied in all")
    return {"e2e": e2e, "batches": batches, "mutations": [m[1] for m in muts],
            "attempted": measured_q + measured_m,
            "unanswered": measured_q - int(lat.size),
            "unseen": measured_m - len(lags), "spans": spans,
            "trace": tracer.read(), "info": info}


def _closed_loop(svc, traffic: Traffic, *, seed: int, seconds: float, trace: bool,
                 t_process: float) -> dict:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    stream = traffic.queries(rng)
    sched = svc.scheduler
    note = _Annotate(trace)
    tracer = _Tracer(trace)
    outstanding = int(traffic.spec["outstanding"])
    queries: list = []            # (terms, site, k, cond): plain tuples
    qidx: dict[int, int] = {}     # id(ticket) -> query index, while in flight
    batches: list[Batch] = []
    spans: list = []
    lat: list[float] = []

    def submit():
        q = next(stream)
        with note("bench.submit"):
            tk = svc.submit(list(q.terms), q.site, k=q.k)
        qidx[id(tk)] = len(queries)
        queries.append((q.terms, q.site, q.k, q.cond))

    clock = time.perf_counter
    _quiet_heap()
    tracer.start(clock())
    t0 = clock()
    setup_s = t0 - t_process
    collections = _Collections().start()
    for _ in range(outstanding):
        submit()
    t_last = t0
    while True:
        now = clock()
        tracer.maybe_stop(now)
        if now - t0 >= seconds:
            break
        ts = now
        with note("bench.step"):
            real = sched.step()
        te = clock()
        batches.append(Batch(0, _answers(real, qidx, spans if tracer.active else None),
                             ts, te, tracer.active))
        for tk in real:
            lat.append((tk.finish_time - tk.submit_time) * 1e3)
            submit()
        t_last = te
    collections.stop()
    tracer.stop()
    window = t_last - t0
    # the queries still in flight are answered and checked, not timed
    tail = sched.drain()
    if tail:
        batches.append(Batch(0, _answers(tail, qidx, None), t_last, clock(), False))
    _with_queries(batches, [Query(*q) for q in queries])
    e2e = {"setup_s": setup_s, "qps": len(lat) / window}
    info = [f"closed loop: {outstanding} in flight; {len(lat)} answered in "
            f"{window:.3f} s; {len(batches)} batches; latency p50 "
            f"{_pct(lat, 50):.3f} ms" if lat else "closed loop: nothing answered",
            collections.line()]
    return {"e2e": e2e, "batches": batches, "mutations": [],
            "attempted": len(lat) + len(tail), "unanswered": 0, "unseen": 0,
            "spans": spans, "trace": tracer.read(), "info": info}
