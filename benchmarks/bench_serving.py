"""Serving-pipeline benchmark: measured vs perf-model-projected response.

Closes the loop the paper closes in §5.1/Fig 11, but against OUR engine:

1. **Calibrate** — :func:`repro.core.calibrate.calibrate_from_engine`
   measures the slave phase, the master merge, and the slave max from the
   live mesh and fits a :class:`MasterParams` (never ``PAPER_TABLE3``).
2. **Measure** — Poisson arrival traces at several rates are replayed
   through the unified master scheduler
   (:meth:`repro.serving.scheduler.MasterScheduler.replay`): virtual
   arrivals + batch-formation deadlines, *real* measured batch service
   times, per-set occupancy.  The replayed tickets' mean response is the
   measured curve.
3. **Project** — Formula (17) via :class:`OdysPerfModel` with the fitted
   parameters; Formula (18) reports the estimation error per rate.

Also reports the result cache's effect: the same trace replayed with the
cache enabled (Zipf-repeating queries), with hit rate and mean response.

The sweep runs with live observability (:mod:`repro.obs`): each replay
folds its spans through a :class:`PhaseAggregator` (per-phase mean lines)
and a :class:`ModelResidualMonitor` — the *online* Formula (18) gauge,
printed next to the offline computation it must match (both call
:meth:`Calibration.projected_response`, so they agree by construction).

**Multi-set scale-out** (``sets``): the sweep from §5.2/Fig 12, measured.
Each set count S carves S *disjoint* mesh slices
(:func:`repro.core.parallel.set_mesh_slices`), serves a Poisson trace at
S x 0.5 mu through the sliced router path, and reports measured throughput
and response against the ``Calibration.with_sets(S)`` projection (Formula
(17)/(18) per set count).  Replay's per-set ``busy_until`` overlap would
credit ~S x throughput even to sets time-sharing one device pool; running
every set on its own disjoint slice is what makes that §5.2 independence
assumption *structurally* true — no device is shared, so per-set service
measured on a slice composes honestly.  Set counts needing more devices
than exist are skipped with a ``sets<S>_skipped`` record (CI raises the
pool with ``--devices``).

Emits ``serving,<metric>,<value>,<note>`` CSV lines.  On CPU the pallas
backend runs under the interpreter (semantics, not speed); the jnp numbers
are the meaningful CPU baseline.  ``smoke=True`` shrinks everything for
the CI lambda-sweep smoke step.
"""
import time

import numpy as np
import jax

from repro.core.calibrate import calibrate_from_engine
from repro.core.index import build_sharded_index, pack_flat_postings
from repro.core.parallel import set_mesh_slices
from repro.core.perfmodel import estimation_error
from repro.data.corpus import CorpusConfig, generate_corpus
from repro.kernels import ops
from repro.obs import (
    MetricsRegistry,
    ModelResidualMonitor,
    PhaseAggregator,
)
from repro.serving.search import SearchService


def poisson_trace(lam: float, n: int, vocab_head: int, *, repeat_frac: float,
                  seed: int):
    """(arrival_time, terms, site) tuples: Poisson arrivals at ``lam``,
    single-keyword queries, a ``repeat_frac`` share drawn from a small hot
    set (the cacheable mass of a production stream)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / lam, size=n))
    hot = rng.integers(0, max(2, vocab_head // 8), size=n)
    cold = rng.integers(0, vocab_head, size=n)
    use_hot = rng.random(n) < repeat_frac
    return [
        (float(t), [int(h if uh else c)], None)
        for t, h, c, uh in zip(arrivals, hot, cold, use_hot)
    ]


def _mean_response(tickets) -> float:
    return float(np.mean([t.response_time for t in tickets]))


def main(backend: str = "jnp", smoke: bool = False, sets=None):
    mode = "jnp" if backend == "jnp" else (
        "interpret" if ops.resolve_interpret() else "compiled"
    )
    n_docs = 600 if smoke else 8000
    vocab = 200 if smoke else 1200
    window = 512 if smoke else 1024
    n_queries = 48 if smoke else 240
    reps = 3 if smoke else 5
    k_values = (10,) if smoke else (10, 50)
    batch_size = 4

    corpus = generate_corpus(
        CorpusConfig(n_docs=n_docs, vocab_size=vocab, mean_doc_len=40,
                     n_sites=20, seed=7)
    )
    ns = 1
    sharded, meta = build_sharded_index(corpus, ns)
    mesh = jax.make_mesh((ns,), ("data",))

    # resident posting bytes: raw flat arrays vs the block-codec layout
    n_live = int(np.sum(np.asarray(sharded.lengths)))
    raw_bytes = int(np.asarray(sharded.postings).nbytes)
    packed_bytes = sum(
        pack_flat_postings(np.asarray(sharded.postings)[s]).nbytes()
        for s in range(ns)
    )
    print(f"serving,index_bytes_raw,{raw_bytes},flat_posting_bytes")
    print(f"serving,index_bytes_packed,{packed_bytes},words+descriptors")
    print(f"serving,bytes_per_posting_raw,{raw_bytes/max(n_live,1):.3f},"
          f"n_live={n_live}")
    print(f"serving,bytes_per_posting_packed,"
          f"{packed_bytes/max(n_live,1):.3f},n_live={n_live}")

    # --- 1. closed-loop calibration from the live engine -------------------
    cal = calibrate_from_engine(
        sharded, meta, mesh, ns=ns, k_values=k_values, window=window,
        q=batch_size, reps=reps, backend=backend,
    )
    for k in k_values:
        print(f"serving,st_slave_k{k},{cal.st_slave[k]*1e6:.2f},us_{mode}")
        print(f"serving,st_master_k{k},{cal.st_master[k]*1e6:.2f},us_{mode}")
        print(f"serving,slave_max_k{k},{cal.slave_max[k]*1e6:.2f},us_{mode}")
    print(f"serving,t_comparison,{cal.t_comparison*1e9:.2f},ns_fitted")
    print(f"serving,t_base,{cal.t_base*1e9:.2f},ns_fitted")

    # --- 2. open-loop lambda sweep through the scheduler -------------------
    def make_service(cache_size: int, registry=None) -> SearchService:
        svc = SearchService(
            sharded, meta, mesh, ns=ns, k=10, window=window, t_max=2,
            t_max_buckets=(2,), backend=backend,
            batch_size=batch_size, cache_size=cache_size,
            registry=registry,
        )
        return svc

    # capacity probe: one warmed batch's wall time bounds the service rate
    probe = make_service(cache_size=0)
    probe_q = [([int(t)], None) for t in range(batch_size)]
    probe.search(probe_q)
    t0 = time.perf_counter()
    probe.search(probe_q)
    batch_wall = time.perf_counter() - t0
    mu = batch_size / batch_wall
    print(f"serving,capacity,{mu:.1f},queries_per_sec_{mode}")

    for frac in (0.25, 0.5, 0.75):
        lam = frac * mu
        reg = MetricsRegistry()
        agg = PhaseAggregator(registry=reg)
        monitor = ModelResidualMonitor(
            cal, batch_size=batch_size, max_wait=batch_wall, lam=lam,
            window=n_queries, registry=reg,
        )
        svc = make_service(cache_size=0, registry=reg)
        svc.scheduler.max_wait = batch_wall  # batch-formation deadline
        trace = poisson_trace(lam, n_queries, min(64, vocab),
                              repeat_frac=0.0, seed=int(frac * 100))
        # warm the bucket's trace so replay measures steady-state service
        svc.search([(terms, site) for _, terms, site in trace[:batch_size]])
        # wire the span sinks only now: the warm batch's compile must not
        # pollute the phase means or the residual window
        svc.scheduler.span_sink = lambda s, a=agg, m=monitor: (
            a.fold(s), m.sink(s),
        )
        tickets = svc.scheduler.replay(trace)
        measured = _mean_response(tickets)
        # Formula (17) with the fitted params, plus the micro-batcher's
        # admission delay (a scheduler parameter, not a queueing effect) —
        # the one shared projection path (Calibration.projected_response).
        projected = cal.projected_response(
            lam, batch_size=batch_size, max_wait=svc.scheduler.max_wait
        )
        err = estimation_error(projected, measured)
        online = monitor.update()
        tag = f"lam{frac:.2f}mu"
        print(f"serving,{tag}_measured,{measured*1e6:.1f},mean_response_us")
        print(f"serving,{tag}_pad_fraction,{svc.stats()['pad_fraction']:.3f},"
              f"mean_inert_share_per_batch")
        print(f"serving,{tag}_model,{projected*1e6:.1f},"
              f"err_formula18={err:.4f}")
        print(f"serving,{tag}_residual_online,{online['error']:.4f},"
              f"formula18_gauge n={online['n']}")
        if frac == 0.5:
            # the paper's latency decomposition, measured (span means)
            for phase, mean in sorted(agg.means().items()):
                print(f"serving,phase_{phase},{mean*1e6:.2f},"
                      f"mean_us_lam{frac:.2f}mu")

    # --- 3. result cache under a Zipf-repeating stream ---------------------
    lam = 0.5 * mu
    trace = poisson_trace(lam, n_queries, min(64, vocab),
                          repeat_frac=0.6, seed=11)
    for cache_size, tag in ((0, "cache_off"), (1024, "cache_on")):
        svc = make_service(cache_size=cache_size)
        svc.scheduler.max_wait = batch_wall
        svc.search([(terms, site) for _, terms, site in trace[:batch_size]])
        tickets = svc.scheduler.replay(trace)
        stats = svc.stats()
        hit_rate = (
            svc.scheduler.cache.stats.hit_rate()
            if svc.scheduler.cache is not None else 0.0
        )
        print(f"serving,{tag}_response,{_mean_response(tickets)*1e6:.1f},"
              f"mean_response_us hit_rate={hit_rate:.2f} "
              f"batches={stats['n_batches']} "
              f"pad_fraction={stats['pad_fraction']:.3f}")

    # --- 4. multi-set scale-out on disjoint mesh slices --------------------
    # Arrival rate scales with the set count (S x 0.5 mu) so the per-set
    # load — and therefore the response time — stays matched across S:
    # the measured curve isolates added *capacity* from queueing relief.
    sweep = [1, 2, 4] if sets is None else sorted({int(s) for s in sets})
    n_dev = jax.device_count()
    usable = [S for S in sweep if S * ns <= n_dev]
    for S in sweep:
        if S not in usable:
            print(f"serving,sets{S}_skipped,1,"
                  f"needs_{S * ns}_devices_have_{n_dev}")
    thr: dict[int, float] = {}
    resp: dict[int, float] = {}
    for S in usable:
        slices = set_mesh_slices(S, ns)
        svc = SearchService(
            sharded, meta, slices[0], ns=ns, k=10, window=window, t_max=2,
            t_max_buckets=(2,), backend=backend,
            batch_size=batch_size, cache_size=0,
            n_sets=S, set_meshes=slices,
        )
        svc.scheduler.max_wait = batch_wall
        lam_s = S * 0.5 * mu
        trace = poisson_trace(lam_s, n_queries, min(64, vocab),
                              repeat_frac=0.0, seed=29 + S)
        # warm every slice's compiled path: the router spreads these S
        # sequential batches one per set (each dispatch busies its set)
        warm = [(terms, site) for _, terms, site in trace[:batch_size]]
        for _ in range(S):
            svc.search(warm)
        tickets = svc.scheduler.replay(trace)
        measured = _mean_response(tickets)
        makespan = max(t.finish_time for t in tickets)
        thr[S] = len(tickets) / makespan
        resp[S] = measured
        projected = cal.with_sets(S).projected_response(
            lam_s, batch_size=batch_size, max_wait=svc.scheduler.max_wait
        )
        err = estimation_error(projected, measured)
        per_set = "/".join(
            str(s["n_batches"]) for s in svc.stats()["sets"]
        )
        print(f"serving,sets{S}_throughput,{thr[S]:.1f},"
              f"qps lam={lam_s:.1f} batches_per_set={per_set}")
        print(f"serving,sets{S}_response_us,{measured * 1e6:.1f},"
              f"mean_response_us_{mode}")
        print(f"serving,sets{S}_model_err,{err:.4f},"
              f"formula18 projected={projected * 1e6:.1f}us")
    for S in usable:
        if S > 1 and 1 in thr:
            print(f"serving,sets{S}_throughput_x,{thr[S] / thr[1]:.3f},"
                  f"vs_single_set")
            print(f"serving,sets{S}_response_ratio,{resp[S] / resp[1]:.3f},"
                  f"vs_single_set")


if __name__ == "__main__":
    main()
