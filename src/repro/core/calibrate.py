"""Closed-loop calibration of the hybrid perf model from the live engine.

The paper's hybrid model (§4) is analytic for the master and network and
*experimental* for the slaves; §5.1 fits the analytic constants (Table 3)
by measuring the real system.  :mod:`repro.core.perfmodel` ships Table 3
verbatim, but those numbers describe a 2012 Odysseus cluster — not this
JAX engine.  This module is the missing measurement half for *our* system:

- :func:`measure_service_times` times the slave phase
  (:func:`~repro.core.parallel.slave_topk_unmerged`) against the full
  pipeline (:func:`~repro.core.parallel.distributed_query_topk`) on the
  same batch; the difference is the measured per-query master service time
  (Formula (4)'s ``ST_master``), and the per-repetition slave timings feed
  the paper's partitioning method (§4.2, Fig 9) for the expected slave max.
- :func:`fit_merge_constants` measures the master's top-k merge at several
  merge widths and least-squares Formula (7)
  ``T_merge = k * (ceil(log2 ns) * t_comparison + t_base)`` for the two
  loser-tree constants.
- :func:`calibrate_from_engine` assembles a fitted
  :class:`~repro.core.perfmodel.MasterParams`: the merge constants from the
  fit, the fixed/per-slave split of the residual master overhead by an
  attribution ratio (documented below), context-switch cost zero (the
  in-process engine has no RPC thread switches), and unmeasured top-k rows
  extrapolated with the paper's Table 3 ratios.

``benchmarks/bench_serving.py`` closes the loop: it sweeps arrival rates
through the scheduler's open-loop replay and reports measured vs
model-projected response time with Formula (18) estimation error, using
the :class:`Calibration` produced here — never ``PAPER_TABLE3``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.engine import make_query_batch
from repro.core.index import INVALID_DOC
from repro.core.parallel import (
    _row_topk,
    distributed_query_topk,
    place_index,
    slave_topk_unmerged,
)
from repro.core.perfmodel import (
    KS,
    SINGLE_10_ONLY,
    MasterParams,
    NetworkParams,
    OdysPerfModel,
    PAPER_TABLE3_MASTER,
    engine_cluster,
    sojourn,
)
from repro.core.slave_max import partitioning_method

# Attribution of the k=10 master overhead between the fixed per-query part
# (T_parent_proc) and the per-slave part ((T_child_proc+rpc)*ns): a single
# measured ns cannot separate them, so we follow the paper's own Table 3
# proportions, where the parent's fixed cost dominates at small ns.
_PARENT_FRACTION = 0.8

_FLOOR = 1e-8  # seconds; keeps fitted params positive and queues stable


def _timed(fn, *args, reps: int = 3, **kw) -> list[float]:
    """Per-repetition wall times (seconds) after one warmup/compile call."""
    jax.block_until_ready(fn(*args, **kw))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        out.append(time.perf_counter() - t0)
    return out


@dataclasses.dataclass(frozen=True)
class Calibration:
    """Fitted model parameters + the raw measurements behind them."""

    master: MasterParams
    network: NetworkParams
    ns: int
    st_slave: dict        # per-k measured slave service time / query (s)
    st_master: dict       # per-k measured master service time / query (s)
    slave_max: dict       # per-k partitioning-method E[slave max] (s)
    t_comparison: float
    t_base: float
    n_sets: int = 1       # replicated sets the arrival stream spreads over

    def with_sets(self, n_sets: int) -> "Calibration":
        """Same fitted parameters projected at ``n_sets`` replicated sets:
        Formula (17) spreads the arrival stream as ``lam / n_sets`` (§5.2).
        The multi-set bench sweep uses this to project each slice count
        from one calibration."""
        return dataclasses.replace(self, n_sets=int(n_sets))

    def slave_max_time(self, sct: str, k: int, lam: float, ns: int) -> float:
        """The hybrid's experimental half for Formula (17), load-aware.

        The in-process mesh runs one batch at a time, so the slave tier is
        a single deterministic server at the measured per-query service
        time: its sojourn under the set's arrival rate is the M/D/1
        Formula (13), and the measured partitioning-method max inflates it
        by the calibration-time max/mean ratio (§4.2's disk-variance
        spread, here the shard-lockstep spread).  Unmeasured k falls back
        to the nearest measured k.
        """
        del sct, ns
        kk = k if k in self.slave_max else min(
            self.slave_max, key=lambda m: abs(m - k)
        )
        st = self.st_slave[kk]
        inflation = self.slave_max[kk] / max(st, _FLOOR)
        return sojourn(lam / self.n_sets, st) * inflation

    def projected_response(
        self,
        lam: float,
        *,
        batch_size: int = 1,
        max_wait: float = 0.0,
        mix=SINGLE_10_ONLY,
    ) -> float:
        """Formula (17) projection at arrival rate ``lam``, plus the
        micro-batcher's expected formation delay.

        This is the single code path both validation surfaces use:
        ``benchmarks/bench_serving.py`` reports it offline against the
        replay measurements, and the online
        :class:`~repro.obs.residual.ModelResidualMonitor` compares it
        against live spans — so the two Formula (18) errors agree by
        construction.

        The formation term is the mean residual wait of a Poisson arrival
        in a size-``batch_size`` batch former, capped by the formation
        deadline: ``min(max_wait, (batch_size - 1) / (2 lam))``.
        """
        model = OdysPerfModel(master=self.master, network=self.network)
        cluster = engine_cluster(self.ns, n_sets=self.n_sets)
        base = model.total_response_time(lam, cluster, mix, self.slave_max_time)
        formation = (
            min(max_wait, (batch_size - 1) / (2.0 * lam))
            if batch_size > 1 else 0.0
        )
        return base + formation


def fit_merge_constants(
    *,
    k_values=(10, 50),
    widths=(2, 4, 8),
    q: int = 8,
    reps: int = 3,
    backend: str = "jnp",
    interpret: bool | None = None,
    seed: int = 0,
) -> tuple[float, float, dict]:
    """Fit Formula (7)'s (t_comparison, t_base) from measured merges.

    Times the master's per-row best-k reduction (the same ``_row_topk``
    the tournament/allgather merges run) over ``widths`` candidate sets of
    ``w * k`` each, then least-squares the loser-tree cost model
    ``T = k * (ceil(log2 w) * t_cmp + t_base)`` per query.
    """
    rng = np.random.default_rng(seed)
    rows_x, rows_y, raw = [], [], {}
    for k in k_values:
        for w in widths:
            cands = jnp.asarray(
                np.sort(rng.integers(0, 2**30, size=(q, w * k)))
                .astype(np.int32)
            )
            merge = jax.jit(partial(_row_topk, k=k, backend=backend,
                                    interpret=interpret))
            per_q = min(_timed(merge, cands, reps=reps)) / q
            raw[(k, w)] = per_q
            rows_x.append([k * math.ceil(math.log2(w)), k])
            rows_y.append(per_q)
    sol, *_ = np.linalg.lstsq(
        np.asarray(rows_x, dtype=np.float64),
        np.asarray(rows_y, dtype=np.float64),
        rcond=None,
    )
    t_cmp = max(float(sol[0]), _FLOOR)
    t_base = max(float(sol[1]), _FLOOR)
    return t_cmp, t_base, raw


def measure_service_times(
    index,
    meta,
    mesh,
    *,
    ns: int,
    k: int,
    window: int = 1024,
    t_max: int = 2,
    q: int = 8,
    reps: int = 4,
    backend: str = "jnp",
    interpret: bool | None = None,
    merge: str = "tournament",
    seed: int = 0,
) -> tuple[float, float, np.ndarray]:
    """Measure (st_slave, st_master, slave_samples) per query at top-``k``.

    ``st_slave`` is the slave-phase service time (no merge); ``st_master``
    is the **full master path** — query-batch construction, dispatch, the
    distributed merge, and host-side result extraction, i.e. everything
    the serving executor does per batch — minus the slave phase: the live
    analogue of Formula (4), where the paper's ``T_parent_proc`` is
    likewise the master's own per-query processing.  ``slave_samples`` is
    the per-repetition slave-time series, repetition-major, ready for the
    partitioning method (§4.2 Step 1.2 builds exactly this sequence).
    """
    rng = np.random.default_rng(seed)
    vocab_head = max(2, min(64, meta.vocab_size))
    queries = [([int(t)], None)
               for t in rng.integers(0, vocab_head, size=q)]
    qb = make_query_batch(queries, t_max=t_max, meta=meta)
    common = dict(mesh=mesh, ns=ns, k=k, window=window,
                  backend=backend, interpret=interpret)

    def master_path(qs):
        """What the serving executor runs per batch (scheduler.py)."""
        batch = make_query_batch(qs, t_max=t_max, meta=meta)
        res = distributed_query_topk(index, batch, merge=merge, **common)
        docs = np.asarray(res.docids)
        hits = np.asarray(res.n_hits)
        return [
            ([int(d) for d in row if d != INVALID_DOC], int(h))
            for row, h in zip(docs, hits)
        ]

    slave_times = _timed(slave_topk_unmerged, index, qb, reps=reps, **common)
    e2e_times = _timed(master_path, queries, reps=reps)
    st_slave = min(slave_times) / q
    st_master = max(min(e2e_times) / q - st_slave, _FLOOR)
    # One slave-max sample per repetition x shard; the mesh runs shards in
    # lockstep, so per-shard sojourn == the measured slave-phase time.
    samples = np.repeat(np.asarray(slave_times) / q, ns)[None, :]
    return st_slave, st_master, samples


def calibrate_from_engine(
    index,
    meta,
    mesh,
    *,
    ns: int,
    k_values=(10, 50),
    window: int = 1024,
    t_max: int = 2,
    q: int = 8,
    reps: int = 4,
    backend: str = "jnp",
    interpret: bool | None = None,
    merge: str = "tournament",
    n_sets: int = 1,
    seed: int = 0,
) -> Calibration:
    """Fit a :class:`MasterParams` from live-engine measurements.

    ``k_values`` must include 10 (the unit query every weight in
    §4.1.3 is normalized against).  Top-k rows the caller does not measure
    (e.g. k=1000 on a small CI corpus) are extrapolated with the paper's
    Table 3 ratios and marked by their absence from ``st_master``.
    """
    assert 10 in k_values, "the unit query (k=10) must be measured"
    index = place_index(index, mesh)    # timed calls must not re-ship the index
    t_cmp, t_base, _ = fit_merge_constants(
        k_values=k_values, q=q, reps=reps, backend=backend,
        interpret=interpret, seed=seed,
    )
    st_slave, st_master, slave_max = {}, {}, {}
    for k in k_values:
        s, m, samples = measure_service_times(
            index, meta, mesh, ns=ns, k=k, window=window, t_max=t_max,
            q=q, reps=max(reps, ns), backend=backend, interpret=interpret,
            merge=merge, seed=seed + k,
        )
        st_slave[k] = s
        st_master[k] = m
        slave_max[k] = float(partitioning_method(samples, ns).mean())

    # Formula (4) decomposition at the measured ns: subtract the fitted
    # merge cost, then split the residual overhead into the fixed parent
    # part and the per-slave RPC part by the attribution ratio.
    log_ns = math.ceil(math.log2(ns)) if ns > 1 else 0
    residual = {
        k: max(st_master[k] - k * (log_ns * t_cmp + t_base), _FLOOR)
        for k in k_values
    }
    t_parent = max(_PARENT_FRACTION * residual[10], _FLOOR)
    rpc = {
        k: max((residual[k] - t_parent) / ns, _FLOOR) for k in k_values
    }
    paper_rpc = PAPER_TABLE3_MASTER.T_master_rpc
    for k in KS:
        if k not in rpc:  # extrapolate with the paper's Table 3 ratio
            rpc[k] = rpc[10] * paper_rpc[k] / paper_rpc[10]
    master = MasterParams(
        T_parent_proc=t_parent,
        T_child_proc=0.0,
        T_master_rpc=dict(rpc),
        t_comparison=t_cmp,
        t_base=t_base,
        # No RPC thread context switches in-process: the term is inert,
        # but the ncs tables keep Table 3's structure for reporting.
        t_per_context_switch=0.0,
        ncs_base=dict(PAPER_TABLE3_MASTER.ncs_base),
        ncs_per_slave=dict(PAPER_TABLE3_MASTER.ncs_per_slave),
        alpha=PAPER_TABLE3_MASTER.alpha,
    )
    # In-process "network": a shared-memory hop.  Equal epsilon rows keep
    # every w_network weight at 1 and the network queue at ~zero load.
    network = NetworkParams(ST_network={k: 1e-9 for k in KS})
    return Calibration(
        master=master, network=network, ns=ns,
        st_slave=st_slave, st_master=st_master, slave_max=slave_max,
        t_comparison=t_cmp, t_base=t_base, n_sets=n_sets,
    )
