"""Measured throughput/latency of OUR JAX engine (the real slave).

Measures per-shard query latency of the JAX slave engine over a synthetic
corpus (5 shards, document-striped), then feeds the *measured* latencies
through the hybrid model exactly like the paper feeds its 5-node
measurements: partitioning-method slave max -> 300-shard projection.

Also reports the §2 limited-search strategy comparison (attribute
embedding vs doc-site gather vs siteId-as-text ZigZag) and the posting-
skipping fraction — the paper's two tightly-integrated-IR claims.
"""
import time

import numpy as np
import jax

from repro.core.engine import make_query_batch, query_topk
from repro.core.index import build_index, partition_corpus
from repro.core.perfmodel import QUERY_MIX_DEFAULT
from repro.core.queries import WorkloadConfig, batch_by_k, generate_workload
from repro.core.slave_max import partitioning_method
from repro.data.corpus import CorpusConfig, generate_corpus
from repro.kernels import ops


def _timed(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / reps


def main(backend: str = "jnp"):
    corpus = generate_corpus(
        CorpusConfig(n_docs=20_000, vocab_size=3_000, mean_doc_len=60,
                     n_sites=100, seed=0)
    )
    meta_idx = [build_index(p) for p in partition_corpus(corpus, 5)]
    meta = meta_idx[0][1]

    specs = generate_workload(
        meta, QUERY_MIX_DEFAULT, WorkloadConfig(n_queries=64, seed=1)
    )
    batches = batch_by_k(specs, t_max=4, meta=meta)

    # per-shard, per-k-batch latency (the "slave measurement")
    r = 6
    sojourns = []
    for k, (qb, ss) in sorted(batches.items()):
        per_query_shard = np.zeros((len(ss), 5 * r))
        for rep in range(r):
            for s, (idx, _) in enumerate(meta_idx):
                dt = _timed(query_topk, idx, qb, k=k, window=2048,
                            backend=backend, reps=1)
                per_query_shard[:, rep * 5 + s] = dt / len(ss)
        sojourns.append(per_query_shard)
        us = per_query_shard.mean() * 1e6
        print(f"engine,slave_query_k{k},{us:.1f},per_query_per_shard_us")
    sj = np.concatenate(sojourns, axis=0)

    for ns in (5, 50, 300):
        est = partitioning_method(np.tile(sj, (1, (ns // (5 * r)) + 1)), ns).mean()
        print(f"engine,slave_max_ns{ns},{est*1e6:.1f},partitioning_method_us")

    # §2 strategies: attribute embedding vs gather vs site-term join
    idx_full, meta_full = build_index(corpus)
    q = [([7], 3), ([15], 5), ([2, 9], 1), ([4], 0)] * 8
    for strat in ("embed", "gather", "site_term"):
        qb = make_query_batch(q, t_max=4, meta=meta_full, strategy=strat)
        dt = _timed(query_topk, idx_full, qb, k=10, window=2048,
                    attr_strategy=strat, backend=backend)
        print(f"engine,limited_search_{strat},{dt/len(q)*1e6:.1f},per_query_us")

    # posting skipping effectiveness.  Tile skipping pays when the driver
    # tile's docID span overlaps few of the other list's tiles: dense x
    # dense lists skip most tiles; a sparse driver spans everything (its
    # measured ~0 fraction is the honest negative case).
    o = np.asarray(idx_full.offsets); ln = np.asarray(idx_full.lengths)
    post = np.asarray(idx_full.postings)
    import jax.numpy as jnp

    def window_of(t, width=None):
        w = int(ln[t]) if width is None else width
        w = max(1024, ((w + 1023) // 1024) * 1024)
        return jnp.asarray(post[o[t]:o[t] + w])

    frac_dd = float(ops.skip_fraction(window_of(1), window_of(0)))
    frac_rc = float(ops.skip_fraction(window_of(2000), window_of(0)))
    print(f"engine,posting_skip_fraction_dense_dense,{frac_dd:.4f},tiles_skipped")
    print(f"engine,posting_skip_fraction_sparse_driver,{frac_rc:.4f},honest_negative")


if __name__ == "__main__":
    main()
