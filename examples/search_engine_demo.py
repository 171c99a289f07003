"""End-to-end ODYS search engine: distributed shards, workload at a Poisson
rate, measured latencies fed through the partitioning method, failover +
straggler mitigation — the full serving story on one box.

    PYTHONPATH=src python examples/search_engine_demo.py
(spawns 8 fake devices; must run as its own process)
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import time                                   # noqa: E402
import numpy as np                            # noqa: E402
import jax                                    # noqa: E402

from repro.core.engine import make_query_batch                    # noqa: E402
from repro.core.faults import SpeculationPolicy, query_latency_with_speculation  # noqa: E402
from repro.core.index import INVALID_DOC, build_sharded_index     # noqa: E402
from repro.core.parallel import distributed_query_topk, place_index  # noqa: E402
from repro.core.perfmodel import QUERY_MIX_DEFAULT                # noqa: E402
from repro.core.queries import WorkloadConfig, batch_by_k, generate_workload  # noqa: E402
from repro.core.slave_max import partitioning_method              # noqa: E402
from repro.data.corpus import CorpusConfig, generate_corpus       # noqa: E402
from repro.launch.elastic import FailoverRouter, rescale          # noqa: E402


def main():
    ns = 4
    backend = os.environ.get("ODYS_BACKEND", "jnp")  # jnp | pallas
    mesh = jax.make_mesh((ns,), ("data",), devices=jax.devices()[:ns])
    corpus = generate_corpus(
        CorpusConfig(n_docs=8_000, vocab_size=1_200, mean_doc_len=50, n_sites=40)
    )
    sharded, meta = build_sharded_index(corpus, ns)
    sharded = place_index(sharded, mesh)      # each slave's shard on its device
    print(f"[demo] {ns} slaves x {corpus.n_docs // ns} docs each")

    # workload
    specs = generate_workload(
        meta, QUERY_MIX_DEFAULT, WorkloadConfig(n_queries=48, arrival_rate=50.0)
    )
    groups = batch_by_k(specs, meta=meta)

    lat = []
    for k, (qb, ss) in sorted(groups.items()):
        kk = min(k, 50)  # cap for the demo
        res = distributed_query_topk(
            sharded, qb, mesh=mesh, ns=ns, k=kk, window=2048,
            merge="tournament", backend=backend,
        )
        jax.block_until_ready(res.docids)
        t0 = time.perf_counter()
        res = distributed_query_topk(
            sharded, qb, mesh=mesh, ns=ns, k=kk, window=2048,
            merge="tournament", backend=backend,
        )
        jax.block_until_ready(res.docids)
        dt = (time.perf_counter() - t0) / qb.n_queries
        lat += [dt] * qb.n_queries
        n_valid = int((res.docids[0] != INVALID_DOC).sum())
        print(f"[demo] k={k}: {qb.n_queries} queries, "
              f"{dt*1e6:.0f} us/query, e.g. {n_valid} results for q0")

    # partitioning-method projection from measured latencies
    sj = np.tile(np.array(lat)[:, None], (1, ns * 80)) * \
        np.random.default_rng(0).lognormal(0, 0.25, size=(len(lat), ns * 80))
    for target_ns in (4, 64, 300):
        est = partitioning_method(sj, target_ns).mean()
        print(f"[demo] projected slave max @ {target_ns} slaves: {est*1e6:.0f} us")

    # failover + straggler mitigation
    router = FailoverRouter(n_sets=3, ns=ns)
    router.observe_latencies(sj)
    router.health.fail(1)
    routes = router.route(1000)
    rng = np.random.default_rng(1)
    primary = rng.lognormal(np.log(np.mean(lat)), 0.25, size=(500, ns))
    primary[::23, 2] *= 25.0
    replica = rng.lognormal(np.log(np.mean(lat)), 0.25, size=(500, ns))
    with_spec, rate = query_latency_with_speculation(
        primary, replica, router.slo, router.policy
    )
    print(f"[demo] set 1 down -> traffic on sets {sorted(set(routes))}; "
          f"speculation rate {rate:.1%}, "
          f"p99 {np.percentile(primary.max(1), 99)*1e6:.0f} -> "
          f"{np.percentile(with_spec, 99)*1e6:.0f} us")

    # elastic rescale 4 -> 6 shards (deterministic re-stripe)
    sharded6, _ = rescale(corpus, 6)
    print(f"[demo] rescaled to 6 shards: postings {sharded6.postings.shape}")
    print("[demo] done")


if __name__ == "__main__":
    main()
