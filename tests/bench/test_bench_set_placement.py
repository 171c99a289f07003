"""A set of four slaves built and placed on four devices through the normal
path (``build_sharded_index`` -> ``SearchService``): each device holds only
its own shard's rows and no device the stacked index, the set's answers
equal the program's oracle (``sequential_reference``) and the benchmark's
per-slave reference (``bench/reference.py``) under both backends, and the
set-up counters are written per shard and per device.

Four devices are fixed when JAX starts, so the set runs in a child process
on four virtual CPU devices: this file, run as a script.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
NS = 4
WINDOW = 1024
BACKENDS = ("jnp", "pallas")


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_build_leaves_stay_on_the_host(child):
    assert child["built_host"] is True


def test_each_device_holds_only_its_shard(child):
    for name, shards in child["placement"].items():
        assert sorted(s["device"] for s in shards) == list(range(NS)), name
        assert sorted(s["row"] for s in shards) == list(range(NS)), name
        for s in shards:
            assert s["rows"] == 1, name
            assert s["equals_own_row"], name


def test_no_device_holds_the_stack(child):
    assert child["stacked_on_one_device"] == []


@pytest.mark.parametrize("backend", BACKENDS)
def test_set_answers_equal_the_oracle(child, backend):
    got = child["answers"][backend]
    assert len(got) == len(child["oracle"]) > 20
    assert got == child["oracle"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_set_answers_equal_the_bench_reference(child, backend):
    assert child["answers"][backend] == child["reference"]
    # the window binds on some slave: the set's answer is not one slave's
    assert child["window_binds"]


def test_setup_counters_per_shard_and_device(child):
    m = child["metrics"]
    assert m["shard_build_count"] == NS
    assert m["shard_build_sum"] > 0
    assert sorted(m["device_bytes"]) == [str(d) for d in range(NS)]
    assert m["device_bytes"] == m["placed_bytes"]
    assert len(set(m["device_bytes"].values())) == 1    # shards of equal width
    assert m["off_registry"] == []


def test_scopes_on_four_devices(child):
    assert child["scoped"] == {"collective-permute": "odys.set_merge",
                               "all-reduce": "odys.set_merge"}


def main() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import re

    import jax
    import numpy as np

    from bench import reference
    from repro.core.engine import make_query_batch
    from repro.core.index import INVALID_DOC, build_index, build_sharded_index, partition_corpus
    from repro.core.parallel import distributed_query_topk, sequential_reference
    from repro.data.corpus import CorpusConfig, generate_corpus
    from repro.obs.registry import MetricsRegistry
    from repro.serving.search import SearchService

    assert jax.device_count() == NS
    corpus = generate_corpus(CorpusConfig(n_docs=6000, vocab_size=400, mean_doc_len=40,
                                          n_sites=8, seed=11))
    index, meta = build_sharded_index(corpus, NS)
    out = {"built_host": all(isinstance(x, np.ndarray) for x in index)}
    mesh = jax.make_mesh((NS,), ("data",))

    rng = np.random.default_rng(5)
    queries = []
    for _ in range(32):
        n_terms = int(rng.integers(1, 4))
        terms = sorted({int(t) for t in rng.zipf(1.3, size=n_terms) - 1 if t < 400})
        site = int(rng.integers(0, 8)) if rng.random() < 0.25 else None
        queries.append((terms or [0], site))
    queries += [([0], None), ([0, 1], None), ([1], 2)]
    k = 50

    shards = [build_index(p)[0] for p in partition_corpus(corpus, NS)]
    batch = make_query_batch(queries, t_max=4, meta=meta)
    want = sequential_reference(shards, batch, ns=NS, k=k, window=WINDOW)
    out["oracle"] = [[[int(d) for d in row if d != INVALID_DOC], int(h)]
                     for row, h in zip(np.asarray(want.docids), np.asarray(want.n_hits))]
    slaves = reference.slave_lists(corpus.doc_offsets, corpus.doc_terms, corpus.doc_site,
                                   corpus.vocab_size, terms={t for q, _ in queries for t in q},
                                   window=WINDOW, ns=NS)
    ref = [reference.set_answer(slaves, None, tuple(t), s, k, WINDOW) for t, s in queries]
    out["reference"] = [[d, n] for d, n, _ in ref]
    out["window_binds"] = any(base > WINDOW for _, _, drv in ref for base, _ in drv)

    out["answers"] = {}
    for backend in BACKENDS:
        reg = MetricsRegistry()
        svc = SearchService(index, meta, mesh, ns=NS, k=k, window=WINDOW, t_max=4,
                            batch_size=8, cache_size=0, backend=backend, registry=reg)
        out["answers"][backend] = [[h.docids, h.n_hits] for h in svc.search(queries)]
    # the last service's placement and counters
    out["placement"] = {
        name: [{"device": s.device.id, "row": s.index[0].start or 0,
                "rows": s.data.shape[0],
                "equals_own_row": bool(np.array_equal(np.asarray(s.data)[0],
                                                      host[s.index[0].start or 0]))}
               for s in leaf.addressable_shards]
        for name, leaf, host in zip(index._fields, svc.index, index)}
    stacked = {x.shape for x in index}
    out["stacked_on_one_device"] = [
        str(a.shape) for a in jax.live_arrays()
        if a.shape in stacked and len(a.sharding.device_set) == 1]
    fams = {name: series for name, _, _, series in reg.collect()}
    (_, hist), = fams["odys_index_shard_build_seconds"]
    placed = {}
    for leaf in svc.index:
        for s in leaf.addressable_shards:
            placed[str(s.device.id)] = placed.get(str(s.device.id), 0) + s.data.nbytes
    out["metrics"] = {
        "shard_build_count": hist.count, "shard_build_sum": hist.sum,
        "device_bytes": {lab["device"]: g.value
                         for lab, g in fams["odys_index_device_bytes"]},
        "placed_bytes": {d: float(n) for d, n in placed.items()},
        "off_registry": [n for n, *_ in SearchService(
            index, meta, mesh, ns=NS, window=WINDOW).registry.collect()],
    }

    hlo = distributed_query_topk.lower(
        svc.index, batch, None, mesh=mesh, ns=NS, k=k, window=WINDOW,
    ).compile().as_text()
    out["scoped"] = {}
    for line in hlo.splitlines():
        m = re.search(r"= \S+ (collective-permute|all-reduce)\(.*op_name=\"([^\"]*)\"", line)
        if m:
            out["scoped"][m.group(1)] = re.search(r"odys\.[a-z_]+", m.group(2)).group(0)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
