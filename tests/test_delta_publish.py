"""The delta publish as a patch: ``DeltaWriter.device_delta`` ships only the
term slabs and documents written since the placed snapshot and scatters
them into a copy of it on the device.

- every published field equals :meth:`DeltaWriter.host_delta` (the whole
  snapshot rebuilt from the mirrors) bit for bit, TILE padding tail and
  skip table included, for both writers, under random
  insert/delete/update streams with a publish between batches;
- each bucket boundary, the overflow past the last bucket and a rebase to
  new shapes publish the way they should, and the counters say so;
- a snapshot published earlier never changes afterwards;
- no patch publish compiles once the first snapshot is placed;
- the search service answers as a from-scratch rebuild does after patched
  publishes, on its one mesh and on per-set meshes.
"""
import sys
import threading

import numpy as np
import pytest

import jax

from repro.core.index import build_index, build_sharded_index
from repro.core.parallel import set_mesh_slices
from repro.data.corpus import (
    CorpusConfig,
    MutationConfig,
    apply_mutations,
    generate_corpus,
    generate_mutations,
)
from repro.indexing import DeltaWriter, ShardedDeltaWriter, compact
from repro.indexing.delta import PATCH_BUCKETS, _apply_patch
from repro.obs.registry import MetricsRegistry, set_registry
from repro.serving.search import SearchService

WRITERS = (DeltaWriter, ShardedDeltaWriter)


def make_writer(cls, corpus, meta, ns, reg, **kw):
    """A writer whose publish counters land in ``reg``."""
    if cls is ShardedDeltaWriter:
        return cls(corpus, meta, ns, registry=reg, **kw)
    prev = set_registry(reg)
    try:
        return cls(corpus, meta, ns, **kw)
    finally:
        set_registry(prev)


def publishes(reg) -> dict[str, float]:
    out = {"patch": 0.0, "full": 0.0}
    for name, _, _, series in reg.collect():
        if name == "odys_delta_publish_total":
            for labels, c in series:
                out[labels["mode"]] = c.value
    return out


def assert_matches_host(w, dev):
    host = w.host_delta()
    for name, d, h in zip(dev._fields, dev, host):
        d = np.asarray(d)
        assert d.shape == h.shape and d.dtype == h.dtype, name
        assert np.array_equal(d, h), name


@pytest.fixture(scope="module")
def small():
    corpus = generate_corpus(
        CorpusConfig(n_docs=300, vocab_size=120, mean_doc_len=15,
                     n_sites=6, seed=17)
    )
    _, meta = build_index(corpus)
    return corpus, meta


@pytest.fixture(scope="module")
def wide():
    """A vocabulary past the last bucket, so one insert can dirty any
    number of slabs, and more base documents than the last bucket."""
    n = PATCH_BUCKETS[-1] + 100
    corpus = generate_corpus(
        CorpusConfig(n_docs=n, vocab_size=n, mean_doc_len=6, n_sites=4,
                     seed=3)
    )
    _, meta = build_index(corpus)
    return corpus, meta


@pytest.mark.parametrize("cls", WRITERS)
def test_patches_equal_the_full_snapshot(small, cls):
    corpus, meta = small
    reg = MetricsRegistry()
    w = make_writer(cls, corpus, meta, 2, reg, term_capacity=256,
                    doc_headroom=256)
    muts = generate_mutations(
        corpus, MutationConfig(n_ops=120, p_insert=0.45, p_delete=0.25,
                               p_update=0.3, mean_doc_len=15, seed=8)
    )
    rng = np.random.default_rng(5)
    kept = [(w.device_delta(), jax.tree.map(np.array, w.host_delta()))]
    done = 0
    while done < len(muts):
        step = int(rng.integers(1, 6))
        w.apply(muts[done:done + step])
        done += step
        dev = w.device_delta()
        assert_matches_host(w, dev)
        kept.append((dev, jax.tree.map(np.array, w.host_delta())))
    # a published snapshot is never written again
    for dev, host in kept:
        for d, h in zip(dev, host):
            assert np.array_equal(np.asarray(d), h)
    assert publishes(reg) == {"patch": len(kept) - 1, "full": 1.0}


def _dirty(w, n_slabs: int, site: int = 0):
    """Insert one document that writes exactly ``n_slabs`` term slabs (its
    keywords plus its site's term) of the one shard."""
    n_terms = n_slabs - int(w.include_site_terms)
    first = w.n_docs % (w.vocab_size - n_terms)
    w.insert_docs([(list(range(first, first + n_terms)), site)])


@pytest.mark.parametrize("cls", WRITERS)
def test_bucket_boundaries_and_overflow(wide, cls):
    corpus, meta = wide
    reg = MetricsRegistry()
    w = make_writer(cls, corpus, meta, 1, reg, term_capacity=128,
                    doc_headroom=64)
    w.device_delta()
    want = {"patch": 0, "full": 1}
    # (slabs dirtied, the bucket they pad to; None: past the last, full)
    cases = [
        case
        for b, nxt in zip(PATCH_BUCKETS, PATCH_BUCKETS[1:] + (None,))
        for case in ((b, b), (b + 1, nxt))
    ]
    for n_slabs, rows in cases:
        _dirty(w, n_slabs)
        pub = w.host_publish()
        assert pub.slabs == (n_slabs if rows else w.ns * w.n_terms)
        assert pub.rows == (rows or 0)
        assert (pub.full is None) == (rows is not None)
        want["patch" if rows else "full"] += 1
        assert_matches_host(w, w.device_delta())
        assert publishes(reg) == want
    # documents share the bucket: deletes write no slab, only flags
    w.delete_docs(range(PATCH_BUCKETS[0] + 1))
    assert w.host_publish().rows == PATCH_BUCKETS[1]
    assert_matches_host(w, w.device_delta())
    w.delete_docs(range(100, 100 + PATCH_BUCKETS[-1] + 1))
    assert w.host_publish().full is not None
    assert_matches_host(w, w.device_delta())
    assert publishes(reg) == {"patch": want["patch"] + 1,
                              "full": want["full"] + 1}


def test_patch_publishes_do_not_compile(wide):
    corpus, meta = wide
    w = DeltaWriter(corpus, meta, 1, term_capacity=128, doc_headroom=64)
    w.device_delta()               # the first placement compiles every bucket
    size0 = _apply_patch._cache_size()
    for b in PATCH_BUCKETS:
        _dirty(w, b)
        assert w.host_publish().rows == b
        assert_matches_host(w, w.device_delta())
    assert _apply_patch._cache_size() == size0


@pytest.mark.parametrize("cls", WRITERS)
def test_rebase_to_new_shapes_publishes_full(small, cls):
    corpus, meta = small
    reg = MetricsRegistry()
    w = make_writer(cls, corpus, meta, 2, reg, term_capacity=128,
                    doc_headroom=64)
    w.apply(generate_mutations(corpus, MutationConfig(n_ops=30, seed=2)))
    w.device_delta()
    (g,) = w.insert_docs([([1, 2, 3], 1)])
    old = w.device_delta()
    old_host = jax.tree.map(np.array, w.host_delta())
    assert publishes(reg) == {"patch": 1.0, "full": 1.0}
    compact(w, term_capacity=256, doc_headroom=128)
    new = w.device_delta()
    assert publishes(reg) == {"patch": 1.0, "full": 2.0}
    assert new.postings.shape != old.postings.shape
    assert_matches_host(w, new)
    (g2,) = w.insert_docs([([4, 7], 2)])
    w.update_docs([(g, [5], None)])
    w.delete_docs([g2])
    assert_matches_host(w, w.device_delta())
    assert publishes(reg) == {"patch": 2.0, "full": 2.0}
    for d, h in zip(old, old_host):
        assert np.array_equal(np.asarray(d), h)


def test_publishes_racing_ingest_lose_no_write(small):
    """Ingest threads write while the main thread publishes: each publish
    runs frozen, so a slab or document written between two publishes ships
    with the next one, and the last publish equals the full snapshot."""
    corpus, meta = small
    w = ShardedDeltaWriter(corpus, meta, 4, term_capacity=512,
                           doc_headroom=1024)
    errs = []

    def ingest(tid):
        try:
            rng = np.random.default_rng(tid)
            for _ in range(60):
                terms = [int(t) for t in rng.choice(120, 4, replace=False)]
                (g,) = w.insert_docs([(terms, tid % 6)])
                w.update_docs([(g, terms[:2], None)])
                if rng.random() < 0.3:
                    w.delete_docs([g])
        except Exception as e:  # surface in the main thread
            errs.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ingest, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            w.device_delta()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert not errs
    assert_matches_host(w, w.device_delta())


QUERIES = [([3], None), ([3, 9], None), ([1, 4, 12], None), ([2], 3),
           ([5, 8], 1), ([110], None), ([0, 7], 5)]


@pytest.mark.parametrize("sets", [False, True], ids=["mesh", "set_meshes"])
def test_service_answers_after_patched_publishes(small, sets):
    """Merge-on-read over patched snapshots answers as a from-scratch
    rebuild of the mutated corpus does, on the service mesh and on a
    per-set mesh (placed per set from the same snapshot)."""
    corpus, _ = small
    sharded, meta = build_sharded_index(corpus, 1)
    mesh = jax.make_mesh((1,), ("data",))
    reg = MetricsRegistry()
    extra = {"n_sets": 1, "set_meshes": set_mesh_slices(1, 1)} if sets else {}
    svc = SearchService(
        sharded, meta, mesh, ns=1, k=10, window=1024, cache_size=0,
        writer=make_writer(DeltaWriter, corpus, meta, 1, reg,
                           term_capacity=256, doc_headroom=128),
        **extra,
    )
    muts = generate_mutations(
        corpus, MutationConfig(n_ops=40, p_insert=0.45, p_delete=0.25,
                               p_update=0.3, mean_doc_len=15, seed=21)
    )
    versions = set()
    for i, m in enumerate(muts, 1):
        svc.writer.apply([m])
        versions.add(svc.writer.version)
        got = svc.search(QUERIES)    # one publish per version
        if i % 20:
            continue
        rebuilt, rmeta = build_sharded_index(apply_mutations(corpus, muts[:i]), 1)
        want = SearchService(rebuilt, rmeta, mesh, ns=1, k=10, window=1024,
                             cache_size=0).search(QUERIES)
        assert [h.docids for h in got] == [h.docids for h in want], i
        assert [h.n_hits for h in got] == [h.n_hits for h in want], i
    assert publishes(reg) == {"patch": len(versions) - 1, "full": 1.0}
