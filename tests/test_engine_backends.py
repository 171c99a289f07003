"""Backend parity: the batched Pallas join (interpret mode) must be
bit-identical to the jnp reference engine and the brute-force oracle,
across strategies, query classes, and padding edge cases."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.engine import (
    NO_ATTR,
    brute_force_topk,
    make_query_batch,
    query_topk,
)
from repro.core.index import (
    INVALID_DOC,
    build_index,
    build_sharded_index,
)
from repro.core.parallel import distributed_query_topk
from repro.data.corpus import Corpus, CorpusConfig, generate_corpus
from repro.serving.search import SearchService


@pytest.fixture(scope="module")
def setup():
    corpus = generate_corpus(
        CorpusConfig(n_docs=600, vocab_size=250, mean_doc_len=30, n_sites=12, seed=11)
    )
    idx, meta = build_index(corpus)
    return corpus, idx, meta


QUERIES = [
    ([7], None),            # single keyword
    ([3, 9], None),         # two-keyword join
    ([1, 4, 12], None),     # three-keyword join
    ([2], 3),               # limited search, single keyword
    ([5, 8], 1),            # limited search, join
    ([240], None),          # rare keyword (short posting list)
]


def _run_both(idx, qb, *, k, window, strategy):
    dj, hj = query_topk(
        idx, qb, k=k, window=window, attr_strategy=strategy, backend="jnp"
    )
    dp, hp = query_topk(
        idx, qb, k=k, window=window, attr_strategy=strategy,
        backend="pallas", interpret=True,
    )
    return (np.asarray(dj), np.asarray(hj)), (np.asarray(dp), np.asarray(hp))


@pytest.mark.parametrize("strategy", ["embed", "gather", "site_term"])
@pytest.mark.parametrize("k", [5, 20])
def test_backend_parity_all_strategies(setup, strategy, k):
    _, idx, meta = setup
    qb = make_query_batch(QUERIES, t_max=4, meta=meta, strategy=strategy)
    (dj, hj), (dp, hp) = _run_both(idx, qb, k=k, window=1024, strategy=strategy)
    np.testing.assert_array_equal(dj, dp)
    np.testing.assert_array_equal(hj, hp)


@pytest.mark.parametrize("window", [128, 256, 512, 1000, 1536])
def test_backend_parity_unaligned_windows(setup, window):
    """Windows that are BLOCK- but not TILE-aligned: a list whose offset
    straddles a tile boundary spans one more physical tile than the window
    itself, so the streamed probe plan must size its spans with ceil
    (regression: floor dropped the straddling tile's matches).  Also
    covers the streamed-driver edge cases: windows shorter than one TILE
    (128 = one BLOCK, 256) and a window ending mid-tile and mid-lane-row
    (1000) — the driver tiles' intended-position masking must clip the
    exact same slots the jnp reference's windowed gather clips."""
    _, idx, meta = setup
    qb = make_query_batch(QUERIES, t_max=4, meta=meta)
    (dj, hj), (dp, hp) = _run_both(idx, qb, k=10, window=window,
                                   strategy="embed")
    np.testing.assert_array_equal(dj, dp)
    np.testing.assert_array_equal(hj, hp)
    assert hj.sum() > 0  # the sweep must actually find matches


def test_pallas_backend_matches_bruteforce(setup):
    corpus, idx, meta = setup
    qb = make_query_batch(QUERIES, t_max=4, meta=meta, strategy="embed")
    docs, _ = query_topk(
        idx, qb, k=10, window=1024, attr_strategy="embed",
        backend="pallas", interpret=True,
    )
    truth = brute_force_topk(corpus, QUERIES, 10)
    for i, want in enumerate(truth):
        got = [int(d) for d in np.asarray(docs[i]) if d != INVALID_DOC]
        assert got == want, i


def test_backend_parity_multitile_window(setup):
    """window=2048 spans two kernel tiles; the trailing tile is mostly pad."""
    _, idx, meta = setup
    qb = make_query_batch(QUERIES, t_max=4, meta=meta)
    (dj, hj), (dp, hp) = _run_both(idx, qb, k=10, window=2048, strategy="embed")
    np.testing.assert_array_equal(dj, dp)
    np.testing.assert_array_equal(hj, hp)


@pytest.mark.parametrize("window", [1024, 256])
def test_empty_lists_and_all_pad_tiles(window):
    """Terms with empty posting lists and fully-padded windows: zero hits,
    never garbage; unrestricted queries keep attr_filter == NO_ATTR.  An
    empty *driver* list means the streamed driver reads n_eff=0 tiles —
    every slot must come back INVALID on both the TILE-sized and the
    sub-TILE window."""
    corpus = Corpus(
        doc_offsets=np.array([0, 2, 4], np.int64),
        doc_terms=np.array([0, 1, 0, 2], np.int32),
        doc_site=np.array([0, 1], np.int32),
        n_docs=2,
        vocab_size=8,       # terms 3..7 have empty posting lists
        n_sites=2,
    )
    idx, meta = build_index(corpus, include_site_terms=False)
    queries = [
        ([5], None),        # empty driver list
        ([0, 5], None),     # join against an empty list
        ([0], None),        # both docs; driver window is almost all pad
        ([0, 2], None),     # real join -> doc 1
    ]
    qb = make_query_batch(queries, t_max=4)
    assert int(qb.attr_filter[2]) == int(NO_ATTR)
    (dj, hj), (dp, hp) = _run_both(idx, qb, k=5, window=window, strategy="embed")
    np.testing.assert_array_equal(dj, dp)
    np.testing.assert_array_equal(hj, hp)
    assert list(hp) == [0, 0, 2, 1]
    assert dp[3][0] == 1


@pytest.mark.parametrize("with_delta", [False, True])
def test_driver_stream_at_array_edge(with_delta):
    """Spare-tile invariant regression (flat_tile_pad must be ceil+1, not
    floor+1): a driver list that starts inside the flat array's final
    partial tile forces the Element window read to clamp at the array
    edge.  Without a whole spare INVALID tile past the last posting, the
    clamped read serves the *previous* list's postings into in-window
    slots and the streamed backend returns documents of the wrong term."""
    from repro.data.corpus import corpus_from_docs

    # 12 BLOCK-padded single-term lists -> flat length 1536, NOT a TILE
    # multiple; the last lists start inside the final partial tile.
    docs = [np.array([i // 3], np.int32) for i in range(36)]
    corpus = corpus_from_docs(docs, [i % 4 for i in range(36)],
                              vocab_size=12, n_sites=4)
    idx, meta = build_index(corpus, include_site_terms=False)
    queries = [([t], None) for t in range(12)]
    qb = make_query_batch(queries, t_max=4)
    if with_delta:
        from repro.indexing import DeltaWriter
        from repro.indexing.delta import local_delta

        w = DeltaWriter(corpus, meta, ns=1, term_capacity=128,
                        doc_headroom=64)
        w.delete_docs([35])          # tombstone in the last list
        w.insert_docs([([11], 1)])   # delta posting for the last term
        delta = local_delta(w.device_delta())
    else:
        delta = None
    dj, hj = query_topk(idx, qb, delta=delta, k=10, window=1024,
                        backend="jnp")
    dp, hp = query_topk(idx, qb, delta=delta, k=10, window=1024,
                        backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp))
    np.testing.assert_array_equal(np.asarray(hj), np.asarray(hp))
    # every term must return ITS OWN documents, not a neighbor's
    for t in range(12):
        expect = sorted(
            d for d in range(36) if t == d // 3
            and not (with_delta and d == 35)
        )
        if with_delta and t == 11:
            expect = expect + [36]  # the inserted doc
        got = [int(d) for d in np.asarray(dp[t]) if d != INVALID_DOC]
        assert got == expect, (t, got, expect)


def test_distributed_backend_flag_forwards(setup):
    """distributed_query_topk accepts backend= and produces identical
    results for both execution engines (single-device mesh)."""
    corpus, _, meta = setup
    ns = 1
    sharded, smeta = build_sharded_index(corpus, ns)
    mesh = jax.make_mesh((ns,), ("data",))
    qb = make_query_batch(QUERIES, t_max=4, meta=smeta)
    rj = distributed_query_topk(
        sharded, qb, mesh=mesh, ns=ns, k=10, window=1024, backend="jnp"
    )
    rp = distributed_query_topk(
        sharded, qb, mesh=mesh, ns=ns, k=10, window=1024,
        backend="pallas", interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(rj.docids), np.asarray(rp.docids))
    np.testing.assert_array_equal(np.asarray(rj.n_hits), np.asarray(rp.n_hits))


def test_distributed_pallas_master_merge(setup):
    """backend='pallas' also routes the master merge through the bitonic
    top-k kernel (allgather exercises it even on a 1-device mesh)."""
    corpus, _, meta = setup
    ns = 1
    sharded, smeta = build_sharded_index(corpus, ns)
    mesh = jax.make_mesh((ns,), ("data",))
    qb = make_query_batch(QUERIES, t_max=4, meta=smeta)
    rj = distributed_query_topk(
        sharded, qb, mesh=mesh, ns=ns, k=10, window=1024,
        merge="allgather", backend="jnp",
    )
    rp = distributed_query_topk(
        sharded, qb, mesh=mesh, ns=ns, k=10, window=1024,
        merge="allgather", backend="pallas", interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(rj.docids), np.asarray(rp.docids))
    np.testing.assert_array_equal(np.asarray(rj.n_hits), np.asarray(rp.n_hits))


def test_search_service_backends(setup):
    """The serving front-end threads backend= down to the slaves."""
    corpus, _, _ = setup
    ns = 1
    sharded, meta = build_sharded_index(corpus, ns)
    mesh = jax.make_mesh((ns,), ("data",))
    queries = [([7], None), ([3, 9], None), ([2], 3)]
    hits = {}
    for backend in ("jnp", "pallas"):
        svc = SearchService(
            sharded, meta, mesh, ns=ns, k=10, window=1024,
            backend=backend, interpret=True,
        )
        hits[backend] = svc.search(queries)
    for a, b in zip(hits["jnp"], hits["pallas"]):
        assert a.docids == b.docids
        assert a.n_hits == b.n_hits
    truth = brute_force_topk(corpus, queries, 10)
    for got, want in zip(hits["pallas"], truth):
        assert got.docids == want


def test_site_term_strategy_ignores_attr_filter(setup):
    """Under attr_strategy='site_term' the jnp engine ignores attr_filter
    (the restriction lives in a join term); the kernel backend must too,
    even when the batch carries non-NO_ATTR filters."""
    _, idx, meta = setup
    qb = make_query_batch([([2], 3), ([5, 8], 1)], t_max=4, meta=meta,
                          strategy="embed")  # sites land in attr_filter
    assert int(qb.attr_filter[0]) != int(NO_ATTR)
    (dj, hj), (dp, hp) = _run_both(
        idx, qb, k=10, window=1024, strategy="site_term"
    )
    np.testing.assert_array_equal(dj, dp)
    np.testing.assert_array_equal(hj, hp)


def test_unknown_backend_rejected(setup):
    _, idx, meta = setup
    qb = make_query_batch([([7], None)], t_max=4, meta=meta)
    with pytest.raises(ValueError):
        query_topk(idx, qb, k=5, window=1024, backend="cuda")
