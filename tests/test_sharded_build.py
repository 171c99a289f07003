"""The set's index build: shards striped with one index computation and
built concurrently, on the host, bit for bit what the serial build with a
per-document gather loop gave; the set's work named on the device; the
set-up counters on the service's registry only."""
import jax
import numpy as np
import pytest

from repro.core.engine import make_query_batch
from repro.core.index import (
    BLOCK,
    INVALID_ATTR,
    INVALID_DOC,
    TILE,
    _build_numpy,
    build_sharded_index,
    partition_corpus,
)
from repro.core.parallel import (
    MERGE_SCOPE,
    SLAVE_SCOPE,
    distributed_query_topk,
    replicated_query_topk,
    slave_topk_unmerged,
)
from repro.data.corpus import Corpus, CorpusConfig, generate_corpus
from repro.obs.registry import MetricsRegistry, NullRegistry, set_registry
from repro.serving.search import SearchService


@pytest.fixture(scope="module")
def corpus():
    return generate_corpus(CorpusConfig(n_docs=1500, vocab_size=200, mean_doc_len=24,
                                        n_sites=6, seed=13))


def loop_partition(corpus, ns):
    """The partition as it was: a Python loop gathering each document."""
    shards = []
    for s in range(ns):
        sel = np.arange(s, corpus.n_docs, ns)
        lens = np.diff(corpus.doc_offsets)[sel]
        offs = np.zeros(sel.shape[0] + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        gather = np.concatenate(
            [corpus.doc_terms[corpus.doc_offsets[d]:corpus.doc_offsets[d + 1]]
             for d in sel]
        ) if sel.size else np.zeros(0, dtype=np.int32)
        shards.append(Corpus(doc_offsets=offs, doc_terms=gather,
                             doc_site=corpus.doc_site[sel], n_docs=int(sel.shape[0]),
                             vocab_size=corpus.vocab_size, n_sites=corpus.n_sites))
    return shards


def serial_stack(corpus, ns):
    """The build as it was: shards in turn, stacked with the same padding."""
    arrays = [_build_numpy(p, True)[0] for p in loop_partition(corpus, ns)]
    out = {}
    for key, pad in (("offsets", 0), ("lengths", 0), ("postings", INVALID_DOC),
                     ("attrs", INVALID_ATTR), ("block_max", INVALID_DOC),
                     ("doc_site", INVALID_ATTR)):
        ms = [a[key] for a in arrays]
        width = max(m.shape[0] for m in ms)
        if key in ("postings", "attrs"):
            width = -(-width // TILE) * TILE
        elif key == "doc_site":
            width = -(-width // BLOCK) * BLOCK
        out[key] = np.full((ns, width), pad, dtype=ms[0].dtype)
        for i, m in enumerate(ms):
            out[key][i, : m.shape[0]] = m
    return out


@pytest.mark.parametrize("ns", [1, 3, 4, 7])
def test_partition_equals_the_gather_loop(corpus, ns):
    for got, want in zip(partition_corpus(corpus, ns), loop_partition(corpus, ns),
                         strict=True):
        for field in ("doc_offsets", "doc_terms", "doc_site"):
            g, w = getattr(got, field), getattr(want, field)
            assert g.dtype == w.dtype and np.array_equal(g, w), field
        assert (got.n_docs, got.vocab_size, got.n_sites) == (
            want.n_docs, want.vocab_size, want.n_sites)


@pytest.mark.parametrize("ns", [1, 2, 4])
def test_build_is_bit_identical_to_the_serial_stack(corpus, ns):
    index, meta = build_sharded_index(corpus, ns)
    want = serial_stack(corpus, ns)
    for name, leaf in zip(index._fields, index):
        assert isinstance(leaf, np.ndarray), name       # never on a device
        assert leaf.dtype == want[name].dtype and np.array_equal(leaf, want[name]), name
    assert len(meta.shard_build_s) == ns and min(meta.shard_build_s) > 0
    # the build times are not part of what the metadata compares
    assert meta == build_sharded_index(corpus, ns)[1]


def _lowered(fn, index, meta, mesh, **kw):
    batch = make_query_batch([([1], None), ([2, 3], 1)], t_max=2, meta=meta)
    return fn.lower(index, batch, None, mesh=mesh, ns=1, k=10, window=256,
                    **kw).as_text(debug_info=True)


@pytest.mark.parametrize("fn,scopes", [
    (distributed_query_topk, (SLAVE_SCOPE, MERGE_SCOPE)),
    (replicated_query_topk, (SLAVE_SCOPE, MERGE_SCOPE)),
    (slave_topk_unmerged, (SLAVE_SCOPE,)),
])
def test_the_set_work_is_named_in_the_lowered_program(corpus, fn, scopes):
    index, meta = build_sharded_index(corpus, 1)
    axes = ("pod", "data") if fn is replicated_query_topk else ("data",)
    mesh = jax.make_mesh((1,) * len(axes), axes)
    text = _lowered(fn, index, meta, mesh)
    for scope in scopes:
        assert scope in text
    # no reader of the kernels' names may match a scope
    for scope in (SLAVE_SCOPE, MERGE_SCOPE):
        assert "intersect_batched" not in scope and "merge_delta_windows" not in scope
    if fn is slave_topk_unmerged:
        assert MERGE_SCOPE not in text


def test_setup_counters_on_the_given_registry_only(corpus):
    index, meta = build_sharded_index(corpus, 1)
    mesh = jax.make_mesh((1,), ("data",))
    reg = MetricsRegistry()
    svc = SearchService(index, meta, mesh, ns=1, registry=reg)
    fams = {name: series for name, _, _, series in reg.collect()}
    (_, hist), = fams["odys_index_shard_build_seconds"]
    assert hist.count == 1 and hist.sum == pytest.approx(meta.shard_build_s[0])
    (labels, gauge), = fams["odys_index_device_bytes"]
    assert labels == {"device": str(jax.devices()[0].id)}
    assert gauge.value == sum(x.nbytes for x in index)
    assert all(isinstance(x, jax.Array) for x in svc.index)
    prev = set_registry(NullRegistry())      # the process default: metrics off
    try:
        off = SearchService(index, meta, mesh, ns=1)
    finally:
        set_registry(prev)
    assert list(off.registry.collect()) == []
