"""The roofline's byte count follows the work, not the read path, and the
peak table refuses a chip it does not know."""
import json

import numpy as np
import pytest

from bench import corpus as bench_corpus
from bench import reference, roofline, spec
from bench.traffic import Traffic
from repro.core.engine import make_query_batch, query_topk
from repro.core.index import INVALID_DOC, build_index
from repro.data.corpus import Corpus


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.hbm_bytes_per_s("TPU v99")
    assert roofline.hbm_bytes_per_s("TPU v5 lite") == 819e9


def test_share_cannot_pass_peak_when_time_covers_bytes():
    kind = "TPU v5 lite"
    n = 10**6
    assert roofline.share_pct(n, n / roofline.hbm_bytes_per_s(kind), kind) == pytest.approx(100.0)
    assert roofline.share_pct(n, 0.0, kind) is None
    assert roofline.share_pct(0, 1.0, kind) is None


def test_bytes_by_condition():
    kw = dict(base_len=10_000, delta_len=0, k=10, n_answer=10, window=4096)
    single = roofline.intersect_bytes(n_terms=1, limited=False, merged=False, **kw)
    multi = roofline.intersect_bytes(n_terms=2, limited=False, merged=False, **kw)
    limited = roofline.intersect_bytes(n_terms=1, limited=True, merged=False, **kw)
    assert single == 4 * 10 + 4 * 11
    assert multi == 4 * 4096 + 4 * 11
    assert limited == 8 * 4096 + 4 * 11
    merged = roofline.intersect_bytes(n_terms=1, limited=False, merged=True,
                                      **dict(kw, delta_len=5))
    assert merged == 4 * 4096 + 4 * 11
    assert roofline.delta_merge_bytes(base_len=100, delta_len=7, limited=False,
                                      window=4096) == 4 * 107


def _batch_bytes(lists, queries, docs, hits, window) -> int:
    """The intersect reader's byte sum over one batch's answers."""
    served = [(0, [(q, [int(x) for x in row if x != INVALID_DOC], int(h))
                   for q, row, h in zip(queries, docs, hits)])]
    n, bad, drivers = reference.check(lists, served, [], window=window, ingest=False)
    assert (n, bad) == (len(queries), 0)
    return sum(
        roofline.intersect_bytes(base_len=base, delta_len=delta, n_terms=len(q.terms),
                                 limited=q.site is not None, merged=False, k=q.k,
                                 n_answer=len(a), window=window)
        for (q, a, _), (base, delta) in zip(served[0][1], drivers[0]))


def test_bytes_do_not_depend_on_the_read_path():
    """One batch gives the same bytes through the dense Pallas kernels and
    through the work-list (compact) kernels."""
    shape = bench_corpus.CorpusShape(n_docs=3000, vocab_size=400, mean_doc_len=64,
                                     zipf_s=1.1, n_sites=8, site_zipf_s=1.2)
    host = bench_corpus.generate(shape, 13)
    corpus = Corpus(doc_offsets=host.doc_offsets, doc_terms=host.doc_terms,
                    doc_site=host.doc_site, n_docs=shape.n_docs,
                    vocab_size=shape.vocab_size, n_sites=shape.n_sites)
    index, meta = build_index(corpus)
    traffic = Traffic(json.loads((spec.BENCH / "traffic" / "mix-open.json").read_text()),
                      vocab_size=shape.vocab_size, n_sites=shape.n_sites,
                      n_docs=shape.n_docs)
    rng = np.random.default_rng(4)
    queries = [q for q in (traffic.query(rng) for _ in range(40)) if q.k == 50][:8]
    batch = make_query_batch([(list(q.terms), q.site) for q in queries], t_max=4,
                             meta=meta)
    window = 2048
    lists = reference.PostingLists(host.doc_offsets, host.doc_terms, host.doc_site,
                                   shape.vocab_size, window=window,
                                   terms={t for q in queries for t in q.terms})
    counts = []
    for backend in ("pallas", "pallas_compact"):
        docs, hits = query_topk(index, batch, k=50, window=window, backend=backend)
        counts.append(_batch_bytes(lists, queries, np.asarray(docs),
                                   np.asarray(hits), window))
    assert counts[0] == counts[1] > 0


def test_every_metric_has_a_reader():
    cells = [w["name"] for w in json.loads(
        (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for name in cells:
        for m in spec.load_cell(name).per_layer:
            assert callable(spec.reader(m["name"]))
