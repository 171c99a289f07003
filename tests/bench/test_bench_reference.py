"""The plain reference: a rebuild's answer where the window covers every
list, and the served path's answer at the timed window, static and under
ingest; its control differs."""
import time

import numpy as np
import pytest

from bench import control as bench_control
from bench import corpus as bench_corpus
from bench import harness, reference
from bench.traffic import Traffic

SHAPE = bench_corpus.CorpusShape(n_docs=600, vocab_size=60, mean_doc_len=12,
                                 zipf_s=1.1, n_sites=5, site_zipf_s=1.2)


def brute(docs: dict, terms, site, k):
    """Rank-ordered survivors of a rebuilt index: every live document that
    holds every keyword (and sits on the site)."""
    hits = sorted(d for d, (ts, s) in docs.items()
                  if set(terms) <= ts and (site is None or s == site))
    return hits[:k], len(hits)


@pytest.mark.parametrize("ingest", [False, True])
def test_reference_is_a_rebuild_when_the_window_covers(ingest):
    host = bench_corpus.generate(SHAPE, 2**32 + 5)
    lists = reference.PostingLists(host.doc_offsets, host.doc_terms,
                                   host.doc_site, SHAPE.vocab_size,
                                   terms=range(SHAPE.vocab_size), window=10**6)
    docs = {d: (set(host.doc_terms[host.doc_offsets[d]:host.doc_offsets[d + 1]].tolist()),
                int(host.doc_site[d])) for d in range(SHAPE.n_docs)}
    traffic = Traffic({"loop": "open", "rate_qps": 1, "zipf_s": 1.1, "max_terms": 3,
                       "mix": {"single/10": 1, "multiple/50": 1, "limited/1000": 1},
                       "mutations": {"rate_per_s": 1, "p_insert": 0.5, "p_delete": 0.2,
                                     "p_update": 0.3, "mean_doc_len": 8, "zipf_s": 1.1,
                                     "site_zipf_s": 1.2, "p_site_change": 0.5,
                                     "target_zipf_s": 1.0}},
                      vocab_size=SHAPE.vocab_size, n_sites=SHAPE.n_sites,
                      n_docs=SHAPE.n_docs)
    rng = np.random.default_rng(1)
    state = reference.Ingest(lists) if ingest else None
    muts = traffic.mutations(np.random.default_rng(2), 160) if ingest else []
    n_docs = SHAPE.n_docs
    for chunk in range(4):
        for q in (traffic.query(rng) for _ in range(60)):
            got = reference.answer(lists, state, q.terms, q.site, q.k, 10**6)
            assert tuple(got[:2]) == brute(docs, q.terms, q.site, q.k), q
        for m in muts[40 * chunk:40 * (chunk + 1)]:
            if m.op == "insert":
                docs[n_docs] = (set(m.terms), m.site)
                n_docs += 1
            elif m.op == "delete":
                docs[m.docid] = (set(), docs[m.docid][1])
            else:
                site = docs[m.docid][1] if m.site is None else m.site
                docs[m.docid] = (set(m.terms), site)
            state.apply(m)


@pytest.mark.parametrize("name", ["slave-mix", "fresh-mix"])
def test_reference_agrees_with_the_served_path(tiny_cell, name):
    cell = tiny_cell(name, rate_qps=40)
    out = harness.run(cell, seed=2**31 + 3, seconds=1.0, trace=False,
                      t_process=time.perf_counter())
    assert out["checks"]["answers_differing"]["value"] == 0
    assert out["correct"], out["checks"]
    assert out["attempted"] > 20


@pytest.mark.parametrize("name", ["slave-mix", "fresh-mix"])
def test_control_differs_where_the_program_does_not(tiny_cell, name):
    cell = tiny_cell(name, rate_qps=40)
    if "mutations" in cell.traffic:
        cell.traffic["mutations"]["rate_per_s"] = 10
    row = bench_control.control(cell, 2**32 + 9, 1.0)
    assert row["differ_from_reference"] == 0
    assert row["differ_from_control"] > 0
    if "mutations" in cell.traffic:
        # the acknowledged mutations change served answers: a frozen delta
        # would serve those wrong
        assert row["differ_without_mutations"] > 0


def test_heads_are_each_lists_first_postings():
    host = bench_corpus.generate(SHAPE, 3)
    got = reference.heads(host.doc_offsets, host.doc_terms, [0, 5, 59], 37)
    for t in (0, 5, 59):
        want = [d for d in range(SHAPE.n_docs)
                if t in host.doc_terms[host.doc_offsets[d]:host.doc_offsets[d + 1]]][:37]
        assert got[t].tolist() == want
