"""The reference over a set of slaves: at ns=1 it is the one-slave check it
replaced, answers, driver lengths and roofline bytes alike; at ns=4 it is
the program's own oracle for the distributed path
(``repro.core.parallel.sequential_reference``); and the harness refuses the
sets it cannot judge."""
import json

import numpy as np
import pytest

from bench import corpus as bench_corpus
from bench import harness, reference, roofline, spec
from bench import trace as bench_trace
from bench.traffic import Traffic


def old_check(lists, batches, mutations, *, window, ingest):
    """``reference.check`` as it was for one slave: the oracle at ns=1."""
    state = reference.Ingest(lists) if ingest else None
    memo: dict = {}
    applied, n, bad, drivers = 0, 0, 0, []
    for n_applied, served in batches:
        while state is not None and applied < n_applied:
            state.apply(mutations[applied])
            applied += 1
        lens = []
        for q, docids, n_hits in served:
            key = (q.terms, q.site, q.k)
            want = memo.get(key) if state is None else None
            if want is None:
                want = reference.answer(lists, state, q.terms, q.site, q.k, window)
                if state is None:
                    memo[key] = want
            n += 1
            bad += (list(docids), int(n_hits)) != tuple(want[:2])
            lens.append(want[2:])
        drivers.append(lens)
    return n, bad, drivers


def old_intersect_roofline(ctx):
    """The intersection reader as it was for one slave."""
    ns = bench_trace.kernel_ns(ctx.trace, "intersect_batched")
    n_bytes = 0
    for b in ctx.batches:
        if not b.traced:
            continue
        for (q, docids, _), (base, delta) in zip(b.answers, b.drivers):
            n_eff = min(min(base, ctx.window) + delta, ctx.window)
            if len(q.terms) == 1 and q.site is None and not ctx.merged:
                read = min(q.k, n_eff) * 4
            else:
                read = n_eff * 4 * (2 if q.site is not None else 1)
            n_bytes += read + (len(docids) + 1) * 4
    return roofline.share_pct(n_bytes, ns / 1e9, ctx.device_kind)


def old_delta_merge_roofline(ctx):
    """The delta-merge reader as it was for one slave."""
    ns = bench_trace.kernel_ns(ctx.trace, "merge_delta_windows")
    n_bytes = 0
    for b in ctx.batches:
        if not b.traced:
            continue
        for (q, _, _), (base, delta) in zip(b.answers, b.drivers):
            n_bytes += ((min(base, ctx.window) + delta) * 4
                        * (2 if q.site is not None else 1))
    return roofline.share_pct(n_bytes, ns / 1e9, ctx.device_kind)


def _tiny(tiny_cell, name, **config):
    """The cell at the tests' tiny crawl and window (``config`` overrides
    the crawl's sizes), its crawl, its traffic."""
    cell = tiny_cell(name)
    cell.config.update(config)
    shape = bench_corpus.CorpusShape.from_config(cell.config)
    host = bench_corpus.generate(shape, 2**31 + 21)
    traffic = Traffic(cell.traffic, vocab_size=shape.vocab_size,
                      n_sites=shape.n_sites, n_docs=shape.n_docs)
    return cell, host, traffic


def _served(cell, host, traffic, window):
    """Batches of answers as a run would record them: the reference's own
    answers, every fifth altered, with the mutations acknowledged so far."""
    rng = np.random.default_rng(5)
    ingest = cell.config.get("ingest") is not None
    muts = traffic.mutations(np.random.default_rng(6), 60) if ingest else []
    queries = [traffic.query(rng) for _ in range(240)]
    lists = reference.PostingLists(host.doc_offsets, host.doc_terms, host.doc_site,
                                   host.shape.vocab_size, window=window,
                                   terms={t for q in queries for t in q.terms})
    state = reference.Ingest(lists) if ingest else None
    batches, applied = [], 0
    for b in range(30):
        while state is not None and applied < 2 * b:
            state.apply(muts[applied])
            applied += 1
        rows = []
        for i, q in enumerate(queries[8 * b:8 * b + 8]):
            docids, n_hits, _, _ = reference.answer(lists, state, q.terms, q.site,
                                                    q.k, window)
            if i % 5 == 0:
                n_hits += 1
            rows.append((q, tuple(docids), n_hits))
        batches.append((applied, rows))
    return lists, batches, muts, ingest


@pytest.mark.parametrize("name", ["slave-mix", "fresh-mix", "slave-sat"])
def test_one_slave_check_is_the_old_check(tiny_cell, name):
    cell, host, traffic = _tiny(tiny_cell, name)
    window = cell.config["service"]["window"]
    lists, batches, muts, ingest = _served(cell, host, traffic, window)
    want = old_check(lists, batches, muts, window=window, ingest=ingest)
    slaves = reference.slave_lists(host.doc_offsets, host.doc_terms, host.doc_site,
                                   host.shape.vocab_size, window=window, ns=1,
                                   terms={t for _, rows in batches
                                          for q, _, _ in rows for t in q.terms})
    got = reference.check(slaves, batches, muts, window=window, ingest=ingest)
    assert got[:2] == want[:2] and want[1] > 0
    assert got[2] == [[(pair,) for pair in lens] for lens in want[2]]
    if ingest:
        assert any(delta for lens in want[2] for _, delta in lens)


@pytest.mark.parametrize("merged", [False, True])
def test_one_slave_rooflines_read_as_before(tiny_cell, merged):
    cell, host, traffic = _tiny(tiny_cell, "fresh-mix" if merged else "slave-mix")
    window = cell.config["service"]["window"]
    lists, batches, muts, ingest = _served(cell, host, traffic, window)
    _, _, old = old_check(lists, batches, muts, window=window, ingest=ingest)
    trace = bench_trace.Trace(1e9, [
        bench_trace.Op("k", 0, 0.0, 3e5, "intersect_batched_driver_streamed.1"),
        bench_trace.Op("m", 0, 4e5, 2e5, "merge_delta_windows.2"),
    ], [])

    def ctx(drivers):
        runs = [harness.Batch(n, rows, 0.0, 0.0, i % 3 != 2, d)
                for i, ((n, rows), d) in enumerate(zip(batches, drivers))]
        return harness.Context("TPU v5 lite", window, merged, [], runs, trace)

    new = [[(pair,) for pair in lens] for lens in old]
    for metric, oracle in (("intersect_roofline", old_intersect_roofline),
                           ("delta_merge_roofline", old_delta_merge_roofline)):
        if metric == "delta_merge_roofline" and not merged:
            assert spec.reader(metric)(ctx(new)) is None
            continue
        want = oracle(ctx(old))
        assert want is not None and want > 0
        assert spec.reader(metric)(ctx(new)) == want


def _different_drivers(slaves, terms):
    return len({int(np.argmin([s.lengths[t] for t in terms])) for s in slaves}) > 1


@pytest.mark.parametrize("n_docs,window", [(None, None), (6000, 1024)])
def test_set_reference_is_the_programs_oracle(tiny_cell, n_docs, window):
    """At ns=4 the reference answers as ``sequential_reference`` does: each
    slave its own driver and window, ``n_hits`` summed, the answers merged.
    On the tiny crawl at the tiny window the hot keywords' lists pass the
    window over the whole crawl only; at 6000 documents and 1024 postings
    they pass it on every slave."""
    from repro.core.engine import make_query_batch
    from repro.core.index import INVALID_DOC, build_index, partition_corpus
    from repro.core.parallel import sequential_reference

    ns = 4
    cell, host, traffic = _tiny(tiny_cell, "slave-mix",
                                **({"n_docs": n_docs} if n_docs else {}))
    window = window or cell.config["service"]["window"]
    corpus = harness._repro_corpus(host)
    shards = [build_index(p)[0] for p in partition_corpus(corpus, ns)]
    _, meta = build_index(corpus)
    rng = np.random.default_rng(8)
    queries = [traffic.query(rng) for _ in range(60)]
    terms = {t for q in queries for t in q.terms} | set(range(40))
    slaves = reference.slave_lists(host.doc_offsets, host.doc_terms, host.doc_site,
                                   host.shape.vocab_size, terms=terms, window=window,
                                   ns=ns)
    assert sum(s.lengths[0] for s in slaves) > window
    if n_docs:
        assert min(s.lengths[0] for s in slaves) > window
    # an AND whose slaves pick different drivers: two keywords of close
    # lengths, the shorter one on one slave being the longer on another
    pair = next((a, b) for a in range(40) for b in range(a + 1, 40)
                if _different_drivers(slaves, (a, b)))
    by_k: dict[int, list] = {}
    for q in queries:
        by_k.setdefault(q.k, []).append((q.terms, q.site))
    for k in (10, 50, 1000):
        by_k.setdefault(k, []).extend([((0,), None), ((1, 0), None), (pair, None),
                                       ((0, 2), 1), ((3,), 0), (pair, 2)])
    conds = set()
    for k, qs in sorted(by_k.items()):
        batch = make_query_batch([(list(t), s) for t, s in qs], t_max=4, meta=meta)
        docs, hits = sequential_reference(shards, batch, ns=ns, k=k, window=window)
        for (t, s), row, h in zip(qs, np.asarray(docs), np.asarray(hits)):
            got = reference.set_answer(slaves, None, t, s, k, window)
            assert got[:2] == ([int(x) for x in row if x != INVALID_DOC], int(h)), (t, s, k)
            conds.add(("single" if len(t) == 1 else "and", s is not None, k))
    assert {(c, lim) for c, lim, _ in conds} >= {("single", False), ("and", False),
                                                  ("and", True), ("single", True)}
    assert _different_drivers(slaves, pair)


def test_set_reference_differs_from_one_slave_where_lists_pass_the_window(tiny_cell):
    """A lone hot keyword: one slave's window counts ``window`` hits, a set
    of four counts each slave's window."""
    _, host, _ = _tiny(tiny_cell, "slave-mix")
    args = (host.doc_offsets, host.doc_terms, host.doc_site, host.shape.vocab_size)
    one = reference.slave_lists(*args, terms={0}, window=512, ns=1)
    four = reference.slave_lists(*args, terms={0}, window=512, ns=4)
    assert reference.set_answer(one, None, (0,), None, 10, 512)[1] == 512
    assert reference.set_answer(four, None, (0,), None, 10, 512)[1] == 4 * 512


def test_stripe_is_the_partition(tiny_cell):
    from repro.core.index import partition_corpus

    _, host, _ = _tiny(tiny_cell, "slave-mix")
    for s, part in enumerate(partition_corpus(harness._repro_corpus(host), 3)):
        offs, terms, site = reference.stripe(host.doc_offsets, host.doc_terms,
                                             host.doc_site, s, 3)
        assert np.array_equal(offs, part.doc_offsets)
        assert np.array_equal(terms, part.doc_terms)
        assert np.array_equal(site, part.doc_site)


def _set_config(ns, ingest):
    cfg = json.loads((spec.BENCH / "configs" / "odys-slave-fresh.json").read_text())
    cfg["service"]["ns"] = ns
    if not ingest:
        cfg["ingest"] = None
    return cfg


@pytest.mark.parametrize("ns,chips,ingest,error", [
    (4, 1, False, "one slave a chip"),
    (1, 4, False, "one slave a chip"),
    (4, 4, True, "ingest"),
    (2, 2, True, "ingest"),
])
def test_cells_the_harness_cannot_judge_are_refused(tmp_path, ns, chips, ingest, error):
    cfg = _set_config(ns, ingest)
    with pytest.raises(ValueError, match=error):
        spec.check_cell(cfg, chips)
    # at load: a cell added as data
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "configs" / "set.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "mix-open.json").write_text(
        (spec.BENCH / "traffic" / "mix-open.json").read_text())
    bench["configs"].append({"name": "set", "file": "bench/configs/set.json"})
    bench["workloads"].append({"name": "set-mix", "config": "set",
                               "traffic": "mix-open", "chips": chips})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match=error):
        spec.load_cell("set-mix", root=tmp_path)
    # at build, before any crawl is made
    cell = spec.Cell("set-mix", cfg, {}, chips, [], [])
    with pytest.raises(ValueError, match=error):
        harness.build(cell, seed=1, trace=False)


@pytest.mark.parametrize("ns,ingest", [(1, False), (1, True), (4, False)])
def test_one_slave_a_chip_is_accepted(ns, ingest):
    spec.check_cell(_set_config(ns, ingest), ns)


def test_set_rooflines_sum_the_slaves_reads():
    """Four slaves: each reads its own driver window (a lone keyword its
    own first k), the answer is written once."""
    from bench.traffic import Query

    trace = bench_trace.Trace(1e9, [
        bench_trace.Op("k", c, 0.0, 1e5, "intersect_batched_streamed.1") for c in range(4)
    ] + [bench_trace.Op("m", c, 2e5, 1e5, "merge_delta_windows.1") for c in range(4)], [])
    lone = Query((0,), None, 10, "single")
    limited = Query((1, 2), 3, 50, "limited")
    slaves = ((9000, 0), (300, 2), (4096, 0), (0, 0))
    batch = harness.Batch(0, [(lone, tuple(range(10)), 40), (limited, (5, 6), 2)],
                          0.0, 0.0, True, [slaves, slaves])
    ctx = harness.Context("TPU v5 lite", 4096, False, [], [batch], trace)
    lone_bytes = 4 * (10 + 10 + 10 + 0) + 4 * 11
    limited_bytes = 8 * (4096 + 302 + 4096 + 0) + 4 * 3
    want = roofline.share_pct(lone_bytes + limited_bytes, 4e5 / 1e9, "TPU v5 lite")
    assert spec.reader("intersect_roofline")(ctx) == pytest.approx(want, rel=1e-12)
    merged = harness.Context("TPU v5 lite", 4096, True, [], [batch], trace)
    merge_bytes = 4 * (4096 + 302 + 4096 + 0) + 8 * (4096 + 302 + 4096 + 0)
    want = roofline.share_pct(merge_bytes, 4e5 / 1e9, "TPU v5 lite")
    assert spec.reader("delta_merge_roofline")(merged) == pytest.approx(want, rel=1e-12)
