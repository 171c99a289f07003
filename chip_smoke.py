#!/usr/bin/env python3
"""Run the ODYS search path once on a TPU and check its answers.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the scale-out paths only

One chip: build a seeded corpus and a one-shard index that holds more than
1 GiB of postings and attributes on the device, serve a few dozen queries of
``QUERY_MIX_DEFAULT`` (single-term, multi-term AND, site-limited; k in
{10, 50, 1000}) through ``SearchService`` with the compiled Pallas kernels
and with the jnp reference, then attach a writer, apply a few hundred
inserts, deletes and updates, and search again.  It exits non-zero unless
pallas and jnp agree on every docid and ``n_hits``, single-term top-k is the
k-prefix of the posting list built on the host from the corpus, every
mutation shows in ``n_hits``, and the compiled query program holds a
Mosaic kernel (``tpu_custom_call``).

Four chips (``--chips 4``): ns=4 shards on a ``(4,)`` mesh with both master
merges (ppermute tournament, all-gather), and 2 ODYS sets x ns=2 on
disjoint device slices, each checked against ``backend="jnp"`` on the same
mesh.

Informational lines come first; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU it exits non-zero before
printing any result.  Everything runs in this one process, which holds the
chip(s) for the whole run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.core.engine import make_query_batch  # noqa: E402
from repro.core.index import build_sharded_index  # noqa: E402
from repro.core.parallel import distributed_query_topk, set_mesh_slices  # noqa: E402
from repro.core.perfmodel import QUERY_MIX_DEFAULT  # noqa: E402
from repro.core.queries import WorkloadConfig, generate_workload  # noqa: E402
from repro.data.corpus import (  # noqa: E402
    CorpusConfig,
    MutationConfig,
    generate_corpus,
    generate_mutations,
)
from repro.launch.compile_cache import use_checkout_cache  # noqa: E402
from repro.serving.search import SearchService  # noqa: E402

#: The paper's slave holds one fifth of its 114M-page crawl.
PAPER_SHARD_PAGES = 22_800_000
WINDOW = 4096
GIB = 1 << 30
#: One chip: a 3M-document shard holds over 1 GiB of postings+attrs.
ONE_CHIP_DOCS = 3_000_000
FOUR_CHIP_DOCS = 1_000_000
#: Queries of each (condition, k) kind of the mix, per run.
ONE_CHIP_PER_KIND = 4
FOUR_CHIP_PER_KIND = 2
#: Inserts, deletes and updates applied through the writer.
N_MUTATIONS = 300


def log(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def require_tpu(chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax sees {devs[0].platform}); nothing run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but jax sees {len(devs)} device(s)")


def corpus_config(n_docs: int, seed: int) -> CorpusConfig:
    # 100k terms: lists are BLOCK-aligned, so a larger vocabulary pays more
    # padding per posting; the delta (n_terms x term_capacity) stays small.
    return CorpusConfig(n_docs=n_docs, vocab_size=100_000, seed=seed)


def query_mix(meta, per_kind: int, seed: int) -> list:
    """``per_kind`` queries of each (condition, k) kind of the default mix."""
    specs = generate_workload(
        meta, QUERY_MIX_DEFAULT, WorkloadConfig(n_queries=600, seed=seed)
    )
    out, seen = [], {}
    for s in specs:
        key = (s.sct, s.k)
        if seen.get(key, 0) < per_kind:
            seen[key] = seen.get(key, 0) + 1
            out.append(s)
    check(len(seen) == len(QUERY_MIX_DEFAULT.qmr), f"mix kinds {sorted(seen)}")
    return out


def serve(svc: SearchService, queries) -> list:
    """(terms, site, k) queries through the service's full pipeline."""
    tickets = [svc.submit(list(t), s, k=k) for t, s, k in queries]
    svc.drain()
    check(all(t.done for t in tickets), "every ticket completed")
    return [t.result for t in tickets]


def compare(name: str, queries, got, want) -> None:
    for q, g, w in zip(queries, got, want):
        check(
            g.docids == w.docids and g.n_hits == w.n_hits,
            f"{name}: pallas != jnp for {q}: {g.n_hits} {g.docids[:5]} vs "
            f"{w.n_hits} {w.docids[:5]}",
        )


def host_posting_list(corpus, term: int) -> np.ndarray:
    """Ascending docIDs holding ``term``, straight from the corpus CSR."""
    pos = np.flatnonzero(corpus.doc_terms == term)
    return np.searchsorted(corpus.doc_offsets, pos, side="right") - 1


def check_single_prefix(corpus, queries, results, ns: int) -> int:
    """Single-term top-k is the k-prefix of the host-built posting list;
    ``n_hits`` counts each shard's list up to the window."""
    n = 0
    for (terms, site, k), r in zip(queries, results):
        if len(terms) != 1 or site is not None:
            continue
        plist = host_posting_list(corpus, terms[0])
        check(
            r.docids == [int(d) for d in plist[:k]],
            f"single-term {terms[0]} top-{k} is the posting-list prefix",
        )
        hits = sum(min(int(np.sum(plist % ns == s)), WINDOW) for s in range(ns))
        check(r.n_hits == hits,
              f"single-term {terms[0]} n_hits {r.n_hits} vs {hits}")
        n += 1
    return n


def service(index, meta, mesh, backend, *, ns, **kw):
    """A compiled service with no result cache: every query reaches the
    engine's Mosaic kernels (``interpret`` is ignored by ``jnp``)."""
    return SearchService(index, meta, mesh, ns=ns, window=WINDOW,
                         backend=backend, interpret=False, cache_size=0,
                         **kw)


def services(index, meta, mesh, *, ns, **kw):
    """The pallas service under test and its jnp reference, same mesh."""
    return (
        service(index, meta, mesh, "pallas", ns=ns, **kw),
        service(index, meta, mesh, "jnp", ns=ns, **kw),
    )


def mutation_probes(corpus, mutations, counts, tail):
    """Give every mutation a probe term whose single-term ``n_hits`` must
    move with it, and the expected count of each probe afterwards.

    A probe's list stays far below the window, so ``n_hits`` counts it
    exactly.  Inserts and updates carry one extra ``tail`` term (a term
    whose base list is short) so each has such a probe; a delete probes a
    short-list term of the document it removes."""
    rng = np.random.default_rng(len(mutations))
    n_base = corpus.n_docs
    now: dict[int, set] = {}        # docid -> terms after the stream so far
    base: dict[int, set] = {}       # docid -> base terms, for changed docs
    probes: set[int] = set()
    muts = []
    next_gid = n_base
    for m in mutations:
        if m.op != "insert" and m.docid < n_base and m.docid not in base:
            base[m.docid] = set(corpus.terms_of(m.docid).tolist())
        if m.op == "delete":
            old = now.get(m.docid, base.get(m.docid))
            short = [t for t in old if counts[t] < WINDOW // 4]
            check(bool(short), f"delete of doc {m.docid} has a short-list term")
            probes.add(min(short))
            now[m.docid] = set()
            muts.append(m)
            continue
        extra = int(rng.choice(tail))
        terms = np.union1d(m.terms, [extra]).astype(np.int32)
        probes.add(extra)
        gid = next_gid if m.op == "insert" else m.docid
        next_gid += m.op == "insert"
        now[gid] = set(terms.tolist())
        muts.append(m._replace(terms=terms))
    expect = {}
    for t in probes:
        c = int(counts[t])
        for gid, terms in now.items():
            c += (t in terms) - (t in base.get(gid, ()))
        expect[t] = c
    return muts, expect


def apply_mutations_to(svc: SearchService, muts) -> None:
    for m in muts:
        if m.op == "insert":
            svc.insert([(m.terms, m.site)])
        elif m.op == "delete":
            svc.delete([m.docid])
        else:
            svc.update([(m.docid, m.terms, m.site)])


def one_chip(args):
    cfg = corpus_config(ONE_CHIP_DOCS, args.seed)
    log(f"corpus: {cfg}")
    log(f"cut: {PAPER_SHARD_PAGES:,}-page paper shard -> {cfg.n_docs:,} docs "
        f"({cfg.n_docs / PAPER_SHARD_PAGES:.3f}); vocabulary, document "
        f"length, Zipf skews and sites are the generator's")
    t0 = time.perf_counter()
    corpus = generate_corpus(cfg)
    index, meta = build_sharded_index(corpus, 1)
    jax.block_until_ready(index)
    build_s = time.perf_counter() - t0
    resident = index.postings.nbytes + index.attrs.nbytes
    log(f"host build: {build_s:.1f} s for {corpus.doc_terms.size:,} term "
        f"postings; flat slots {index.postings.shape[1]:,}")
    log(f"postings+attrs resident: {resident:,} B ({resident / GIB:.3f} GiB)")
    check(resident >= GIB, "postings+attrs >= 1 GiB on device")

    mesh = jax.make_mesh((1,), ("data",))
    pallas, ref = services(index, meta, mesh, ns=1)
    index = pallas.index
    stats = jax.devices()[0].memory_stats() or {}
    log(f"bytes_in_use: {stats.get('bytes_in_use', 'not reported')}")

    batch = make_query_batch([([1, 2], None)] * 8, t_max=4, meta=meta)
    t = time.perf_counter()
    text = distributed_query_topk.lower(
        pallas.index, batch, None, mesh=mesh, ns=1, k=10, window=WINDOW,
        backend="pallas", interpret=False,
    ).compile().as_text()
    log(f"compile (static index, k=10): {time.perf_counter() - t:.1f} s")
    check("tpu_custom_call" in text, "query program holds tpu_custom_call")

    specs = query_mix(meta, ONE_CHIP_PER_KIND, args.seed)
    queries = [(list(s.terms), s.site, s.k) for s in specs]
    for label, q in (("first query (compiles)", queries[0]),
                     ("second query", queries[1])):
        t = time.perf_counter()
        serve(pallas, [q])
        log(f"{label} wall time (pallas, k={q[2]}): "
            f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    got = serve(pallas, queries)
    log(f"pallas: {len(queries)} queries in {time.perf_counter() - t:.1f} s")
    want = serve(ref, queries)
    compare("static index", queries, got, want)
    n_single = check_single_prefix(corpus, queries, got, 1)
    log(f"static index: {len(queries)} queries, pallas == jnp; "
        f"{n_single} single-term == host posting-list prefix")

    counts = np.bincount(corpus.doc_terms, minlength=cfg.vocab_size)
    tail = np.flatnonzero((counts > 0) & (counts < WINDOW // 8))
    raw = generate_mutations(
        corpus, MutationConfig(n_ops=N_MUTATIONS, seed=args.seed)
    )
    muts, expect = mutation_probes(corpus, raw, counts, tail)
    probe_q = [([t], None, 10) for t in sorted(expect)]
    # the writer rides on the index the read-only services placed
    pallas = service(index, meta, mesh, "pallas", ns=1, updatable=True,
                     corpus=corpus, term_capacity=512, doc_headroom=1024)
    ref = service(index, meta, mesh, "jnp", ns=1, writer=pallas.writer)
    before = serve(pallas, probe_q)
    for (terms, _, _), r in zip(probe_q, before):
        check(r.n_hits == counts[terms[0]], f"probe {terms[0]} before updates")
    t = time.perf_counter()
    apply_mutations_to(pallas, muts)
    ops = {o: sum(m.op == o for m in muts) for o in ("insert", "delete", "update")}
    log(f"applied {ops} in {time.perf_counter() - t:.1f} s")
    after = serve(pallas, probe_q)
    compare("probes after updates", probe_q, after, serve(ref, probe_q))
    for (terms, _, _), r in zip(probe_q, after):
        check(r.n_hits == expect[terms[0]],
              f"probe {terms[0]}: n_hits {r.n_hits} != {expect[terms[0]]}")
    moved = sum(expect[t] != counts[t] for t in expect)
    compare("mix after updates", queries, serve(pallas, queries),
            serve(ref, queries))
    log(f"updates: {len(probe_q)} probe terms ({moved} moved) match the "
        f"mutated corpus; mix after updates pallas == jnp")


def four_chips(args):
    cfg = corpus_config(FOUR_CHIP_DOCS, args.seed)
    log(f"corpus: {cfg}")
    corpus = generate_corpus(cfg)
    devs = jax.devices()[:4]

    index4, meta = build_sharded_index(corpus, 4)
    specs = query_mix(meta, FOUR_CHIP_PER_KIND, args.seed)
    queries = [(list(s.terms), s.site, s.k) for s in specs]
    mesh4 = jax.make_mesh((4,), ("data",), devices=devs)
    for merge in ("tournament", "allgather"):
        pallas, ref = services(index4, meta, mesh4, ns=4, merge=merge)
        got = serve(pallas, queries)
        compare(f"ns=4 {merge}", queries, got, serve(ref, queries))
        n_single = check_single_prefix(corpus, queries, got, 4)
        log(f"ns=4 on a (4,) mesh, merge={merge}: {len(queries)} queries, "
            f"pallas == jnp; {n_single} single-term == host prefix")
    del index4, pallas, ref

    index2, meta2 = build_sharded_index(corpus, 2)
    slices = set_mesh_slices(2, 2, devices=devs)
    pallas, ref = services(index2, meta2, slices[0], ns=2, n_sets=2,
                           set_meshes=slices, batch_size=4)
    got = serve(pallas, queries)
    compare("2 sets x ns=2", queries, got, serve(ref, queries))
    used = [s["n_batches"] for s in pallas.stats()["sets"]]
    check(all(n > 0 for n in used), f"both sets served batches: {used}")
    log(f"2 sets x ns=2 on disjoint slices: batches per set {used}; "
        f"{len(queries)} queries, pallas == jnp")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    require_tpu(args.chips)
    log(f"compile cache: {use_checkout_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args)
    else:
        one_chip(args)
    log(f"total: {time.perf_counter() - t0:.1f} s")
    d = jax.devices()[0]
    # count: the chips this run meshed over, not every chip the host has
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
