"""The plain reference: ODYS's query semantics worked out from the corpus
and the mutation log alone.  It imports nothing of the program.

A slave answers a keyword query over its posting lists (ascending docIDs,
which is rank order) as the engine documents it (``core/engine.py``):

- the *driver* is the query's keyword with the shortest list (the first
  such keyword on a tie); only its first ``window`` postings are
  candidates;
- a candidate survives if every other keyword's first ``window`` postings
  hold it, and, for a limited search, if its site is the query's;
- the answer is the first ``k`` survivors, and ``n_hits`` counts them all.

With online ingest the lists are the base crawl's (immutable until a
compaction) plus a delta: inserted and updated documents' current terms.
A deleted document is dead everywhere; an updated base document's base
postings are stale.  The driver's candidates are its first ``window``
base postings merged with its delta list by docID (a base posting before
a delta posting of the same document), cut to ``window``; dead and stale
entries keep their slots and never count.  A candidate is in another
keyword's list if it is among that list's first ``window`` base postings
and its base version is current, or if it is in that keyword's delta list.
List lengths, which choose the driver, are base plus delta postings.

With the window covering every list, both forms give the exact answer of
a rebuilt index over the mutated crawl.

A *set* of ``ns`` slaves (ODYS secs. 3 and 5.1: the master broadcasts a
query, each slave answers over its own documents, the master merges)
answers as follows (``core/parallel.py``, ``partition_corpus`` in
``core/index.py``):

- slave ``s`` holds the global documents ``d`` with ``d % ns == s``, as
  local documents ``d // ns`` in the same order; its lists, their lengths
  and their first ``window`` postings are its own;
- each slave answers the query as above over its own lists: it picks its
  own driver from its local list lengths and reads its own first
  ``window`` postings of each keyword; its survivors map back to global
  docIDs as ``local * ns + s``;
- ``n_hits`` is the sum of the slaves' counts, and the answer is the first
  ``k`` of the union of the slaves' survivors, by global docID.

With ``ns`` 1 the set is the one slave above, and the set's answer is that
slave's.  Online ingest is answered for one slave only.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np


#: Documents a pass of :func:`heads` takes at a time.
SCAN_DOCS = 1 << 16


def heads(doc_offsets: np.ndarray, doc_terms: np.ndarray, terms: Iterable[int],
          window: int) -> dict[int, np.ndarray]:
    """The first ``window`` docIDs of each of ``terms``' lists, ascending.

    One scan of the crawl in document order, a block of documents at a
    time; a term drops out of the scan once its window is full, so the
    frequent terms cost only the first blocks."""
    vocab = int(max(terms, default=0)) + 1
    want = np.zeros(vocab, bool)
    want[list(terms)] = True
    room = np.full(vocab, window, np.int64)
    parts: dict[int, list[np.ndarray]] = {t: [] for t in terms}
    n_docs = doc_offsets.shape[0] - 1
    for d0 in range(0, n_docs, SCAN_DOCS):
        d1 = min(n_docs, d0 + SCAN_DOCS)
        p0, p1 = doc_offsets[d0], doc_offsets[d1]
        block = doc_terms[p0:p1]
        sel = np.flatnonzero(want[np.minimum(block, vocab - 1)] & (block < vocab))
        if sel.size == 0:
            continue
        ts = block[sel]
        docs = np.searchsorted(doc_offsets[d0:d1 + 1], p0 + sel, side="right") - 1 + d0
        order = np.argsort(ts, kind="stable")
        ts, docs = ts[order], docs[order]
        cuts = np.flatnonzero(np.diff(ts)) + 1
        for lo, hi in zip(np.concatenate([[0], cuts]), np.concatenate([cuts, [ts.size]])):
            t = int(ts[lo])
            take = min(hi - lo, room[t])
            parts[t].append(docs[lo:lo + take])
            room[t] -= take
            if room[t] == 0:
                want[t] = False
        if not want.any():
            break
    return {t: (np.concatenate(p) if p else np.zeros(0, np.int64)).astype(np.int64)
            for t, p in parts.items()}


class PostingLists:
    """The base crawl's list lengths, and the first ``window`` postings of
    the lists that queries name, from the crawl itself."""

    def __init__(self, doc_offsets, doc_terms, doc_site, vocab_size: int, *,
                 terms: Iterable[int], window: int):
        self.lengths = np.bincount(doc_terms, minlength=vocab_size)
        self.window = window
        self._heads = heads(doc_offsets, doc_terms, set(terms), window)
        self.doc_site = np.asarray(doc_site)
        self.n_docs = int(doc_site.shape[0])

    def head(self, t: int, window: int) -> np.ndarray:
        """The first ``window`` postings of term ``t`` (at most the window
        the lists were read with)."""
        if window > self.window:
            raise ValueError(f"lists were read with window {self.window}")
        return self._heads[t][:window]


def stripe(doc_offsets: np.ndarray, doc_terms: np.ndarray, doc_site: np.ndarray,
           shard: int, ns: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Slave ``shard``'s documents of a set of ``ns``: the crawl's documents
    ``shard, shard + ns, ...`` in order, as a crawl of their own (CSR
    offsets, terms, sites); with ``ns`` 1, the crawl itself."""
    if ns == 1:
        return doc_offsets, doc_terms, doc_site
    starts = doc_offsets[:-1][shard::ns]
    lens = doc_offsets[1:][shard::ns] - starts
    offsets = np.zeros(lens.shape[0] + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    take = np.repeat(starts - offsets[:-1], lens) + np.arange(offsets[-1])
    return offsets, doc_terms[take], doc_site[shard::ns]


def slave_lists(doc_offsets, doc_terms, doc_site, vocab_size: int, *,
                terms: Iterable[int], window: int, ns: int) -> list[PostingLists]:
    """Each slave's :class:`PostingLists` over its stripe of the crawl."""
    terms = set(terms)
    return [PostingLists(*stripe(doc_offsets, doc_terms, doc_site, s, ns), vocab_size,
                         terms=terms, window=window) for s in range(ns)]


class Ingest:
    """The delta that acknowledged mutations have built, as plain sets."""

    def __init__(self, base: PostingLists):
        self.base = base
        self.n_docs = base.n_docs
        self.dead: set[int] = set()
        self.stale: set[int] = set()                 # updated base documents
        self.delta_terms: dict[int, tuple[int, ...]] = {}
        self.delta: dict[int, set[int]] = {}         # term -> delta docIDs
        self.site: dict[int, int] = {}               # current site, if moved

    def apply(self, m) -> None:
        if m.op == "insert":
            d = self.n_docs
            self.n_docs += 1
            self._put(d, m.terms)
            self.site[d] = m.site
        elif m.op == "delete":
            self._drop(m.docid)
            self.dead.add(m.docid)
        elif m.op == "update":
            d = m.docid
            if d in self.delta_terms:
                self._drop(d)
            else:
                self.stale.add(d)
            self._put(d, m.terms)
            if m.site is not None:
                self.site[d] = m.site
        else:
            raise ValueError(m.op)

    def _put(self, d: int, terms) -> None:
        self.delta_terms[d] = tuple(terms)
        for t in terms:
            self.delta.setdefault(t, set()).add(d)

    def _drop(self, d: int) -> None:
        for t in self.delta_terms.pop(d, ()):
            self.delta[t].discard(d)

    def site_of(self, d: int) -> int:
        s = self.site.get(d)
        return int(self.base.doc_site[d]) if s is None else s

    def delta_list(self, t: int) -> np.ndarray:
        return np.asarray(sorted(self.delta.get(t, ())), np.int64)


def answer(lists: PostingLists, ingest: Ingest | None, terms, site, k: int,
           window: int) -> tuple[list[int], int, int, int]:
    """(docids, n_hits) of one query by the semantics above, and the
    driver's base and delta list lengths."""
    if ingest is None:
        lens = [int(lists.lengths[t]) for t in terms]
        d = int(np.argmin(lens))
        cand = lists.head(terms[d], window)
        for i, t in enumerate(terms):
            if i != d:
                cand = cand[np.isin(cand, lists.head(t, window))]
        if site is not None:
            cand = cand[lists.doc_site[cand] == site]
        return [int(x) for x in cand[:k]], int(cand.shape[0]), lens[d], 0

    dl = {t: ingest.delta_list(t) for t in terms}
    lens = [int(lists.lengths[t]) + dl[t].shape[0] for t in terms]
    d = int(np.argmin(lens))
    main = lists.head(terms[d], window)
    docs = np.concatenate([main, dl[terms[d]]])
    src = np.concatenate([np.zeros(main.shape[0], np.int8),
                          np.ones(dl[terms[d]].shape[0], np.int8)])
    order = np.argsort(docs, kind="stable")[:window]
    docs, src = docs[order], src[order]
    dead = np.fromiter((x in ingest.dead for x in docs), bool, docs.shape[0])
    stale = np.fromiter((x in ingest.stale for x in docs), bool, docs.shape[0])
    base_ok = ~dead & ~stale
    keep = np.where(src == 0, base_ok, ~dead)
    for i, t in enumerate(terms):
        if i != d:
            keep &= (np.isin(docs, lists.head(t, window)) & base_ok) | np.isin(docs, dl[t])
    if site is not None:
        keep &= np.fromiter((ingest.site_of(int(x)) == site for x in docs), bool,
                            docs.shape[0])
    hits = docs[keep]
    return ([int(x) for x in hits[:k]], int(hits.shape[0]),
            int(lists.lengths[terms[d]]), int(dl[terms[d]].shape[0]))


def set_answer(slaves: list[PostingLists], ingest: Ingest | None, terms, site,
               k: int, window: int) -> tuple[list[int], int, tuple]:
    """(docids, n_hits) of one query over a set of slaves by the semantics
    above, and each slave's driver base and delta list lengths."""
    ns = len(slaves)
    if ingest is not None and ns > 1:
        raise ValueError("online ingest is answered for one slave only")
    docids, n_hits, drivers = [], 0, []
    for s, lists in enumerate(slaves):
        local, n, base, delta = answer(lists, ingest, terms, site, k, window)
        docids += [x * ns + s for x in local]
        n_hits += n
        drivers.append((base, delta))
    return sorted(docids)[:k], n_hits, tuple(drivers)


def check(slaves: list[PostingLists], batches: Iterable, mutations: list, *,
          window: int, ingest: bool) -> tuple[int, int, list]:
    """Compare every served answer with the reference's over the set of
    ``slaves`` (one :class:`PostingLists` a slave, in slave order).

    ``batches`` holds ``(n_applied, [(query, docids, n_hits), ...])`` in
    dispatch order: the answers one batch served after the first
    ``n_applied`` mutations were acknowledged.  Returns the queries
    compared, the queries that differ, and per batch, per query, each
    slave's driver lengths ``(base, delta)`` (the rooflines' byte counts
    read them).
    """
    state = Ingest(slaves[0]) if ingest else None
    memo: dict = {}      # read-only lists: a repeated query has one answer
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
                want = set_answer(slaves, state, q.terms, q.site, q.k, window)
                if state is None:
                    memo[key] = want
            n += 1
            bad += (list(docids), int(n_hits)) != tuple(want[:2])
            lens.append(want[2])
        drivers.append(lens)
    return n, bad, drivers
