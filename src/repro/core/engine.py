"""ODYS slave query engine — reference (pure jnp) implementation.

This is the per-"slave" (per-shard) query processor.  It implements the
three query classes of the paper's query model (§4.1.1) over the TPU index
layout of :mod:`repro.core.index`:

- **single-keyword top-k**: a k-prefix read of the posting list (postings
  are rank-ordered, so the first k postings *are* the answer);
- **multiple-keyword top-k**: ZigZag join — membership of the shortest
  list's postings in every other list, early-k selection in rank order;
- **limited search**: keyword + siteId, with three strategies that
  reproduce the paper's §2/Fig 4 comparison:
    * ``embed``     — attribute embedding, fused predicate on the embedded
                      attrs stream (Fig 4(b); the paper's winner),
    * ``gather``    — join against the doc->site table via random-access
                      gather (the un-integrated Fig 1(c) plan),
    * ``site_term`` — the siteId-as-text plan: add the site's own posting
                      list as an extra join term (Fig 1(d)/4(a)); resolved
                      at query construction time.

All shapes are static: queries are padded to ``T_MAX`` terms, posting-list
windows to ``window`` postings, results to ``k``.  ``window`` is the
engine's analogue of the paper's bounded posting scan: rank-ordered postings
mean a top-k never needs more than the window unless the query is extremely
selective (the paper makes the same argument for its 22.8M-page shards,
§5.1 footnote 12).

**Merge-on-read** (online updates, :mod:`repro.indexing`): when a
:class:`~repro.indexing.delta.DeltaIndex` is attached, every term's logical
posting list is the merge of its main list and its delta list, with the
tombstone bitmap deciding per-posting liveness (a main posting dies when
its doc is deleted *or* superseded by an updated version in the delta; a
delta posting dies only on delete).  Other-term windows are masked before
the membership probe; the driver window keeps tombstoned postings in their
rank slots and filters them in the same fused pass as validity and the
embedded-attribute predicate — in the Pallas backend that predicate is
fused *inside the kernel* (``a_live`` operand), mirroring the paper's
one-sequential-scan argument.  Both backends therefore return bit-identical
results, equal to a from-scratch rebuild over the mutated corpus whenever
the window covers the merged list (the engine's standing assumption).

**Data path — the PostingSource layer.**  Every layer of the engine
obtains per-(query, term) posting streams through a
:class:`PostingSource`, of which there are two:

- :class:`StaticPostingSource` — the read-only main index.  On the Pallas
  backend *nothing* is gathered: the source hands the kernel the driver
  window's tile spans (:class:`DriverSpan` — the window start in the flat
  arrays plus its live-posting count) and the kernel reads driver tiles
  straight from the flat ``postings``/``attrs`` arrays through
  aligned Element-indexed BlockSpecs, emitting the window as kernel *output* (the
  one materialization the ZigZag join fundamentally needs, since the
  result is selected from it); *other-term* streams are probed in place —
  the jnp backend with ``searchsorted`` over the term's window, the
  Pallas backend streaming (8, 128) tiles whose skip-table-derived tile
  ranges are scalar-prefetched per (query, term) — so neither a
  ``(Q, window)`` driver gather nor a ``(Q, T_MAX, window)`` HBM staging
  buffer exists, and non-overlapping tiles are never DMA'd.
- :class:`MergedPostingSource` — main + delta under merge-on-read.  The
  driver stream is the *merged* window: on the Pallas backend the merge
  runs in VMEM (:mod:`repro.kernels.delta_merge` — one bitonic merge pass
  over the main window streamed tile-by-tile from the flat arrays and the
  delta slab streamed via its prefetched slab index, with empty slabs
  short-circuited via the delta's skip table), replacing both the former
  host-side jnp sort of ``window + term_capacity`` keys per (query, term)
  *and* the former ``(Q, window)`` main-window gather that fed it.  The
  kernel emits each merged slot's stream id; one elementwise pass over
  the tombstone bits turns it into the live stream
  (:meth:`MergedPostingSource.driver_live`).  Other-term streams again
  never materialize: membership in the merged logical list is (member of
  main list AND doc not dead/superseded) OR (member of delta list AND doc
  not dead) — two streaming probes over the physical structures, with the
  driver posting's tombstone flags deciding which probe may count.

Both backends consume the same source abstraction, so freshness semantics
(per-batch snapshot isolation, results equal to a from-scratch rebuild
while windows cover the merged lists) are defined once.  The legacy
staging path (gather + host-side merge sort) is retained as
``backend="pallas_staged"`` purely as the before/after comparator for
``benchmarks/bench_updates.py``.

This module is also the *oracle* for the Pallas kernels in
:mod:`repro.kernels` and runs inside ``shard_map`` for the distributed
engine (:mod:`repro.core.parallel`).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.index import (
    INVALID_ATTR,
    INVALID_DOC,
    IndexMeta,
    InvertedIndex,
    site_term_id,
    unpack_flat_postings_jnp,
)
from repro.indexing.delta import DOC_DEAD, DOC_SUPERSEDED, DeltaIndex

NO_TERM = np.int32(-1)
NO_ATTR = np.int32(-1)


class QueryBatch(NamedTuple):
    """Fixed-shape batch of queries (padded to T_MAX terms)."""

    terms: jnp.ndarray        # int32[Q, T_MAX]; NO_TERM padding
    n_terms: jnp.ndarray      # int32[Q]
    attr_filter: jnp.ndarray  # int32[Q]; NO_ATTR = unrestricted

    @property
    def n_queries(self) -> int:
        return self.terms.shape[0]


def make_query_batch(
    queries: list[tuple[list[int], int | None]],
    *,
    t_max: int = 4,
    meta: IndexMeta | None = None,
    strategy: str = "embed",
) -> QueryBatch:
    """Build a QueryBatch from (term_list, site_or_None) tuples.

    With ``strategy='site_term'`` the site restriction is rewritten into an
    extra join term (Fig 1(d)) and ``attr_filter`` stays empty.

    This runs host-side, ahead of the jitted query program; the serving
    path times it as the batch's ``batch_build`` phase.
    """
    q = len(queries)
    terms = np.full((q, t_max), NO_TERM, dtype=np.int32)
    n_terms = np.zeros(q, dtype=np.int32)
    attr = np.full(q, NO_ATTR, dtype=np.int32)
    for i, (ts, site) in enumerate(queries):
        ts = list(ts)
        if site is not None and strategy == "site_term":
            assert meta is not None and meta.include_site_terms
            ts = ts + [site_term_id(meta, site)]
        elif site is not None:
            attr[i] = site
        assert 1 <= len(ts) <= t_max, (ts, t_max)
        terms[i, : len(ts)] = ts
        n_terms[i] = len(ts)
    return QueryBatch(jnp.asarray(terms), jnp.asarray(n_terms), jnp.asarray(attr))


# ---------------------------------------------------------------------------
# Windowed posting access
# ---------------------------------------------------------------------------

def _window(flat: jnp.ndarray, off: jnp.ndarray, window: int, fill) -> jnp.ndarray:
    """Fixed-size windowed gather starting at ``off``; OOB reads -> fill."""
    idx = off + jnp.arange(window, dtype=jnp.int32)
    return jnp.take(flat, idx, mode="fill", fill_value=fill)


def term_window(
    index: InvertedIndex, term: jnp.ndarray, window: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(docids[window], attrs[window], valid[window]) for one term."""
    t = jnp.clip(term, 0, index.offsets.shape[0] - 1)
    off = index.offsets[t]
    ln = jnp.where(term < 0, 0, index.lengths[t])
    docs = _window(index.postings, off, window, INVALID_DOC)
    attrs = _window(index.attrs, off, window, INVALID_ATTR)
    valid = jnp.arange(window, dtype=jnp.int32) < ln
    docs = jnp.where(valid, docs, INVALID_DOC)
    return docs, attrs, valid


def member_sorted(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """For each a[i], is it present in sorted array b? (searchsorted probe)."""
    idx = jnp.searchsorted(b, a, side="left")
    probe = jnp.take(b, idx, mode="clip")
    return probe == a


def _first_k_by_rank(docids: jnp.ndarray, mask: jnp.ndarray, k: int):
    """Select the k smallest (=best-ranked) docids where mask holds."""
    key = jnp.where(mask, docids, INVALID_DOC)
    neg_top, _ = lax.top_k(-key.astype(jnp.int32), k)
    out = (-neg_top).astype(jnp.int32)
    return out, jnp.sum(mask.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Merge-on-read: logical windows over main + delta with tombstone filtering
# ---------------------------------------------------------------------------

def delta_term_window(
    delta: DeltaIndex, term: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(docids[cap], attrs[cap], valid[cap]) for one term's delta list.

    Same access pattern as :func:`term_window` — the delta shares the main
    index's CSR layout, just with a fixed per-term capacity.
    """
    cap = delta.term_capacity
    t = jnp.clip(term, 0, delta.offsets.shape[0] - 1)
    off = delta.offsets[t]
    ln = jnp.where(term < 0, 0, delta.lengths[t])
    docs = _window(delta.postings, off, cap, INVALID_DOC)
    attrs = _window(delta.attrs, off, cap, INVALID_ATTR)
    valid = jnp.arange(cap, dtype=jnp.int32) < ln
    docs = jnp.where(valid, docs, INVALID_DOC)
    return docs, attrs, valid


def posting_live(
    delta: DeltaIndex, docs: jnp.ndarray, *, from_delta: bool
) -> jnp.ndarray:
    """Per-posting tombstone predicate.

    A *main* posting is live iff its doc is neither deleted nor superseded
    (the updated version lives in the delta); a *delta* posting is live iff
    its doc is not deleted.  INVALID/padding docIDs read flag 0 (live) and
    are killed by the validity predicate instead.
    """
    flags = jnp.take(delta.doc_flags, docs, mode="fill", fill_value=0)
    kill = DOC_DEAD if from_delta else (DOC_DEAD | DOC_SUPERSEDED)
    return (flags & jnp.int32(kill)) == 0


def merged_term_window(
    index: InvertedIndex,
    delta: DeltaIndex,
    term: jnp.ndarray,
    window: int,
    *,
    drop_dead: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge-on-read window: (docids, attrs, live), each ``[window]``.

    Merges the main window and the term's delta list into one ascending
    docID stream (both inputs are sorted; a single rank-order sort realizes
    the ZigZag-friendly merge).  ``drop_dead=True`` removes tombstoned
    postings *before* the merge — the form membership probes need.
    ``drop_dead=False`` keeps them in their rank slots with ``live=0`` so
    the driver stream can defer the tombstone predicate to the same fused
    pass as validity + attribute filtering (in-kernel for Pallas).

    This host-side jnp merge is the *reference* driver merge (jnp backend
    + oracle for :func:`repro.kernels.delta_merge.merge_delta_windows`,
    which performs it in VMEM on the Pallas backend) and the legacy
    staged path's probe-window builder; the streaming probes
    (:meth:`MergedPostingSource.member`) need no merged window at all.
    """
    m_docs, m_attrs, m_valid = term_window(index, term, window)
    m_live = posting_live(delta, m_docs, from_delta=False) & m_valid
    d_docs, d_attrs, d_valid = delta_term_window(delta, term)
    d_live = posting_live(delta, d_docs, from_delta=True) & d_valid

    docs = jnp.concatenate([m_docs, d_docs])
    attrs = jnp.concatenate([m_attrs, d_attrs])
    live = jnp.concatenate([m_live, d_live])
    if drop_dead:
        docs = jnp.where(live, docs, INVALID_DOC)
    order = jnp.argsort(docs, stable=True)
    docs = jnp.take(docs, order)[:window]
    attrs = jnp.take(attrs, order)[:window]
    live = jnp.take(live, order)[:window]
    return docs, attrs, (live & (docs != INVALID_DOC)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# PostingSource: how every layer obtains per-(query, term) posting streams
# ---------------------------------------------------------------------------


class DriverSpan(NamedTuple):
    """Per-query placement of the driver window in the flat posting arrays.

    This is what a PostingSource hands the streaming kernels *instead of*
    a materialized ``(Q, window)`` gather: the window's start offset in
    the flat arrays (BLOCK-aligned, every list start is) and how many of
    its slots hold live postings.  The kernels turn it into aligned
    Element BlockSpec reads and read the driver tiles straight from HBM.
    """

    off: jnp.ndarray    # int32[Q] window start in the flat arrays
    n_eff: jnp.ndarray  # int32[Q] live postings in the window (<= window)


class StaticPostingSource:
    """Posting access over the read-only main index.

    No stream is ever gathered: the *driver* window is handed to the
    kernel as a :class:`DriverSpan` (tile offsets into the flat arrays —
    the kernel streams the tiles and emits the window as output), and
    *other-term* streams are probed in place (jnp ``searchsorted`` here,
    streamed tiles in the Pallas backend) — one pass over the physical
    index per query, the discipline the paper's slave cost model assumes.
    The jnp reference backend still materializes the driver window
    (:meth:`driver_window`), as the oracle for the streamed output.
    """

    def __init__(self, index: InvertedIndex):
        self.index = index
        self.delta: DeltaIndex | None = None

    @property
    def doc_site(self) -> jnp.ndarray:
        return self.index.doc_site

    def list_lengths(self, terms: jnp.ndarray) -> jnp.ndarray:
        """Physical lengths of the logical lists (driver ordering key)."""
        tt = jnp.clip(terms, 0, self.index.offsets.shape[0] - 1)
        return self.index.lengths[tt]

    def driver_slot(self, terms: jnp.ndarray, n_terms) -> jnp.ndarray:
        """Shortest-logical-list term slot (classic ZigZag driver
        ordering — the driver bounds the number of candidate postings)."""
        t_max = terms.shape[0]
        lens = jnp.where(
            jnp.arange(t_max) < n_terms,
            self.list_lengths(terms),
            jnp.int32(2**31 - 1),
        )
        return jnp.argmin(lens)

    def driver_window(self, term, window: int):
        """(docs, attrs, live) of the driver term, each ``[window]`` — the
        jnp reference's materialized driver (oracle for the streamed path)."""
        docs, attrs, valid = term_window(self.index, term, window)
        return docs, attrs, valid

    def driver_span(self, terms: jnp.ndarray, window: int) -> DriverSpan:
        """Tile spans of the driver windows — the streamed backends' driver
        handoff (batched over queries; no posting is touched here)."""
        tt = jnp.clip(terms, 0, self.index.offsets.shape[0] - 1)
        off = jnp.take(self.index.offsets, tt)
        ln = jnp.where(terms < 0, 0, jnp.take(self.index.lengths, tt))
        return DriverSpan(off, jnp.minimum(ln, window))

    def member(self, a_docs, term, window: int, a_flags=None):
        """Membership of each driver posting in the term's logical list."""
        b_docs, _, _ = term_window(self.index, term, window)
        return member_sorted(a_docs, b_docs)


class MergedPostingSource(StaticPostingSource):
    """Merge-on-read posting access over main + delta.

    The driver stream is the merged window (tombstoned postings keep their
    rank slots with ``live=0`` — the fused finalize pass kills them).  On
    the Pallas backend nothing is gathered to build it: the inherited
    :meth:`driver_span` hands the delta-merge kernel the *main* window's
    tile spans, the kernel streams main tiles and the delta slab from
    their flat arrays and emits the merged window plus each slot's stream
    id, and :meth:`driver_live` turns that stream id into the per-posting
    tombstone stream.  Other-term membership never materializes a merged
    window: a driver posting joins the logical list iff it occurs in the
    main list and its doc is neither deleted nor superseded, OR it occurs
    in the delta list and its doc is not deleted.  ``driver_flags``
    supplies the per-posting tombstone bits those probes key off.
    """

    def __init__(self, index: InvertedIndex, delta: DeltaIndex):
        super().__init__(index)
        self.delta = delta

    @property
    def doc_site(self) -> jnp.ndarray:
        return self.delta.doc_site

    def list_lengths(self, terms: jnp.ndarray) -> jnp.ndarray:
        tt = jnp.clip(terms, 0, self.index.offsets.shape[0] - 1)
        return self.index.lengths[tt] + self.delta.lengths[tt]

    def driver_window(self, term, window: int):
        docs, attrs, live = merged_term_window(
            self.index, self.delta, term, window, drop_dead=False
        )
        return docs, attrs, live > 0

    def driver_flags(self, a_docs) -> jnp.ndarray:
        """Tombstone bits of each driver posting's document."""
        return jnp.take(
            self.delta.doc_flags, a_docs, mode="fill", fill_value=0
        )

    def driver_live(self, docs, src, a_flags=None) -> jnp.ndarray:
        """Per-posting live stream of a merged driver window, from each
        slot's stream id (delta-merge kernel output; 0 = main, 1 = delta)
        and the tombstone bits — one elementwise pass, replacing the
        pre-merge host-side liveness gather of the staged path."""
        if a_flags is None:
            a_flags = self.driver_flags(docs)
        main_ok = (a_flags & jnp.int32(DOC_DEAD | DOC_SUPERSEDED)) == 0
        delta_ok = (a_flags & jnp.int32(DOC_DEAD)) == 0
        live = (docs != INVALID_DOC) & jnp.where(src == 0, main_ok, delta_ok)
        return live.astype(jnp.int32)

    def member(self, a_docs, term, window: int, a_flags=None):
        if a_flags is None:
            a_flags = self.driver_flags(a_docs)
        m_docs, _, _ = term_window(self.index, term, window)
        d_docs, _, _ = delta_term_window(self.delta, term)
        main_ok = (a_flags & jnp.int32(DOC_DEAD | DOC_SUPERSEDED)) == 0
        delta_ok = (a_flags & jnp.int32(DOC_DEAD)) == 0
        return (member_sorted(a_docs, m_docs) & main_ok) | (
            member_sorted(a_docs, d_docs) & delta_ok
        )


def make_posting_source(
    index: InvertedIndex, delta: DeltaIndex | None
) -> StaticPostingSource:
    return (
        StaticPostingSource(index)
        if delta is None
        else MergedPostingSource(index, delta)
    )


# ---------------------------------------------------------------------------
# Query execution (single query; vmap'ed for the batch)
# ---------------------------------------------------------------------------

def _query_topk_one(
    source: StaticPostingSource,
    terms: jnp.ndarray,       # int32[T_MAX]
    n_terms: jnp.ndarray,     # int32[]
    attr_filter: jnp.ndarray, # int32[]
    *,
    k: int,
    window: int,
    attr_strategy: str,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    t_max = terms.shape[0]

    driver_slot = source.driver_slot(terms, n_terms)
    docs, attrs, mask = source.driver_window(terms[driver_slot], window)
    a_flags = (
        source.driver_flags(docs) if source.delta is not None else None
    )

    # Join every other term's list (statically unrolled over T_MAX slots).
    for slot in range(t_max):
        active = (jnp.arange(t_max)[slot] < n_terms) & (slot != driver_slot)
        m = source.member(docs, terms[slot], window, a_flags)
        mask = mask & jnp.where(active, m, True)

    # Limited search.
    if attr_strategy == "embed":
        ok = attrs == attr_filter
    elif attr_strategy == "gather":
        site = jnp.take(source.doc_site, jnp.clip(docs, 0, None), mode="clip")
        ok = site == attr_filter
    elif attr_strategy == "site_term":
        ok = jnp.ones_like(mask)  # rewritten into a term at build time
    else:
        raise ValueError(attr_strategy)
    mask = mask & jnp.where(attr_filter == NO_ATTR, True, ok)

    return _first_k_by_rank(docs, mask, k)


# ---------------------------------------------------------------------------
# Kernel-backed execution (batched Pallas ZigZag join with posting skipping)
# ---------------------------------------------------------------------------

def _query_topk_batch_pallas(
    index: InvertedIndex,
    batch: QueryBatch,
    *,
    k: int,
    window: int,
    attr_strategy: str,
    interpret: bool,
    delta: DeltaIndex | None = None,
    use_packed: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fully-streamed Pallas path: the PostingSource hands the kernels
    driver tile spans (:class:`DriverSpan`) and every posting — driver and
    other-term alike — is read tile-by-tile from the flat arrays through
    scalar-prefetched BlockSpec index maps.  No ``(Q, window)`` driver
    gather and no ``(Q, T_MAX, window)`` staging buffer exist anywhere on
    this path; the driver window materializes exactly once, as kernel
    *output* (the candidate set top-k selects from).  Under merge-on-read
    the driver merge runs in VMEM over the streamed main window and delta
    slab (:func:`repro.kernels.delta_merge.merge_delta_windows`) and the
    join probes main and delta streams separately with the tombstone flags
    deciding which probe counts (see :class:`MergedPostingSource`)."""
    from repro.kernels import ops

    t_max = batch.terms.shape[1]
    source = make_posting_source(index, delta)

    def pick(terms, n_terms):
        driver_slot = source.driver_slot(terms, n_terms)
        slots = jnp.arange(t_max)
        active = ((slots < n_terms) & (slots != driver_slot)).astype(jnp.int32)
        return terms[driver_slot], active

    d_terms, active = jax.vmap(pick)(batch.terms, batch.n_terms)
    span = source.driver_span(d_terms, window)

    # The kernels' fused attribute predicate serves the embed strategy
    # (the attrs stream rides the same tiles as the postings); site_term
    # rewrites the restriction into a join term at build time, and gather
    # — the deliberately un-integrated Fig 1(c) plan — joins the doc->site
    # table host-side below.  Both of those disable the fused predicate
    # (it keys off attr_filter >= 0).
    kernel_filter = (
        batch.attr_filter
        if attr_strategy == "embed"
        else jnp.full_like(batch.attr_filter, NO_ATTR)
    )
    if attr_strategy not in ("embed", "gather", "site_term"):
        raise ValueError(attr_strategy)

    packed = index.packed if use_packed else None
    if delta is None:
        docs, mask = ops.intersect_fullstream(
            span.off, span.n_eff, batch.terms, active, kernel_filter,
            index.postings, index.attrs, index.offsets, index.lengths,
            index.block_max, window=window, packed=packed,
            interpret=interpret,
        )
    else:
        d_packed = delta.packed if use_packed else None
        docs, mattrs, msrc = ops.merge_windows(
            index.postings, index.attrs, span.off, span.n_eff,
            delta.postings, delta.attrs, delta.offsets, delta.lengths,
            delta.block_max, d_terms, window=window,
            packed=packed, d_packed=d_packed, interpret=interpret,
        )
        a_flags = source.driver_flags(docs)
        live = source.driver_live(docs, msrc, a_flags)
        mask = ops.intersect_streamed(
            docs, mattrs, live, batch.terms, active, kernel_filter,
            index.postings, index.offsets, index.lengths, index.block_max,
            delta.postings, delta.offsets, delta.lengths, delta.block_max,
            a_flags,
            packed=packed, d_packed=d_packed,
            interpret=interpret,
        )

    if attr_strategy == "gather":
        site = jnp.take(source.doc_site, jnp.clip(docs, 0, None), mode="clip")
        ok = site == batch.attr_filter[:, None]
        mask = mask * jnp.where(batch.attr_filter[:, None] == NO_ATTR, True, ok)
    return jax.vmap(partial(_first_k_by_rank, k=k))(docs, mask > 0)


# ---------------------------------------------------------------------------
# Legacy staged path (backend="pallas_staged"): the pre-streaming data path,
# kept only as the before/after comparator for benchmarks/bench_updates.py
# ---------------------------------------------------------------------------

def _query_windows(
    index: InvertedIndex,
    batch: QueryBatch,
    *,
    window: int,
    attr_strategy: str,
    delta: DeltaIndex | None = None,
):
    """Stage the batch for the batched kernel: per-query driver window +
    attribute stream + tombstone/live stream, all T_MAX other-term windows,
    and active-slot flags.

    The driver's slot rides along as an *inactive* other-term slot, so the
    kernel sees a static (Q, T_MAX, window) layout regardless of n_terms.
    With a delta attached every window is the merge-on-read logical window;
    the driver keeps tombstoned postings (``live=0``) so the kernel can
    apply the tombstone predicate in its fused finalize pass.
    """
    t_max = batch.terms.shape[1]
    source = make_posting_source(index, delta)

    def one(terms, n_terms):
        driver_slot = source.driver_slot(terms, n_terms)
        if delta is None:
            others = jax.vmap(
                lambda tm: term_window(index, tm, window)[0]
            )(terms)  # (T_MAX, window)
            # The driver window is one of the slot sweeps — select, don't
            # regather.
            docs = jnp.take(others, driver_slot, axis=0)
            live = jnp.ones_like(docs)
            if attr_strategy in ("embed", "site_term"):
                # Embedded-attribute stream of the driver window (for
                # site_term the predicate is disabled downstream; the
                # stream is unused).  The unused docs/valid outputs are
                # dead-code-eliminated by XLA.
                _, astream, _ = term_window(index, terms[driver_slot], window)
            elif attr_strategy == "gather":
                astream = jnp.take(
                    index.doc_site, jnp.clip(docs, 0, None), mode="clip"
                )
            else:
                raise ValueError(attr_strategy)
        else:
            others = jax.vmap(
                lambda tm: merged_term_window(
                    index, delta, tm, window, drop_dead=True
                )[0]
            )(terms)  # (T_MAX, window), tombstones dropped pre-probe
            docs, mattrs, live = merged_term_window(
                index, delta, terms[driver_slot], window, drop_dead=False
            )
            if attr_strategy in ("embed", "site_term"):
                astream = mattrs
            elif attr_strategy == "gather":
                astream = jnp.take(
                    delta.doc_site, jnp.clip(docs, 0, None), mode="clip"
                )
            else:
                raise ValueError(attr_strategy)
        slots = jnp.arange(t_max)
        active = ((slots < n_terms) & (slots != driver_slot)).astype(jnp.int32)
        return docs, astream, live, others, active

    return jax.vmap(one)(batch.terms, batch.n_terms)


def _query_topk_batch_staged(
    index: InvertedIndex,
    batch: QueryBatch,
    *,
    k: int,
    window: int,
    attr_strategy: str,
    interpret: bool,
    delta: DeltaIndex | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Legacy staged path: gathers every other-term window into a
    ``(Q, T_MAX, window)`` HBM buffer (merge-on-read additionally pays a
    host-side jnp merge sort per (query, term)) before one pallas_call.
    Retained only for A/B measurement against the streaming path."""
    from repro.kernels import ops

    docs, astream, live, others, active = _query_windows(
        index, batch, window=window, attr_strategy=attr_strategy, delta=delta
    )
    # site_term rewrites the restriction into a join term at build time; the
    # jnp backend ignores attr_filter under this strategy, so disable the
    # kernel's fused predicate too (it keys off attr_filter >= 0).
    attr_filter = (
        jnp.full_like(batch.attr_filter, NO_ATTR)
        if attr_strategy == "site_term"
        else batch.attr_filter
    )
    mask = ops.intersect_batched(
        docs, astream, others, active, attr_filter,
        a_live=None if delta is None else live,
        interpret=interpret,
    )
    return jax.vmap(partial(_first_k_by_rank, k=k))(docs, mask > 0)


@partial(jax.jit, static_argnames=("window", "attr_strategy"))
def _compact_prelude(index, batch, delta, *, window, attr_strategy):
    """Jitted front half of the compacted path: driver pick + span +
    kernel-side attr filter.  Everything up to the first host sync the
    work-list builders need."""
    t_max = batch.terms.shape[1]
    source = make_posting_source(index, delta)

    def pick(terms, n_terms):
        driver_slot = source.driver_slot(terms, n_terms)
        slots = jnp.arange(t_max)
        active = ((slots < n_terms) & (slots != driver_slot)).astype(jnp.int32)
        return terms[driver_slot], active

    d_terms, active = jax.vmap(pick)(batch.terms, batch.n_terms)
    span = source.driver_span(d_terms, window)
    kernel_filter = (
        batch.attr_filter
        if attr_strategy == "embed"
        else jnp.full_like(batch.attr_filter, NO_ATTR)
    )
    return d_terms, active, span.off, span.n_eff, kernel_filter


@jax.jit
def _compact_driver_state(index, delta, docs, msrc):
    """Jitted middle stage: driver flags + liveness between the merge and
    probe kernels of the compacted delta path."""
    source = make_posting_source(index, delta)
    a_flags = source.driver_flags(docs)
    live = source.driver_live(docs, msrc, a_flags)
    return a_flags, live


@partial(jax.jit, static_argnames=("k", "attr_strategy"))
def _compact_finish(index, delta, batch, docs, mask, *, k, attr_strategy):
    """Jitted back half of the compacted path: host-strategy site mask +
    rank-order top-k selection."""
    if attr_strategy == "gather":
        source = make_posting_source(index, delta)
        site = jnp.take(source.doc_site, jnp.clip(docs, 0, None), mode="clip")
        ok = site == batch.attr_filter[:, None]
        mask = mask * jnp.where(batch.attr_filter[:, None] == NO_ATTR, True, ok)
    return jax.vmap(partial(_first_k_by_rank, k=k))(docs, mask > 0)


def _query_topk_batch_pallas_compact(
    index: InvertedIndex,
    batch: QueryBatch,
    *,
    k: int,
    window: int,
    attr_strategy: str,
    interpret: bool,
    delta: DeltaIndex | None = None,
    use_packed: bool = False,
    live_q=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Work-list compacted twin of :func:`_query_topk_batch_pallas`: the
    same fully-streamed data path, but every kernel launches a 1-D grid
    over a host-built dense work list (:mod:`repro.kernels.worklist`), so
    inert padding queries (``live_q`` false), absent term slots, and empty
    probe spans contribute zero grid steps.  The builders pull the probe
    plans to the host, which is why this path cannot live inside the one
    jitted dispatcher — instead it is a chain of jitted stages
    (:func:`_compact_prelude` → kernel launches → :func:`_compact_finish`)
    with only the descriptor construction between them running in Python
    (the inner pallas calls are jitted per work-list shape, pow2-bucketed
    by :func:`repro.kernels.worklist.worklist_pad`)."""
    from repro.kernels import ops

    if attr_strategy not in ("embed", "gather", "site_term"):
        raise ValueError(attr_strategy)
    d_terms, active, span_off, span_neff, kernel_filter = _compact_prelude(
        index, batch, delta, window=window, attr_strategy=attr_strategy
    )

    packed = index.packed if use_packed else None
    if delta is None:
        docs, mask = ops.intersect_fullstream_compact(
            span_off, span_neff, batch.terms, active, kernel_filter,
            index.postings, index.attrs, index.offsets, index.lengths,
            index.block_max, window=window, packed=packed,
            interpret=interpret, live_q=live_q,
        )
    else:
        d_packed = delta.packed if use_packed else None
        docs, mattrs, msrc = ops.merge_windows_compact(
            index.postings, index.attrs, span_off, span_neff,
            delta.postings, delta.attrs, delta.offsets, delta.lengths,
            delta.block_max, d_terms, window=window,
            packed=packed, d_packed=d_packed, interpret=interpret,
            live_q=live_q,
        )
        a_flags, live = _compact_driver_state(index, delta, docs, msrc)
        mask = ops.intersect_streamed_compact(
            docs, mattrs, live, batch.terms, active, kernel_filter,
            index.postings, index.offsets, index.lengths, index.block_max,
            delta.postings, delta.offsets, delta.lengths, delta.block_max,
            a_flags,
            packed=packed, d_packed=d_packed,
            interpret=interpret, live_q=live_q,
        )

    return _compact_finish(
        index, delta, batch, docs, mask, k=k, attr_strategy=attr_strategy
    )


@partial(
    jax.jit,
    static_argnames=(
        "k", "window", "attr_strategy", "backend", "interpret", "codec"
    ),
)
def _query_topk_jitted(
    index: InvertedIndex,
    batch: QueryBatch,
    *,
    delta: DeltaIndex | None = None,
    k: int = 10,
    window: int = 4096,
    attr_strategy: str = "embed",
    backend: str = "jnp",
    interpret: bool | None = None,
    codec: str = "raw",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched local top-k.  Returns (docids[Q, k], n_hits[Q]).

    docids are local to this index/shard, ascending (= rank order), padded
    with INVALID_DOC when fewer than k documents match inside the window.

    ``delta`` attaches a per-shard online-update delta
    (:mod:`repro.indexing`): every posting access becomes merge-on-read
    over main + delta with tombstone filtering, so inserts/updates/deletes
    are visible without touching the main index.

    ``backend`` selects the execution engine:

    - ``"jnp"``    — the pure-jnp reference join (searchsorted membership
      through the same :class:`PostingSource` layer);
    - ``"pallas"`` — the fully-streamed block-skipping Pallas path: driver
      windows and other-term probes both read tile-by-tile from the flat
      index arrays
      (:func:`repro.kernels.posting_intersect.intersect_batched_driver_streamed`
      on the static index;
      :func:`repro.kernels.delta_merge.merge_delta_windows` +
      :func:`repro.kernels.posting_intersect.intersect_batched_streamed`
      under merge-on-read); ``interpret=True`` runs it under the Pallas
      interpreter so CPU CI checks the exact kernel the TPU compiles.
      ``interpret=None`` picks interpret mode automatically off-TPU.
    - ``"pallas_staged"`` — the legacy gather-based path (per-batch
      ``(Q, T_MAX, window)`` staging + host-side merge sort), kept as the
      before/after comparator for ``benchmarks/bench_updates.py``.

    ``codec="packed"`` reads postings through the block codec: the index
    (and delta snapshot, when attached) must carry its packed twin.  On
    the ``pallas`` backend the packed words stream straight into the
    kernels and decode in VMEM; the other backends decode the full array
    on device first (``unpack_flat_postings_jnp``) — same results, which
    is exactly the codec bit-parity oracle.  ``codec="raw"`` (default)
    keeps the uncompressed read path as the A/B comparator.
    """
    if codec not in ("raw", "packed"):
        raise ValueError(f"unknown codec {codec!r}")
    if codec == "packed":
        if index.packed is None:
            raise ValueError(
                "codec='packed' needs an index carrying its packed twin "
                "(build_index(codec='packed') or pack_index)"
            )
        if delta is not None and delta.packed is None:
            raise ValueError(
                "codec='packed' needs a delta snapshot with a packed twin "
                "(DeltaWriter(codec='packed'))"
            )
        if backend != "pallas":
            index = index._replace(
                postings=unpack_flat_postings_jnp(index.packed)
            )
            if delta is not None:
                delta = delta._replace(
                    postings=unpack_flat_postings_jnp(delta.packed)
                )
    if backend == "jnp":
        source = make_posting_source(index, delta)
        fn = partial(
            _query_topk_one,
            source,
            k=k,
            window=window,
            attr_strategy=attr_strategy,
        )
        return jax.vmap(fn)(batch.terms, batch.n_terms, batch.attr_filter)
    if backend in ("pallas", "pallas_staged"):
        from repro.kernels import ops

        interpret = ops.resolve_interpret(
            interpret, packed=backend == "pallas" and codec == "packed"
        )
        if backend == "pallas":
            return _query_topk_batch_pallas(
                index,
                batch,
                k=k,
                window=window,
                attr_strategy=attr_strategy,
                interpret=interpret,
                delta=delta,
                use_packed=codec == "packed",
            )
        return _query_topk_batch_staged(
            index,
            batch,
            k=k,
            window=window,
            attr_strategy=attr_strategy,
            interpret=interpret,
            delta=delta,
        )
    raise ValueError(f"unknown backend {backend!r}")


def query_topk(
    index: InvertedIndex,
    batch: QueryBatch,
    *,
    delta: DeltaIndex | None = None,
    k: int = 10,
    window: int = 4096,
    attr_strategy: str = "embed",
    backend: str = "jnp",
    interpret: bool | None = None,
    codec: str = "raw",
    live_q=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched local top-k — the public entry point.

    ``backend="jnp"``, ``"pallas"``, and ``"pallas_staged"`` delegate to
    the jitted engine (see :func:`_query_topk_jitted` for the full
    semantics).  ``backend="pallas_compact"`` runs the same fully-streamed
    Pallas data path through the work-list compaction layer
    (:mod:`repro.kernels.worklist`): kernels launch 1-D grids over dense
    host-built work lists, so grid steps are proportional to *live* work,
    not bucket shape.  ``live_q`` (host bool[Q], compact backend only)
    marks inert padding queries; their result rows come back as
    (INVALID_DOC, 0) without costing a single grid step, and an all-inert
    batch launches no kernel at all.  Bit-identical to ``"pallas"`` on
    live rows.
    """
    if backend != "pallas_compact":
        if live_q is not None:
            raise ValueError(
                "live_q needs backend='pallas_compact' (the dense grids "
                "already mask inert queries in-kernel)"
            )
        return _query_topk_jitted(
            index, batch, delta=delta, k=k, window=window,
            attr_strategy=attr_strategy, backend=backend,
            interpret=interpret, codec=codec,
        )
    if codec not in ("raw", "packed"):
        raise ValueError(f"unknown codec {codec!r}")
    if codec == "packed":
        if index.packed is None:
            raise ValueError(
                "codec='packed' needs an index carrying its packed twin "
                "(build_index(codec='packed') or pack_index)"
            )
        if delta is not None and delta.packed is None:
            raise ValueError(
                "codec='packed' needs a delta snapshot with a packed twin "
                "(DeltaWriter(codec='packed'))"
            )
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret, packed=codec == "packed")
    return _query_topk_batch_pallas_compact(
        index, batch, k=k, window=window, attr_strategy=attr_strategy,
        interpret=interpret, delta=delta, use_packed=codec == "packed",
        live_q=live_q,
    )


@partial(jax.jit, static_argnames=("k",))
def single_keyword_topk(
    index: InvertedIndex, terms: jnp.ndarray, *, k: int = 10
) -> jnp.ndarray:
    """The paper's headline fast path: top-k of a single keyword is a
    k-prefix read of the rank-ordered posting list — no join, no sort."""

    def one(term):
        docs, _, valid = term_window(index, term, k)
        return jnp.where(valid, docs, INVALID_DOC)

    return jax.vmap(one)(terms)


# ---------------------------------------------------------------------------
# Host-side brute-force oracle (for property tests)
# ---------------------------------------------------------------------------

def brute_force_topk(
    corpus, queries: list[tuple[list[int], int | None]], k: int
) -> list[list[int]]:
    """Ground truth by Python set intersection over the raw corpus."""
    out = []
    for ts, site in queries:
        sets = []
        for t in ts:
            s = set()
            for d in range(corpus.n_docs):
                if t in corpus.terms_of(d):
                    s.add(d)
            sets.append(s)
        docs = set.intersection(*sets) if sets else set()
        if site is not None:
            docs = {d for d in docs if corpus.doc_site[d] == site}
        out.append(sorted(docs)[:k])
    return out
