"""Per-shard delta index: the online-update half of DB-IR.

ODYS's central claim (PAPER.md; §1, §3) is that a search engine built on a
tightly-integrated parallel DBMS can update its IR index *transactionally,
online* — no batch rebuild, no stale-index window — which GFS-style
engines cannot.  This module supplies that write path for the TPU index
layout of :mod:`repro.core.index`:

**DeltaIndex** (device view, one per shard) is a small, fixed-capacity
posting buffer with the *same* CSR + skip-table layout as the main
:class:`~repro.core.index.InvertedIndex`:

- ``offsets[t] = t * term_capacity`` — every term owns a fixed,
  BLOCK-aligned slab (the delta's analogue of the main CSR; kept as an
  explicit array so the two structures are interchangeable to readers);
- ``postings``/``attrs`` — local docIDs ascending per list, the embedded
  siteId riding alongside exactly as in the main index;
- ``block_max`` — the per-BLOCK skip table over the delta slab;
- ``doc_flags`` — the **tombstone bitmap**.  One int32 of flag bits per
  local docID, sized to cover *both* structures (all base docs plus the
  insert headroom):

  * ``DOC_DEAD`` — the document is deleted; every posting of it, in main
    *and* delta, is masked at read time;
  * ``DOC_SUPERSEDED`` — the document was updated; its *main* postings are
    stale (masked), its live postings are in the delta.  A delta posting is
    therefore live iff its doc is not DEAD; a main posting is live iff its
    doc is neither DEAD nor SUPERSEDED.

- ``doc_site`` — the authoritative local docID -> siteId table covering
  base + delta docs (updates may move a document between sites).

**DeltaWriter** is the host-side transaction manager: ``insert_docs`` /
``delete_docs`` / ``update_docs`` mutate per-shard numpy mirrors and a
monotone version counter.  New documents take the next global docIDs and
stripe across shards with the existing ``d % ns`` map, so
:func:`repro.core.index.local_to_global_docids` needs no change.

**Publish.**  :meth:`DeltaWriter.device_delta` returns the current version
as a :class:`ShardedDelta` on the device (fixed shapes — mutations never
retrigger compilation of the query programs).  A version usually differs
from the placed one by a few term slabs and documents, so the writer
stamps every ``(shard, term)`` slab with its op counter when a posting
lands in or leaves it, and logs every document whose flags or site it
writes.  A publish is two steps, which the serving path times apart:
:meth:`DeltaWriter.host_publish` gathers, on the host, the whole rows of
the slabs stamped after the placed snapshot, their lengths and skip-table
rows, and the logged documents' entries, into one buffer padded to a
fixed bucket (:data:`PATCH_BUCKETS`); ``device_delta`` copies it to the
device, where one jitted scatter writes it into a *copy* of the placed
snapshot.  Snapshots are never donated or written in place, so a batch in
flight keeps reading the version it was dispatched with.  The first
publish, the first after a :meth:`~DeltaWriter.rebase` (compaction, or a
new generation's shapes), and one with more dirty slabs or documents than
the last bucket place :meth:`DeltaWriter.host_delta` — the whole snapshot
rebuilt from the mirrors, also the oracle the patch is tested against —
and compile every bucket's scatter there, so no publish after it compiles.

**Freshness semantics** (merge-on-read, see :mod:`repro.core.engine`):
a query that starts after ``device_delta()`` returns sees every mutation
applied before the snapshot — per-batch snapshot isolation.  Results are
identical to a from-scratch rebuild over the mutated corpus as long as the
query window covers the merged list (the same bounded-window assumption
the read-only engine already makes); deleted docs continue to occupy
driver-window slots until compaction folds them out
(:mod:`repro.indexing.compaction`).

**ShardedDeltaWriter** (multi-master ingest, PR 10) extends the writer to
concurrent insert/delete/update streams: per-shard locks on the posting
path, per-shard write queues for striped submission, and publishes
stamped with a :class:`VectorVersion` ``(writer_epoch, per-shard seqs)``
so version-stamped caches stay correct without a global write lock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import threading
from collections import deque
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.index import (
    BLOCK,
    DESC_PAD,
    DOC_DEAD,       # noqa: F401  (canonical home: core.index, next to the
    DOC_SUPERSEDED,  # noqa: F401  layout constants the kernels import)
    INVALID_ATTR,
    INVALID_DOC,
    IndexMeta,
    PackedFlatArrays,
    export_index_bytes,
    flat_tile_pad,
    pack_flat_postings,
)
from repro.data.corpus import Corpus, corpus_from_docs
from repro.obs.registry import MetricsRegistry, get_registry


class DeltaFullError(RuntimeError):
    """The delta is out of posting or document capacity.

    Batches apply document-by-document: when this is raised mid-batch the
    *earlier* documents remain applied (and visible to the next snapshot);
    ``applied`` tells the caller how many, so a retry after compaction must
    resume from that offset instead of re-submitting the whole batch.
    """

    def __init__(self, msg: str, *, applied: int = 0):
        super().__init__(msg)
        self.applied = applied


class DeltaIndex(NamedTuple):
    """Device-side delta for ONE shard (same layout family as the main index).

    ``postings``/``attrs`` are TILE-padded (like the main index) so the
    streaming kernels can DMA whole (8, 128) tiles straight from the flat
    arrays; ``block_max`` keeps its *exact* ``(n_terms*cap)//BLOCK`` length
    — it is both the skip table the device read path consumes and the
    record of the slab capacity (:attr:`term_capacity` derives from it).
    """

    offsets: jnp.ndarray    # int32[n_terms]   t * term_capacity (BLOCK-aligned)
    lengths: jnp.ndarray    # int32[n_terms]   valid postings per list
    postings: jnp.ndarray   # int32[>= n_terms * cap] docIDs (TILE-padded)
    attrs: jnp.ndarray      # int32[>= n_terms * cap] siteIds (TILE-padded)
    block_max: jnp.ndarray  # int32[(n_terms*cap)//BLOCK] skip table (valid-max)
    doc_flags: jnp.ndarray  # int32[nd_cap]    tombstone bitmap (both structures)
    doc_site: jnp.ndarray   # int32[nd_cap]    authoritative docID -> siteId
    # Block-codec twin of ``postings`` (DeltaWriter(codec="packed") attaches
    # it per shard); trailing + defaulted so positional construction from
    # the 7 ShardedDelta fields keeps working.
    packed: PackedFlatArrays | None = None

    @property
    def term_capacity(self) -> int:
        # block_max is exact (never padded), so the slab width is static
        # even though the flat posting arrays carry TILE padding.
        return self.block_max.shape[-1] * BLOCK // self.offsets.shape[-1]


class ShardedDelta(NamedTuple):
    """ns stacked per-shard deltas (leading axis = shard, like ShardedIndex)."""

    offsets: jnp.ndarray    # int32[ns, n_terms]
    lengths: jnp.ndarray    # int32[ns, n_terms]
    postings: jnp.ndarray   # int32[ns, n_terms * cap]
    attrs: jnp.ndarray      # int32[ns, n_terms * cap]
    block_max: jnp.ndarray  # int32[ns, (n_terms*cap)//BLOCK]
    doc_flags: jnp.ndarray  # int32[ns, nd_cap]
    doc_site: jnp.ndarray   # int32[ns, nd_cap]


def local_delta(stacked: ShardedDelta) -> DeltaIndex:
    """Inside shard_map each device sees a leading shard dim of 1."""
    return DeltaIndex(*(x[0] for x in stacked))


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _pad_block(n: int) -> int:
    return _ceil_div(n, BLOCK) * BLOCK


def _block_max_rows(rows: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Skip-table rows of whole term slabs ``rows`` (n, cap): per BLOCK the
    max over the slab's *valid* postings (its first ``lengths`` entries),
    INVALID_DOC for a block that holds none."""
    n, cap = rows.shape
    valid = np.arange(cap)[None] < lengths[:, None]
    bm = np.where(valid, rows, -1).reshape(n, cap // BLOCK, BLOCK).max(axis=2)
    return np.where(bm >= 0, bm, INVALID_DOC).astype(np.int32)


#: Dirty-slab counts a patch publish pads to (its documents share the
#: bucket), so every patch has one of a few fixed shapes.  A version with
#: more dirty slabs or documents than the last bucket publishes in full.
PATCH_BUCKETS = (64, 256, 1024, 4096)


def _patch_parts(buf, rows: int, cap: int):
    """Split a patch buffer of ``rows`` entries into its fields, each
    ``(rows, width)``: slab shard, slab term, slab length, postings row,
    attrs row, skip-table row, doc shard, doc local id, doc flags, doc
    site.  Views on the host, static slices under jit."""
    widths = (1, 1, 1, cap, cap, cap // BLOCK, 1, 1, 1, 1)
    out, at = [], 0
    for w in widths:
        out.append(buf[at : at + rows * w].reshape(rows, w))
        at += rows * w
    return out


def _patch_size(rows: int, cap: int) -> int:
    return rows * (7 + 2 * cap + cap // BLOCK)


def _put_rows(x, shard, start, rows):
    """``x[shard[i], start[i] : start[i] + w] = rows[i]`` for every i; a
    row whose shard is out of range (bucket padding) is dropped."""
    dn = lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 1),
    )
    return lax.scatter(
        x, jnp.concatenate([shard, start], axis=1), rows, dn,
        unique_indices=True, mode=lax.GatherScatterMode.FILL_OR_DROP,
    )


@functools.partial(jax.jit, static_argnames=("rows",))
def _apply_patch(lengths, postings, attrs, block_max, doc_flags, doc_site,
                 buf, *, rows: int):
    """The device half of a patch publish: a new snapshot's fields (all
    but ``offsets``), the placed ones with the patch's slabs and documents
    written over them.  Nothing is donated: the placed snapshot stays
    valid for the batches still reading it."""
    bpt = block_max.shape[1] // lengths.shape[1]
    cap = bpt * BLOCK
    s, t, ln, p, a, b, ds, dl, df, dsite = _patch_parts(buf, rows, cap)
    return (
        _put_rows(lengths, s, t, ln),
        _put_rows(postings, s, t * cap, p),
        _put_rows(attrs, s, t * cap, a),
        _put_rows(block_max, s, t * bpt, b),
        _put_rows(doc_flags, ds, dl, df),
        _put_rows(doc_site, ds, dl, dsite),
    )


class _HostPublish(NamedTuple):
    """The host half of one publish (:meth:`DeltaWriter.host_publish`)."""

    version: int                # writer._version, read before the gather
    base: int                   # version of the snapshot it patches; -1: full
    full: ShardedDelta | None   # host_delta(), on a full publish
    patch: np.ndarray | None    # int32 patch buffer (_patch_parts layout)
    rows: int                   # its bucket
    slabs: int                  # term slabs it ships
    logs: tuple[int, ...]       # per shard, doc-log entries it covers


@dataclasses.dataclass
class _ShardState:
    """Host-side numpy mirror of one shard's delta."""

    lengths: np.ndarray    # int32[n_terms]
    postings: np.ndarray   # int32[n_terms, cap]  (2D host-side; flat on device)
    attrs: np.ndarray      # int32[n_terms, cap]
    doc_flags: np.ndarray  # int32[nd_cap]
    doc_site: np.ndarray   # int32[nd_cap]
    # Dirty tracking for the patch publish: the writer version each slab
    # was last written at, and the local ids of documents written since
    # the placed snapshot (duplicates allowed).
    term_stamp: np.ndarray          # int64[n_terms]
    doc_log: list = dataclasses.field(default_factory=list)


class DeltaWriter:
    """Host-side write path over a sharded corpus: the ODYS master's
    transactional ingest, mirrored per shard.

    Parameters
    ----------
    corpus:
        The corpus the *current main index* was built from (the base).
    meta:
        The main index's :class:`IndexMeta` (term layout must match).
    ns:
        Shard count — must equal the main index's.
    term_capacity:
        Delta postings per term (rounded up to BLOCK).  A term list that
        fills up raises :class:`DeltaFullError`; compact and retry.
    doc_headroom:
        Total number of *inserted* documents the current delta generation
        can hold (sized so device shapes stay static between compactions).
        A compaction may hand the writer a larger generation via
        :meth:`rebase`'s ``doc_headroom``/``term_capacity`` — shapes may
        change at that boundary because the main index recompiles there
        anyway.
    """

    def __init__(
        self,
        corpus: Corpus,
        meta: IndexMeta,
        ns: int,
        *,
        term_capacity: int = 2 * BLOCK,
        doc_headroom: int = 1024,
        codec: str = "raw",
    ):
        assert ns >= 1
        if codec not in ("raw", "packed"):
            raise ValueError(f"unknown codec {codec!r}")
        self.codec = codec
        self._packed_cache: tuple[int, list[PackedFlatArrays]] | None = None
        self.ns = ns
        self.meta = meta
        self.include_site_terms = meta.include_site_terms
        self.vocab_size = meta.vocab_size
        self.n_sites = meta.n_sites
        self.n_terms = meta.n_terms
        self.term_capacity = _pad_block(max(term_capacity, 1))
        self._base = corpus
        self._base_n_docs = corpus.n_docs

        n_base_local = _ceil_div(corpus.n_docs, ns)
        self._doc_cap_local = _ceil_div(doc_headroom, ns)
        self._n_base_local_init = n_base_local
        # Local-docID admission limit (exact headroom); nd_cap is the
        # BLOCK-padded *array* width and may exceed it.
        self._doc_limit_local = n_base_local + self._doc_cap_local
        self.nd_cap = _pad_block(self._doc_limit_local)

        self.generation = 0
        self._shards = [self._fresh_shard(corpus, s) for s in range(ns)]

        # Mutated-corpus mirror: authoritative per-doc state, maintained
        # independently of the delta structures so compaction can be
        # *verified* against a from-scratch rebuild (compaction.py).
        self._docs: list[np.ndarray] = [
            np.asarray(corpus.terms_of(d), dtype=np.int32).copy()
            for d in range(corpus.n_docs)
        ]
        self._sites: list[int] = [int(x) for x in corpus.doc_site]
        self.n_docs = corpus.n_docs            # total, including inserts
        self._delta_docs: set[int] = set()     # gids whose live postings are in delta
        self._version = 0
        self._snapshot: ShardedDelta | None = None   # host arrays
        self._snapshot_version = -1
        # The placed device snapshot, the version it holds, and the host
        # half of the next publish.
        self._dev: ShardedDelta | None = None
        self._dev_version = -1
        self._publish: _HostPublish | None = None
        self._publish_metrics(get_registry())

    def _publish_metrics(self, reg: MetricsRegistry) -> None:
        self._m_publish_total = {
            mode: reg.counter(
                "odys_delta_publish_total",
                help="delta versions placed on the device, by how: a patch "
                     "of the dirty slabs or the whole snapshot",
                mode=mode,
            )
            for mode in ("patch", "full")
        }
        self._m_publish_slabs = reg.histogram(
            "odys_delta_publish_slabs",
            help="term slabs shipped per delta publish",
            buckets=tuple(float(4**i) for i in range(13)),
        )

    # ------------------------------------------------------------------
    # construction / rebase
    # ------------------------------------------------------------------

    def _fresh_shard(self, base: Corpus, s: int) -> _ShardState:
        st = _ShardState(
            lengths=np.zeros(self.n_terms, dtype=np.int32),
            # 2-D host-side write mirrors, flattened + tile-padded only
            # at snapshot time in host_delta().
            # lint: allow(posting-alloc)
            postings=np.full(
                (self.n_terms, self.term_capacity), INVALID_DOC, dtype=np.int32
            ),
            # lint: allow(posting-alloc)
            attrs=np.full(
                (self.n_terms, self.term_capacity), INVALID_ATTR, dtype=np.int32
            ),
            doc_flags=np.zeros(self.nd_cap, dtype=np.int32),
            doc_site=np.full(self.nd_cap, INVALID_ATTR, dtype=np.int32),
            term_stamp=np.zeros(self.n_terms, dtype=np.int64),
        )
        base_sites = base.doc_site[s::self.ns]
        st.doc_site[: base_sites.shape[0]] = base_sites
        return st

    def rebase(
        self,
        folded: Corpus,
        *,
        term_capacity: int | None = None,
        doc_headroom: int | None = None,
    ) -> None:
        """Point the writer at a freshly-compacted main index (folded is the
        corpus the new main was built from).  Resets every delta structure;
        by default doc shapes stay fixed so jitted query functions keep
        their traces for the *delta* operands (the main index itself
        changed shape).

        ``term_capacity``/``doc_headroom`` start a new delta **generation**
        with re-sized device shapes.  A compaction boundary is the one
        place this is free: the main index recompiles there anyway, so the
        delta operands may change shape alongside it.  The new headroom
        budget counts from the folded corpus (the drained delta's inserts
        are now base documents), which is what lets a growing corpus keep
        ingesting past the original lifetime-fixed headroom.
        """
        if term_capacity is not None or doc_headroom is not None:
            if term_capacity is not None:
                self.term_capacity = _pad_block(max(term_capacity, 1))
            if doc_headroom is not None:
                self._doc_cap_local = _ceil_div(max(doc_headroom, 1), self.ns)
            self._n_base_local_init = _ceil_div(folded.n_docs, self.ns)
            self._doc_limit_local = self._n_base_local_init + self._doc_cap_local
            self.nd_cap = _pad_block(self._doc_limit_local)
            self.generation += 1
            self._snapshot = None
        if _ceil_div(folded.n_docs, self.ns) > self._doc_limit_local:
            raise DeltaFullError(
                "folded corpus exceeds the writer's fixed doc capacity"
            )
        self._base = folded
        self._base_n_docs = folded.n_docs
        self._shards = [self._fresh_shard(folded, s) for s in range(self.ns)]
        self._delta_docs = set()
        self._dev = None      # every slab was reset: the next publish is full
        self._bump()

    # ------------------------------------------------------------------
    # low-level sorted posting ops (host numpy, per shard)
    # ------------------------------------------------------------------

    def _insert_posting(self, st: _ShardState, t: int, local: int, attr: int):
        ln = int(st.lengths[t])
        row, arow = st.postings[t], st.attrs[t]
        pos = int(np.searchsorted(row[:ln], local))
        row[pos + 1 : ln + 1] = row[pos:ln]
        arow[pos + 1 : ln + 1] = arow[pos:ln]
        row[pos] = local
        arow[pos] = attr
        st.lengths[t] = ln + 1
        self._stamp(st, t)

    def _remove_posting(self, st: _ShardState, t: int, local: int):
        ln = int(st.lengths[t])
        row, arow = st.postings[t], st.attrs[t]
        pos = int(np.searchsorted(row[:ln], local))
        if pos >= ln or row[pos] != local:
            return
        row[pos : ln - 1] = row[pos + 1 : ln]
        arow[pos : ln - 1] = arow[pos + 1 : ln]
        row[ln - 1] = INVALID_DOC
        arow[ln - 1] = INVALID_ATTR
        st.lengths[t] = ln - 1
        self._stamp(st, t)

    def _stamp(self, st: _ShardState, t: int):
        # After the write, above any version a publish could have read
        # before it: the slab ships with the next publish at the latest.
        st.term_stamp[t] = self._version + 1

    def _posting_terms(self, gid: int) -> list[int]:
        """All term ids carrying postings for gid's *current* version."""
        ts = [int(t) for t in self._docs[gid]]
        if self.include_site_terms:
            ts.append(self.vocab_size + self._sites[gid])
        return ts

    def _check_terms(self, terms: np.ndarray, site: int):
        if terms.size and (terms[0] < 0 or terms[-1] >= self.vocab_size):
            raise ValueError(f"term out of range: {terms}")
        if not (0 <= site < self.n_sites):
            raise ValueError(f"site out of range: {site}")

    def _shard_of(self, gid: int) -> tuple[_ShardState, int]:
        return self._shards[gid % self.ns], gid // self.ns

    def _bump(self, shard: int | None = None):
        # ``shard`` tells the multi-writer subclass which per-shard
        # sequence advanced (None = a structural bump: rebase/compaction).
        # The single-writer base keeps one monotone counter either way.
        del shard
        self._version += 1

    # ------------------------------------------------------------------
    # transactional ops
    # ------------------------------------------------------------------

    def insert_docs(
        self, docs: Sequence[tuple[Sequence[int], int]]
    ) -> list[int]:
        """Insert new documents; returns their global docIDs.

        docIDs are assigned monotonically (new docs rank below all existing
        ones — the synthetic corpus's rank-order-by-docID convention) and
        stripe across shards with the same ``d % ns`` map as the base.
        Each document is admitted atomically (capacity is checked for every
        affected posting list before any is touched) and bumps the snapshot
        version as it lands, so a mid-batch :class:`DeltaFullError` leaves
        the earlier documents applied AND visible — resume the batch from
        the exception's ``applied`` offset after compacting.
        """
        gids: list[int] = []
        for terms, site in docs:
            try:
                gids.append(self._insert_one(terms, site))
            except DeltaFullError as e:
                raise DeltaFullError(str(e), applied=len(gids)) from None
        return gids

    def _insert_one(self, terms: Sequence[int], site: int) -> int:
        """Admit ONE document (the per-doc primitive the batch loop and the
        multi-writer subclass share); returns its global docID."""
        terms_u = np.unique(np.asarray(terms, dtype=np.int64)).astype(
            np.int32
        )
        self._check_terms(terms_u, site)
        gid = self.n_docs
        st, local = self._shard_of(gid)
        if local >= self._doc_limit_local:
            raise DeltaFullError("document headroom exhausted")
        plist = [int(t) for t in terms_u]
        if self.include_site_terms:
            plist.append(self.vocab_size + site)
        for t in plist:
            if st.lengths[t] >= self.term_capacity:
                raise DeltaFullError(f"delta list full for term {t}")
        for t in plist:
            self._insert_posting(st, t, local, site)
        st.doc_site[local] = site
        st.doc_log.append(local)
        self._docs.append(terms_u)
        self._sites.append(int(site))
        self._delta_docs.add(gid)
        self.n_docs += 1
        self._bump(gid % self.ns)
        return gid

    def delete_docs(self, docids: Sequence[int]) -> None:
        """Tombstone documents.  Postings already in the delta are removed
        physically (reclaiming capacity); main postings are masked by the
        DOC_DEAD bit until compaction folds them out."""
        for gid in docids:
            self._delete_one(int(gid))

    def _delete_one(self, gid: int) -> None:
        if not (0 <= gid < self.n_docs):
            raise KeyError(f"unknown docID {gid}")
        st, local = self._shard_of(gid)
        if st.doc_flags[local] & DOC_DEAD:
            return
        if gid in self._delta_docs:
            for t in self._posting_terms(gid):
                self._remove_posting(st, t, local)
            self._delta_docs.discard(gid)
        st.doc_flags[local] |= DOC_DEAD
        st.doc_log.append(local)
        self._docs[gid] = np.zeros(0, dtype=np.int32)
        self._bump(gid % self.ns)

    def update_docs(
        self, updates: Sequence[tuple[int, Sequence[int], int | None]]
    ) -> None:
        """Replace documents in place: ``(docid, new_terms, new_site|None)``.

        The docID (= rank) is preserved.  The old version's main postings
        are masked via DOC_SUPERSEDED; an older delta version is removed
        physically; the new postings land in the delta.  As with inserts,
        each update is atomic and versioned individually: a mid-batch
        :class:`DeltaFullError` (``applied`` = count landed) or ``KeyError``
        leaves the earlier updates applied and visible.
        """
        applied = 0
        for gid, terms, site in updates:
            try:
                self._update_one(int(gid), terms, site)
            except DeltaFullError as e:
                raise DeltaFullError(str(e), applied=applied) from None
            applied += 1

    def _update_one(
        self, gid: int, terms: Sequence[int], site: int | None
    ) -> None:
        if not (0 <= gid < self.n_docs):
            raise KeyError(f"unknown docID {gid}")
        st, local = self._shard_of(gid)
        if st.doc_flags[local] & DOC_DEAD:
            raise KeyError(f"docID {gid} is deleted")
        new_site = self._sites[gid] if site is None else int(site)
        terms_u = np.unique(np.asarray(terms, dtype=np.int64)).astype(
            np.int32
        )
        self._check_terms(terms_u, new_site)
        in_delta = gid in self._delta_docs
        old_plist = set(self._posting_terms(gid)) if in_delta else set()
        new_plist = [int(t) for t in terms_u]
        if self.include_site_terms:
            new_plist.append(self.vocab_size + new_site)
        for t in new_plist:
            drop = 1 if t in old_plist else 0
            if st.lengths[t] - drop >= self.term_capacity:
                raise DeltaFullError(f"delta list full for term {t}")
        if in_delta:
            for t in old_plist:
                self._remove_posting(st, t, local)
        else:
            st.doc_flags[local] |= DOC_SUPERSEDED
        for t in new_plist:
            self._insert_posting(st, t, local, new_site)
        st.doc_site[local] = new_site
        st.doc_log.append(local)
        self._docs[gid] = terms_u
        self._sites[gid] = new_site
        self._delta_docs.add(gid)
        self._bump(gid % self.ns)

    def apply(self, mutations) -> None:
        """Apply a :func:`repro.data.corpus.generate_mutations` stream."""
        for m in mutations:
            if m.op == "insert":
                self.insert_docs([(m.terms, m.site)])
            elif m.op == "delete":
                self.delete_docs([m.docid])
            elif m.op == "update":
                self.update_docs([(m.docid, m.terms, m.site)])
            else:
                raise ValueError(m.op)

    # ------------------------------------------------------------------
    # views
    # ------------------------------------------------------------------

    @property
    def version(self) -> int:
        return self._version

    @property
    def doc_headroom(self) -> int:
        """Total inserted-document capacity of the current generation."""
        return self._doc_cap_local * self.ns

    @property
    def base_corpus(self) -> Corpus:
        """The corpus the current main index was built from."""
        return self._base

    @property
    def delta_doc_ids(self) -> frozenset[int]:
        """Global docIDs whose live postings are in the delta."""
        return frozenset(self._delta_docs)

    def _exclusive(self):
        """The section a publish runs in (the multi-writer freezes)."""
        return contextlib.nullcontext()

    def device_delta(self) -> ShardedDelta:
        """The current version on the device — the one call through which
        a published snapshot is obtained; cached per version.

        Places :meth:`host_publish`'s patch (a jitted scatter into a copy
        of the placed snapshot) or full snapshot.  A returned snapshot is
        never written again."""
        with self._exclusive():
            pub = self.host_publish()
            if pub is None:
                return self._dev
            if pub.full is not None:
                dev = jax.device_put(pub.full)
                self._warm_patches(dev)
            else:
                dev = ShardedDelta(self._dev.offsets, *_apply_patch(
                    *self._dev[1:], jax.device_put(pub.patch), rows=pub.rows
                ))
            for st, n in zip(self._shards, pub.logs):
                del st.doc_log[:n]
            self._dev, self._dev_version, self._publish = dev, pub.version, None
            self._m_publish_total["full" if pub.full is not None else "patch"].inc()
            self._m_publish_slabs.observe(pub.slabs)
            return dev

    def _warm_patches(self, dev: ShardedDelta) -> None:
        """Compile every bucket's patch for ``dev``'s shapes (a cache hit
        after the first full publish of a generation): an all-padding
        patch writes nothing, and its result is dropped."""
        for rows in PATCH_BUCKETS:
            buf = np.zeros(_patch_size(rows, self.term_capacity), np.int32)
            parts = _patch_parts(buf, rows, self.term_capacity)
            parts[0][:, 0] = parts[6][:, 0] = self.ns + np.arange(rows)
            _apply_patch(*dev[1:], jax.device_put(buf), rows=rows)

    def host_publish(self) -> _HostPublish | None:
        """The host half of a publish, cached per version (None: the placed
        snapshot is current).

        Gathers the slabs stamped after the placed snapshot — whole rows of
        the current state with their lengths and skip-table rows — and the
        logged documents' flags and sites into one buffer, padded to the
        smallest bucket that holds both counts (padding rows name shards
        past the last, so the scatter drops them).  The version is read
        before the gather: a write racing it is stamped later and ships
        again with the next publish, which its whole-row content makes
        harmless.  With no placed snapshot, after a rebase, or past the
        last bucket, the publish is :meth:`host_delta` in full.
        """
        with self._exclusive():
            ver = self._version
            base = self._dev_version if self._dev is not None else -1
            if base == ver:
                return None
            pub = self._publish
            if pub is not None and (pub.version, pub.base) == (ver, base):
                return pub
            logs = tuple(len(st.doc_log) for st in self._shards)
            rows = None
            if base >= 0:
                slabs = [np.flatnonzero(st.term_stamp > base) for st in self._shards]
                docs = [
                    np.unique(np.asarray(st.doc_log[:n], np.int32))
                    for st, n in zip(self._shards, logs)
                ]
                n_slabs = sum(len(ts) for ts in slabs)
                n_docs = sum(len(ds) for ds in docs)
                rows = next(
                    (b for b in PATCH_BUCKETS if b >= max(n_slabs, n_docs)), None
                )
            if rows is None:
                self._publish = _HostPublish(
                    ver, base, self.host_delta(), None, 0,
                    self.ns * self.n_terms, logs,
                )
                return self._publish
            cap = self.term_capacity
            buf = np.zeros(_patch_size(rows, cap), np.int32)
            s_, t_, ln_, p_, a_, b_, ds_, dl_, df_, dsite_ = _patch_parts(
                buf, rows, cap
            )
            s_[n_slabs:, 0] = self.ns + np.arange(rows - n_slabs)
            ds_[n_docs:, 0] = self.ns + np.arange(rows - n_docs)
            i = j = 0
            for s, (st, ts, ds) in enumerate(zip(self._shards, slabs, docs)):
                k = slice(i, i + len(ts))
                s_[k, 0], t_[k, 0], ln_[k, 0] = s, ts, st.lengths[ts]
                p_[k], a_[k] = st.postings[ts], st.attrs[ts]
                b_[k] = _block_max_rows(st.postings[ts], st.lengths[ts])
                k = slice(j, j + len(ds))
                ds_[k, 0], dl_[k, 0] = s, ds
                df_[k, 0], dsite_[k, 0] = st.doc_flags[ds], st.doc_site[ds]
                i, j = i + len(ts), j + len(ds)
            self._publish = _HostPublish(ver, base, None, buf, rows, n_slabs, logs)
            return self._publish

    def host_delta(self) -> ShardedDelta:
        """The whole snapshot rebuilt from the host mirrors, a stacked
        pytree of numpy arrays: the full publish, and the oracle a patched
        device snapshot equals bit for bit.

        Shapes are fixed per generation, so repeated snapshots never
        retrigger compilation of jitted query functions; the snapshot is
        cached per version (mutation batches invalidate it) and its
        arrays are never written again.
        """
        with self._exclusive():
            if self._snapshot is not None and self._snapshot_version == self._version:
                return self._snapshot
            ns, cap = self.ns, self.term_capacity
            # TILE-pad the flat arrays (spare INVALID tile included — the
            # same flat_tile_pad invariant as the main index, so the
            # streaming kernels can address whole (8, 128) tiles and
            # clamped edge reads stay provably masked); block_max stays
            # exact (see DeltaIndex).
            flat = self.n_terms * cap
            flat_pad = flat_tile_pad(flat)
            postings = np.full((ns, flat_pad), INVALID_DOC, np.int32)
            attrs = np.full((ns, flat_pad), INVALID_ATTR, np.int32)
            # Skip table: unlike the main index, the max is over *valid*
            # postings only (a partially-filled block records its true max,
            # an empty block INVALID_DOC): the device read path uses this
            # table both for posting skipping and to tell an occupied slab
            # from an empty one (delta-merge skip).  Only occupied slabs
            # need the reduction.
            block_max = np.full(
                (ns, self.n_terms, cap // BLOCK), INVALID_DOC, np.int32
            )
            for s, st in enumerate(self._shards):
                postings[s, :flat] = st.postings.reshape(-1)
                attrs[s, :flat] = st.attrs.reshape(-1)
                ts = np.flatnonzero(st.lengths)
                block_max[s, ts] = _block_max_rows(st.postings[ts], st.lengths[ts])
            offsets = np.broadcast_to(
                (np.arange(self.n_terms, dtype=np.int32) * cap)[None],
                (ns, self.n_terms),
            )
            self._snapshot = ShardedDelta(
                offsets=np.ascontiguousarray(offsets),
                lengths=np.stack([s.lengths for s in self._shards]),
                postings=postings,
                attrs=attrs,
                block_max=block_max.reshape(ns, -1),
                doc_flags=np.stack([s.doc_flags for s in self._shards]),
                doc_site=np.stack([s.doc_site for s in self._shards]),
            )
            self._snapshot_version = self._version
            export_index_bytes(int(postings.nbytes), None, kind="delta")
            return self._snapshot

    def shard_deltas(self) -> list[DeltaIndex]:
        """Per-shard device views (for the sequential reference path).

        With ``codec="packed"`` each view carries the block-codec twin of
        its posting slab (re-encoded per snapshot version, cached like the
        snapshot itself) and the ``odys_index_bytes{kind="delta"}`` gauges
        report both layouts' resident totals.
        """
        stacked = self.device_delta()
        shards = [DeltaIndex(*(x[s] for x in stacked)) for s in range(self.ns)]
        if self.codec != "packed":
            return shards
        if self._packed_cache is None or self._packed_cache[0] != self._version:
            # Slab decodes span the whole per-term capacity, so descriptor
            # reads may run cap//BLOCK blocks ahead of the slab start.
            bpt = self.term_capacity // BLOCK
            packs = [
                pack_flat_postings(
                    np.asarray(d.postings), span_blocks=max(DESC_PAD, bpt)
                )
                for d in shards
            ]
            export_index_bytes(
                sum(int(np.asarray(d.postings).nbytes) for d in shards),
                sum(p.nbytes() for p in packs),
                kind="delta",
            )
            self._packed_cache = (self._version, packs)
        return [
            d._replace(packed=p)
            for d, p in zip(shards, self._packed_cache[1])
        ]

    def mutated_corpus(self) -> Corpus:
        """Materialize the authoritative post-mutation corpus (deleted docs
        become empty docs so docIDs — and thus ranks — stay stable)."""
        return corpus_from_docs(
            self._docs, self._sites,
            vocab_size=self.vocab_size, n_sites=self.n_sites,
        )

    # ------------------------------------------------------------------
    # fill / compaction triggers
    # ------------------------------------------------------------------

    def posting_fill(self) -> float:
        """Max posting-list fill fraction across shards and terms."""
        return max(
            float(s.lengths.max()) / self.term_capacity for s in self._shards
        )

    def doc_fill(self) -> float:
        """Inserted-document headroom consumed (whole writer lifetime)."""
        used = _ceil_div(self.n_docs, self.ns) - self._n_base_local_init
        return max(0.0, used / self._doc_cap_local)

    def fill(self) -> float:
        """Worst capacity dimension (reporting/monitoring)."""
        return max(self.posting_fill(), self.doc_fill())

    def needs_compaction(self, threshold: float = 0.5) -> bool:
        """True once the *posting* fill crosses ``threshold``.

        Deliberately ignores :meth:`doc_fill`: document headroom is
        consumed for the writer's lifetime (compaction cannot drain it),
        so triggering on it would re-compact on every mutation forever.
        Headroom exhaustion surfaces as :class:`DeltaFullError` at insert
        time instead — recover by creating a new writer over the
        compacted corpus.
        """
        return self.posting_fill() >= threshold


# ---------------------------------------------------------------------------
# Multi-master ingest (PR 10): concurrent streams, vector-versioned publish
# ---------------------------------------------------------------------------


class VectorVersion(NamedTuple):
    """Snapshot stamp of a :class:`ShardedDeltaWriter` publish.

    ``epoch`` counts structural transitions (rebase/compaction); ``seqs``
    is the per-shard mutation sequence at publish time.  Hashable and
    compared by value, so the version-stamped
    :class:`~repro.serving.scheduler.ResultCache` and the snapshot caches
    keyed on ``writer.version`` work unchanged: ANY shard's publish (or an
    epoch bump) makes the stamp unequal and lazily invalidates — a stale
    result is never served across any shard's mutations, without a global
    write lock imposing a total order first.
    """

    epoch: int
    seqs: tuple[int, ...]


class ShardedDeltaWriter(DeltaWriter):
    """Multi-master ingest over the per-shard delta: the ODYS deployment
    shape (§6) where several masters feed one engine's write path.

    Concurrency model
    -----------------
    - ``insert_docs`` / ``delete_docs`` / ``update_docs`` are **thread
      safe** and may be called from concurrent ingest streams.  Global
      docID allocation is a tiny serial section (an O(1) counter + doc
      table append under ``_alloc_lock``); every posting mutation runs
      under the *owning shard's* lock only, so streams touching different
      shards proceed in parallel — there is no global lock on the posting
      path.
    - ``submit_insert`` / ``submit_delete`` / ``submit_update`` stripe
      operations to **per-shard write queues** (deletes/updates by their
      docID's ``gid % ns`` home shard; inserts round-robin, since their
      shard is fixed only when the docID is allocated at apply time).
      :meth:`drain` applies queued ops FIFO per shard and may itself run
      from one worker per shard concurrently.  A queued op that loses a
      cross-stream conflict race (e.g. update of a doc another master
      deleted, or a capacity-exhausted insert) is dropped and counted on
      ``odys_ingest_conflicts_total`` instead of poisoning the queue.
    - A publish (the base class's, dirty slabs and all) runs under
      :meth:`frozen` (all shard locks, re-entrant); the snapshot's stamp
      is the :class:`VectorVersion` ``(epoch, per-shard seqs)``.

    Divergence from the single-writer base: a concurrent insert reserves
    its docID *before* the capacity check (the shard is a function of the
    docID), so a capacity-failed insert leaves a dead, empty placeholder
    doc instead of consuming nothing — global docIDs stay dense either
    way.
    """

    def __init__(
        self,
        corpus: Corpus,
        meta: IndexMeta,
        ns: int,
        *,
        term_capacity: int = 2 * BLOCK,
        doc_headroom: int = 1024,
        codec: str = "raw",
        registry: MetricsRegistry | None = None,
    ):
        super().__init__(
            corpus, meta, ns,
            term_capacity=term_capacity, doc_headroom=doc_headroom,
            codec=codec,
        )
        reg = registry if registry is not None else get_registry()
        self._publish_metrics(reg)
        # Lock order is always alloc -> shard (frozen() follows it too);
        # no path acquires the alloc lock while holding a shard lock.
        self._alloc_lock = threading.RLock()
        self._shard_locks = [threading.RLock() for _ in range(ns)]
        self._count_lock = threading.Lock()   # O(1) version-counter bumps
        self._epoch = 0
        self._seqs = [0] * ns
        self._queues: list[deque] = [deque() for _ in range(ns)]
        self._rr = itertools.count()          # insert striping cursor
        self._m_ops = {
            op: reg.counter(
                "odys_ingest_ops_total",
                help="ingest operations applied to the delta",
                op=op,
            )
            for op in ("insert", "delete", "update")
        }
        self._m_conflicts = reg.counter(
            "odys_ingest_conflicts_total",
            help="queued ops dropped at apply time (cross-stream conflict "
                 "or capacity exhaustion)",
        )
        self._m_depth = {
            s: reg.gauge(
                "odys_ingest_queue_depth",
                help="ops enqueued and not yet drained",
                shard=str(s),
            )
            for s in range(ns)
        }
        self._m_publish = {
            s: reg.gauge(
                "odys_ingest_publish_seq",
                help="per-shard mutation sequence at the last published "
                     "snapshot",
                shard=str(s),
            )
            for s in range(ns)
        }

    # ------------------------------------------------------------------
    # vector version
    # ------------------------------------------------------------------

    @property
    def version(self) -> VectorVersion:
        return VectorVersion(self._epoch, tuple(self._seqs))

    def _bump(self, shard: int | None = None):
        with self._count_lock:
            self._version += 1    # total op count (packed-cache key)
            if shard is None:
                self._epoch += 1  # structural: rebase/compaction boundary
            else:
                self._seqs[shard] += 1

    @contextlib.contextmanager
    def frozen(self):
        """Exclusive section: allocation + every shard quiesced.

        Publish (:meth:`host_delta`) and compaction
        (:func:`repro.indexing.compaction.compact`) run under this so they
        observe a cross-shard-consistent state.  Locks are re-entrant, so
        compaction's fold -> publish -> rebase nesting is fine.  Queued
        submissions still *enqueue* during a freeze — they just cannot
        drain until it lifts.
        """
        self._alloc_lock.acquire()
        for lock in self._shard_locks:
            lock.acquire()
        try:
            yield
        finally:
            for lock in reversed(self._shard_locks):
                lock.release()
            self._alloc_lock.release()

    # ------------------------------------------------------------------
    # thread-safe per-doc primitives
    # ------------------------------------------------------------------

    def _insert_one(self, terms: Sequence[int], site: int) -> int:
        terms_u = np.unique(np.asarray(terms, dtype=np.int64)).astype(
            np.int32
        )
        self._check_terms(terms_u, site)
        with self._alloc_lock:
            gid = self.n_docs
            _, local = self._shard_of(gid)
            if local >= self._doc_limit_local:
                raise DeltaFullError("document headroom exhausted")
            shard = gid % self.ns
            lock = self._shard_locks[shard]
            # Take the shard lock before publishing the allocation: a
            # rebase (frozen) can then never observe an allocated-but-
            # unapplied doc, which would fold it into the main index AND
            # apply its delta postings afterwards.
            lock.acquire()
            self.n_docs += 1
            self._docs.append(terms_u)
            self._sites.append(int(site))
        try:
            st = self._shards[shard]
            plist = [int(t) for t in terms_u]
            if self.include_site_terms:
                plist.append(self.vocab_size + site)
            for t in plist:
                if st.lengths[t] >= self.term_capacity:
                    # docID already allocated: leave a dead, empty
                    # placeholder so global docIDs stay dense
                    st.doc_flags[local] |= DOC_DEAD
                    st.doc_log.append(local)
                    self._docs[gid] = np.zeros(0, dtype=np.int32)
                    self._bump(shard)
                    raise DeltaFullError(f"delta list full for term {t}")
            for t in plist:
                self._insert_posting(st, t, local, site)
            st.doc_site[local] = site
            st.doc_log.append(local)
            self._delta_docs.add(gid)
            self._bump(shard)
        finally:
            lock.release()
        self._m_ops["insert"].inc()
        return gid

    def _delete_one(self, gid: int) -> None:
        with self._shard_locks[gid % self.ns]:
            super()._delete_one(gid)
        self._m_ops["delete"].inc()

    def _update_one(
        self, gid: int, terms: Sequence[int], site: int | None
    ) -> None:
        with self._shard_locks[gid % self.ns]:
            super()._update_one(gid, terms, site)
        self._m_ops["update"].inc()

    # ------------------------------------------------------------------
    # per-shard write queues (the multi-master staging lanes)
    # ------------------------------------------------------------------

    def submit_insert(self, terms: Sequence[int], site: int) -> None:
        """Enqueue an insert (applied at the next :meth:`drain`)."""
        self._enqueue(
            next(self._rr) % self.ns,
            ("insert", tuple(int(t) for t in terms), int(site)),
        )

    def submit_delete(self, docid: int) -> None:
        self._enqueue(int(docid) % self.ns, ("delete", int(docid)))

    def submit_update(
        self, docid: int, terms: Sequence[int], site: int | None = None
    ) -> None:
        self._enqueue(
            int(docid) % self.ns,
            ("update", int(docid), tuple(int(t) for t in terms), site),
        )

    def _enqueue(self, shard: int, op: tuple) -> None:
        self._queues[shard].append(op)   # deque.append is GIL-atomic
        self._m_depth[shard].set(float(len(self._queues[shard])))

    def queue_depth(self, shard: int | None = None) -> int:
        qs = self._queues if shard is None else [self._queues[shard]]
        return sum(len(q) for q in qs)

    def drain(self, shard: int | None = None) -> int:
        """Apply queued ops FIFO per shard; returns how many applied.

        Safe to call concurrently (e.g. one drain worker per shard):
        ops pop atomically and apply under their shard's lock.  Conflicted
        ops (see class docstring) are dropped and counted.
        """
        shards = range(self.ns) if shard is None else (int(shard),)
        applied = 0
        for s in shards:
            q = self._queues[s]
            while True:
                try:
                    op = q.popleft()
                except IndexError:
                    break
                try:
                    self._apply_queued(op)
                    applied += 1
                except (KeyError, DeltaFullError):
                    self._m_conflicts.inc()
                self._m_depth[s].set(float(len(q)))
        return applied

    def _apply_queued(self, op: tuple) -> None:
        kind = op[0]
        if kind == "insert":
            self._insert_one(list(op[1]), op[2])
        elif kind == "delete":
            self._delete_one(op[1])
        elif kind == "update":
            self._update_one(op[1], list(op[2]), op[3])
        else:
            raise ValueError(f"unknown queued op {kind!r}")

    # ------------------------------------------------------------------
    # vector-versioned publish
    # ------------------------------------------------------------------

    def rebase(self, folded, **kw) -> None:
        with self.frozen():
            super().rebase(folded, **kw)

    def _exclusive(self):
        return self.frozen()

    def device_delta(self) -> ShardedDelta:
        """Publish, stamped with the :class:`VectorVersion` it holds."""
        with self.frozen():
            snap = super().device_delta()
            for s, seq in enumerate(self._seqs):
                self._m_publish[s].set(float(seq))
            return snap
