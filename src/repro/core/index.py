"""ODYS IR index, adapted to TPU (DESIGN.md §2).

The paper's tightly-integrated IR index is:

    keyword B+-tree  ->  posting list (rank-ordered)  ->  sub-index per list
                         each posting = (docID, offsets [, embedded attrs])

TPU-native layout (all dense, HBM-resident):

- **CSR term table**: ``offsets[t] .. offsets[t]+lengths[t]`` addresses term
  ``t``'s postings in one flat array.  The B+-tree's job (term -> list head)
  becomes two O(1) array reads.
- **Postings**: ``postings`` holds docIDs, ascending per list.  docIDs are
  assigned in PageRank order, so ascending docID order *is* rank order: a
  single-keyword top-k is a k-prefix read (paper §3.1) and the ZigZag join
  streams both lists in one direction (paper §2).
- **Sub-index -> skip table**: every list is start-aligned to ``BLOCK=128``
  postings (one TPU lane row); ``block_max[b]`` is the max docID in aligned
  block ``b``.  A join can decide from ``block_max`` alone that a whole
  block cannot contain matches and skip its HBM->VMEM DMA — this is the
  paper's *posting skipping*, with a 128-posting block as the unit of I/O
  instead of a disk page.  The flat ``postings``/``attrs`` arrays are
  additionally padded to a multiple of ``TILE = 8*BLOCK``: the streaming
  kernels (:mod:`repro.kernels.posting_intersect`) DMA whole (8, 128) VMEM
  tiles straight out of these arrays via scalar-prefetched offsets, with no
  per-query window gather in between.
- **Attribute embedding**: ``attrs[p]`` stores the embedded structured
  attribute (siteId) of ``postings[p]``; a limited search is one fused
  pass over (docid, attr) pairs — the paper's Fig 4(b).
- **Site terms** (paper Fig 1(d) optimization): when
  ``include_site_terms=True``, each siteId also gets its *own* posting list
  under term id ``vocab_size + site``, so a limited search can instead run
  as a two-list ZigZag join (Fig 4(a)).
- **Block codec** (packed postings): the flat posting array additionally
  has a compressed twin, :class:`PackedFlatArrays` — per-BLOCK
  delta-encoded, bit-packed docID gaps with a fixed power-of-two bit width
  per block, chosen from the block's max gap and stored in a per-block
  descriptor next to the skip table.  HBM then holds packed words; the
  streamed kernels decode each block into VMEM right after the DMA, and
  main index, delta snapshots, and compaction all encode through
  :func:`pack_flat_postings` — one implementation, one layout contract.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.data.corpus import Corpus

BLOCK = 128                      # postings per skip-table block (lane width)
TILE = 8 * BLOCK                 # postings per VMEM tile (8 sublanes x 128 lanes)
INVALID_DOC = np.int32(2**31 - 1)  # padding docID; sorts after every real doc
INVALID_ATTR = np.int32(-1)


def flat_tile_pad(n: int) -> int:
    """Padded length of a flat posting/attr array holding ``n`` postings.

    TILE-aligned, with at least one whole spare INVALID tile past the last
    valid posting.  The spare tile is a *load-bearing* invariant of the
    streamed read path: driver windows start at BLOCK (not TILE)
    granularity, are read as the aligned tiles covering them, and a window
    tile whose read would run off the end of the array is clamped to the
    read that ends at the array edge.  The spare tile guarantees any such clamped tile
    lies entirely past every list's live range, so the kernels' intended-
    position masking discards all of it — clamping can shift *which* data
    arrives, never which data is *kept*.  Both the main index build and the
    delta snapshot (:mod:`repro.indexing.delta`) must pad through this
    helper so the invariant cannot desynchronize.

    ceil + 1, not floor + 1: when ``n`` is not a TILE multiple, floor + 1
    leaves less than a whole tile of slack past the last posting, and a
    clamped driver read of a list near the array end would serve the
    *previous* list's postings into in-window slots.
    """
    return (-(-n // TILE) + 1) * TILE


def flat_live_extent(offsets: np.ndarray, lengths: np.ndarray) -> int:
    """First flat offset past every list's BLOCK-aligned slot.

    Everything at or beyond this offset is INVALID fill — the *live
    extent* side of the padding contract.  Together with the array's
    padded length it makes the spare-tile invariant machine-checkable
    (:func:`padding_contract`, consumed by :mod:`repro.analysis`).
    """
    offsets = np.asarray(offsets)
    lengths = np.asarray(lengths)
    if offsets.size == 0:
        return 0
    padded = np.maximum(((lengths + BLOCK - 1) // BLOCK) * BLOCK, BLOCK)
    return int(np.max(offsets.astype(np.int64) + padded.astype(np.int64)))


class FlatPadding(NamedTuple):
    """Checkable form of the flat-array padding contract.

    ``live_extent`` is the first offset past every list's slot (see
    :func:`flat_live_extent`); ``padded_len`` the flat array's actual
    length.  The streamed read path is safe iff the array keeps at least
    one whole spare INVALID tile past the live extent — what
    :func:`flat_tile_pad` guarantees and :meth:`spare_tile_ok` verifies.
    """

    live_extent: int
    padded_len: int

    def spare_tile_ok(self, read_elems: int = TILE) -> bool:
        """True iff a clamped ``read_elems``-sized edge read lies entirely
        past the live extent (the invariant edge-clamped window reads
        rely on)."""
        return self.padded_len - read_elems >= self.live_extent


def padding_contract(
    offsets: np.ndarray, lengths: np.ndarray, padded_len: int
) -> FlatPadding:
    """The padding contract of a flat posting/attr array, as metadata the
    static checker (:mod:`repro.analysis`) can verify without executing a
    kernel."""
    return FlatPadding(flat_live_extent(offsets, lengths), int(padded_len))

# Tombstone bits of the online-update doc_flags bitmap (repro.indexing).
# Defined here, next to the layout constants, so the kernel layer can fuse
# the liveness predicate without depending on the write path: DEAD masks a
# doc's postings in both structures; SUPERSEDED masks its *main* postings
# only (the live version of the doc lives in the delta).
DOC_DEAD = np.int32(1)
DOC_SUPERSEDED = np.int32(2)


# ---------------------------------------------------------------------------
# Block codec: per-BLOCK delta-encoded, bit-packed postings
# ---------------------------------------------------------------------------
#
# Every BLOCK (128 postings, one lane row) compresses independently:
#
#   base  = first docID of the block (docIDs ascend inside a list, and a
#           block never straddles lists — list starts are BLOCK-aligned)
#   gaps  = docID[l] - docID[l-1]  (gap[0] = 0; base carries the level)
#   width = the smallest of PACK_WIDTHS whose range covers the block's max
#           gap — powers of two dividing 32, so a w-bit field never
#           straddles a 32-bit word and lane l's field sits at word
#           (l*w) >> 5, shift (l*w) & 31 of the block's 4*w packed words
#
# Gap coding (not offset-from-base) is deliberate: a block's gaps are ~128x
# smaller than its docID range, which is where the 3-4x win lives.  The
# per-block descriptor (base, width|count, cumulative word offset) rides in
# SMEM next to the skip table; the packed words are the only posting bytes
# HBM serves on the streamed read path — raw int32 postings exist only as
# VMEM decode output inside the kernels.

#: Legal per-block bit widths.  All divide 32 (no field straddles a word);
#: 0 encodes blocks with <= 1 posting (no gaps), 32 is the exact-docID
#: fallback for blocks whose max gap needs the full range.
PACK_WIDTHS = (0, 1, 2, 4, 8, 16, 32)

#: Descriptor arrays carry this many trailing zero blocks so a clamped
#: chunk's decode (up to TILE/BLOCK blocks past the live range) never
#: indexes out of bounds; a padding descriptor decodes to all-INVALID.
DESC_PAD = 8


def packed_word_pad(n_words: int, chunk_rows: int) -> int:
    """Padded length of a packed-words array holding ``n_words`` words.

    The packed twin of :func:`flat_tile_pad`: packed chunks are read as
    (``chunk_rows``, 128) word blocks from *row-misaligned* starts (a
    block's words begin wherever the previous block's ended), so one spare
    tile is not enough — the edge clamp must absorb a whole chunk, not a
    whole tile.  Padding ``n_words + chunk_rows * BLOCK`` through
    ``flat_tile_pad`` keeps >= one chunk plus one spare tile of zero fill
    past the live words, which is the packed-space spare-tile invariant
    the contract checker (repro.analysis) verifies.
    """
    return flat_tile_pad(n_words + chunk_rows * BLOCK)


@jax.tree_util.register_pytree_node_class
class PackedFlatArrays:
    """Compressed twin of a flat posting array (see module docstring).

    Array leaves (pytree children; device-resident under jit):

    - ``words``:    int32[W]  bit-packed gap fields, 4*width words per
      block, concatenated in block order; zero-filled padding per
      :func:`packed_word_pad`
    - ``blk_base``: int32[n_blocks + DESC_PAD]  first docID per block
    - ``blk_meta``: int32[n_blocks + DESC_PAD]  ``width | (count << 6)``
    - ``blk_woff``: int32[n_blocks + DESC_PAD + 1]  cumulative word offset
      of each block (constant past the live range — padding blocks pack to
      zero words)

    ``chunk_rows`` is static (pytree aux): the fixed (rows, 128) read that
    covers any ``span_blocks`` consecutive blocks' words regardless of
    their word alignment — it sizes every packed BlockSpec, so it must be
    a compile-time constant.
    """

    def __init__(self, words, blk_base, blk_meta, blk_woff, *, chunk_rows):
        self.words = words
        self.blk_base = blk_base
        self.blk_meta = blk_meta
        self.blk_woff = blk_woff
        self.chunk_rows = int(chunk_rows)

    def tree_flatten(self):
        return (
            (self.words, self.blk_base, self.blk_meta, self.blk_woff),
            (self.chunk_rows,),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, chunk_rows=aux[0])

    @property
    def n_blocks(self) -> int:
        """Block count of the flat array this packs (descriptor arrays
        carry DESC_PAD extra padding entries past it)."""
        return self.blk_base.shape[0] - DESC_PAD

    def nbytes(self) -> int:
        """Resident bytes of the packed structure (words + descriptors)."""
        return int(
            self.words.nbytes + self.blk_base.nbytes
            + self.blk_meta.nbytes + self.blk_woff.nbytes
        )

    def padding(self) -> FlatPadding:
        """The packed-space padding contract: live words vs padded words.
        Check with ``spare_tile_ok(read_elems=chunk_rows * BLOCK)``."""
        live_words = int(np.asarray(self.blk_woff)[-1])
        return FlatPadding(live_words, int(self.words.shape[0]))


def pack_flat_postings(
    flat: np.ndarray, *, span_blocks: int = DESC_PAD
) -> PackedFlatArrays:
    """Encode a TILE-padded flat posting array into packed-word form.

    ``span_blocks`` is the widest run of consecutive blocks any consumer
    decodes from one chunk read — TILE/BLOCK (= 8) for the tile-granular
    probe/driver streams; a delta snapshot whose per-term capacity exceeds
    TILE passes its blocks-per-term so slab decodes fit one chunk too.
    """
    flat = np.asarray(flat, dtype=np.int32)
    if flat.ndim != 1 or flat.shape[0] % TILE:
        raise ValueError("pack_flat_postings needs a TILE-padded flat array")
    n_blocks = flat.shape[0] // BLOCK
    blocks = flat.reshape(n_blocks, BLOCK)
    lane = np.arange(BLOCK, dtype=np.int32)

    valid = blocks != INVALID_DOC
    cnt = valid.sum(axis=1).astype(np.int32)
    if not np.array_equal(valid, lane[None, :] < cnt[:, None]):
        raise ValueError("valid postings must be a prefix of every BLOCK")
    base = np.where(cnt > 0, blocks[:, 0], 0).astype(np.int32)

    gaps = np.zeros_like(blocks)
    gaps[:, 1:] = blocks[:, 1:] - blocks[:, :-1]
    gaps = np.where(lane[None, :] < cnt[:, None], gaps, 0)
    gaps[:, 0] = 0
    if gaps.min(initial=0) < 0:
        raise ValueError("postings must ascend within every BLOCK")
    maxgap = gaps.max(axis=1, initial=0)

    widths = np.full(n_blocks, 32, np.int32)
    for w in (16, 8, 4, 2, 1):
        widths = np.where(maxgap <= (1 << w) - 1, w, widths)
    widths = np.where(maxgap == 0, 0, widths).astype(np.int32)

    # Cumulative word offsets; padding blocks (all-INVALID) pack to zero
    # words, so woff is constant past the live range by construction.
    wpb = widths * (BLOCK // 32)
    woff = np.zeros(n_blocks + DESC_PAD + 1, np.int64)
    np.cumsum(wpb, out=woff[1:n_blocks + 1])
    total_words = int(woff[n_blocks])
    woff[n_blocks + 1:] = total_words

    # The fixed chunk read covering any span_blocks consecutive blocks:
    # worst case over every start block of (words spanned, rounded out to
    # whole 128-word rows from the start block's row rounded down to the
    # 8-sublane tile — chunk reads start on tile boundaries, the only
    # Element offsets Mosaic accepts).
    sub = TILE // BLOCK
    span = max(DESC_PAD, int(span_blocks))
    b0 = np.arange(n_blocks, dtype=np.int64)
    end = np.minimum(b0 + span, n_blocks)
    r0 = (woff[b0] // BLOCK) // sub * sub
    rows_needed = -(-(woff[end] - r0 * BLOCK) // BLOCK)
    # Rounded up to the 8-sublane tile: chunk BlockSpecs must stay
    # (8, 128)-aligned like every other int32 block.
    chunk_rows = int(max(1, rows_needed.max(initial=1)))
    chunk_rows = -(-chunk_rows // sub) * sub

    words = np.zeros(packed_word_pad(total_words, chunk_rows), np.uint32)
    ug = gaps.astype(np.uint32)
    for w in PACK_WIDTHS[1:]:
        sel = np.nonzero(widths == w)[0]
        if sel.size == 0:
            continue
        lanes_per_word = 32 // w
        nw = BLOCK // lanes_per_word          # 4*w words per block
        g3 = ug[sel].reshape(sel.size, nw, lanes_per_word).astype(np.uint64)
        sh = np.arange(lanes_per_word, dtype=np.uint64) * np.uint64(w)
        packed = np.bitwise_or.reduce(g3 << sh[None, None, :], axis=2)
        dst = woff[sel][:, None] + np.arange(nw)[None, :]
        words[dst] = packed.astype(np.uint32)

    desc_len = n_blocks + DESC_PAD
    blk_base = np.zeros(desc_len, np.int32)
    blk_base[:n_blocks] = base
    blk_meta = np.zeros(desc_len, np.int32)
    blk_meta[:n_blocks] = widths | (cnt << 6)
    return PackedFlatArrays(
        words=jnp.asarray(words.view(np.int32)),
        blk_base=jnp.asarray(blk_base),
        blk_meta=jnp.asarray(blk_meta),
        blk_woff=jnp.asarray(woff.astype(np.int32)),
        chunk_rows=chunk_rows,
    )


def unpack_flat_postings(packed: PackedFlatArrays) -> np.ndarray:
    """Host-side (numpy) decode — the round-trip reference for the codec
    property tests.  Returns the raw TILE-padded flat array bit-exactly."""
    words = np.asarray(packed.words).view(np.uint32)
    n_blocks = packed.n_blocks
    meta = np.asarray(packed.blk_meta)[:n_blocks].astype(np.int64)
    woff = np.asarray(packed.blk_woff).astype(np.int64)[:n_blocks]
    base = np.asarray(packed.blk_base)[:n_blocks].astype(np.int64)
    w = meta & 63
    cnt = meta >> 6
    lane = np.arange(BLOCK, dtype=np.int64)
    idx = woff[:, None] + ((lane[None, :] * w[:, None]) >> 5)
    lane_word = words[np.minimum(idx, words.shape[0] - 1)].astype(np.uint64)
    shift = ((lane[None, :] * w[:, None]) & 31).astype(np.uint64)
    mask = (np.uint64(1) << w.astype(np.uint64)[:, None]) - np.uint64(1)
    gaps = (lane_word >> shift) & mask
    docs = base[:, None] + np.cumsum(gaps.astype(np.int64), axis=1)
    out = np.where(lane[None, :] < cnt[:, None], docs, int(INVALID_DOC))
    return out.astype(np.int32).reshape(-1)


def unpack_flat_postings_jnp(packed: PackedFlatArrays) -> jnp.ndarray:
    """Device-side full-array decode: the jnp backend's packed read path
    (host/XLA, not Pallas) — proves bit-parity of the codec itself, while
    ``backend="pallas"`` decodes per-block in VMEM."""
    n_blocks = packed.n_blocks
    meta = packed.blk_meta[:n_blocks]
    w = meta & 63
    cnt = meta >> 6
    lane = jnp.arange(BLOCK, dtype=jnp.int32)
    idx = packed.blk_woff[:n_blocks, None] + ((lane[None, :] * w[:, None]) >> 5)
    lane_word = jnp.take(packed.words, idx, mode="fill", fill_value=0)
    shift = (lane[None, :] * w[:, None]) & 31
    mask = jnp.where(
        w >= 32, jnp.int32(-1), (jnp.int32(1) << jnp.minimum(w, 31)) - 1
    )
    gaps = jax.lax.shift_right_logical(lane_word, shift) & mask[:, None]
    docs = packed.blk_base[:n_blocks, None] + jnp.cumsum(
        gaps, axis=1, dtype=jnp.int32
    )
    out = jnp.where(lane[None, :] < cnt[:, None], docs, INVALID_DOC)
    return out.reshape(-1)


class InvertedIndex(NamedTuple):
    """Device-side index. All fields are jnp arrays (pytree-friendly)."""

    offsets: jnp.ndarray    # int32[n_terms]   start of each list (BLOCK-aligned)
    lengths: jnp.ndarray    # int32[n_terms]   valid postings per list
    postings: jnp.ndarray   # int32[P]         docIDs, ascending per list
    attrs: jnp.ndarray      # int32[P]         embedded attribute per posting
    block_max: jnp.ndarray  # int32[P//BLOCK]  skip table (max docID per block)
    doc_site: jnp.ndarray   # int32[n_docs_pad] docID -> siteId (gather strategy)
    packed: PackedFlatArrays | None = None  # block-codec twin of ``postings``

    @property
    def n_terms(self) -> int:
        return self.offsets.shape[0]


@dataclasses.dataclass(frozen=True)
class IndexMeta:
    """Static (non-traced) metadata for an :class:`InvertedIndex`."""

    n_docs: int
    vocab_size: int
    n_sites: int
    n_terms: int           # vocab_size (+ n_sites when site terms included)
    include_site_terms: bool


def site_term_id(meta: IndexMeta, site: int) -> int:
    """Term id of the Fig 1(d) site-text posting list for ``site``."""
    assert meta.include_site_terms
    return meta.vocab_size + site


def _build_numpy(
    corpus: Corpus, include_site_terms: bool
) -> tuple[dict[str, np.ndarray], IndexMeta]:
    """Invert the corpus CSR into the term CSR, host-side."""
    n_docs, vocab = corpus.n_docs, corpus.vocab_size
    doc_ids = np.repeat(
        np.arange(n_docs, dtype=np.int64),
        np.diff(corpus.doc_offsets),
    )
    terms = corpus.doc_terms.astype(np.int64)

    if include_site_terms:
        # Each doc also "contains" the pseudo-term for its site.
        site_terms = vocab + corpus.doc_site[np.arange(n_docs)].astype(np.int64)
        terms = np.concatenate([terms, site_terms])
        doc_ids = np.concatenate([doc_ids, np.arange(n_docs, dtype=np.int64)])
        n_terms = vocab + corpus.n_sites
    else:
        n_terms = vocab

    # Sort by (term, docid): docids ascending inside each list == rank order.
    order = np.lexsort((doc_ids, terms))
    s_terms, s_docs = terms[order], doc_ids[order]
    lengths = np.bincount(s_terms, minlength=n_terms).astype(np.int32)

    # BLOCK-align every list start.
    padded = ((lengths + BLOCK - 1) // BLOCK) * BLOCK
    padded = np.maximum(padded, BLOCK)  # empty lists still own one block
    offsets = np.zeros(n_terms, dtype=np.int64)
    np.cumsum(padded[:-1], out=offsets[1:])
    total = int(offsets[-1] + padded[-1])
    # TILE-align the flat arrays with a spare INVALID tile (flat_tile_pad):
    # the streaming kernels address postings as whole (8, 128) VMEM tiles
    # straight from HBM — including the *driver* window, read at BLOCK
    # granularity via aligned Element reads — with no per-query gather.
    total = flat_tile_pad(total)

    postings = np.full(total, INVALID_DOC, dtype=np.int32)
    attrs = np.full(total, INVALID_ATTR, dtype=np.int32)
    src_off = np.zeros(n_terms + 1, dtype=np.int64)
    np.cumsum(lengths, out=src_off[1:])
    # Scatter each list into its aligned slot.
    dst = offsets[s_terms] + (np.arange(s_terms.shape[0]) - src_off[s_terms])
    postings[dst] = s_docs.astype(np.int32)
    attrs[dst] = corpus.doc_site[s_docs]

    block_max = postings.reshape(-1, BLOCK).max(axis=1)

    # doc -> site lookup table, padded to a multiple of BLOCK for kernels.
    nd_pad = ((n_docs + BLOCK - 1) // BLOCK) * BLOCK
    doc_site = np.full(nd_pad, INVALID_ATTR, dtype=np.int32)
    doc_site[:n_docs] = corpus.doc_site

    arrays = dict(
        offsets=offsets.astype(np.int32),
        lengths=lengths,
        postings=postings,
        attrs=attrs,
        block_max=block_max,
        doc_site=doc_site,
    )
    meta = IndexMeta(
        n_docs=n_docs,
        vocab_size=vocab,
        n_sites=corpus.n_sites,
        n_terms=n_terms,
        include_site_terms=include_site_terms,
    )
    return arrays, meta


def export_index_bytes(
    raw_nbytes: int, packed_nbytes: int | None, *, kind: str
) -> None:
    """Export the ``odys_index_bytes{layout, kind}`` gauges (repro.obs):
    resident posting-structure bytes of the raw flat array and, when the
    codec is on, its packed twin — the compression win as a dashboard
    number.  No-op unless metrics are enabled."""
    from repro.obs import get_registry

    reg = get_registry()
    help_ = "resident posting-structure bytes by layout and index kind"
    reg.gauge("odys_index_bytes", help=help_, layout="raw", kind=kind).set(
        int(raw_nbytes)
    )
    if packed_nbytes is not None:
        reg.gauge(
            "odys_index_bytes", help=help_, layout="packed", kind=kind
        ).set(int(packed_nbytes))


def pack_index(index: InvertedIndex) -> InvertedIndex:
    """Attach the block-codec twin to an existing index (e.g. a shard of a
    freshly-compacted :class:`ShardedIndex`)."""
    return index._replace(
        packed=pack_flat_postings(np.asarray(index.postings))
    )


def build_index(
    corpus: Corpus, *, include_site_terms: bool = True, codec: str = "raw"
) -> tuple[InvertedIndex, IndexMeta]:
    if codec not in ("raw", "packed"):
        raise ValueError(f"unknown codec {codec!r}")
    arrays, meta = _build_numpy(corpus, include_site_terms)
    packed = (
        pack_flat_postings(arrays["postings"]) if codec == "packed" else None
    )
    idx = InvertedIndex(
        **{k: jnp.asarray(v) for k, v in arrays.items()}, packed=packed
    )
    export_index_bytes(
        arrays["postings"].nbytes,
        None if packed is None else packed.nbytes(),
        kind="main",
    )
    return idx, meta


# ---------------------------------------------------------------------------
# Document partitioning (paper §3.1: "partitioning by documents")
# ---------------------------------------------------------------------------

def partition_corpus(corpus: Corpus, ns: int) -> list[Corpus]:
    """Stripe docs round-robin by *rank*: global doc d -> shard d % ns,
    local docID d // ns.

    Striping (vs contiguous ranges) keeps every shard's rank distribution
    identical, so per-shard top-k candidate quality is balanced — the
    property the paper relies on when merging per-slave top-k lists.
    The map is deterministic and invertible:  global = local * ns + shard,
    which is what makes elastic re-partitioning a pure reshuffle
    (launch/elastic.py).
    """
    shards = []
    for s in range(ns):
        sel = np.arange(s, corpus.n_docs, ns)
        lens = np.diff(corpus.doc_offsets)[sel]
        offs = np.zeros(sel.shape[0] + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        gather = np.concatenate(
            [
                corpus.doc_terms[corpus.doc_offsets[d]:corpus.doc_offsets[d + 1]]
                for d in sel
            ]
        ) if sel.size else np.zeros(0, dtype=np.int32)
        shards.append(
            Corpus(
                doc_offsets=offs,
                doc_terms=gather,
                doc_site=corpus.doc_site[sel],
                n_docs=int(sel.shape[0]),
                vocab_size=corpus.vocab_size,
                n_sites=corpus.n_sites,
            )
        )
    return shards


class ShardedIndex(NamedTuple):
    """ns stacked per-shard indexes, padded to common shapes.

    Leading axis = shard; intended to be laid out over the mesh ``data``
    axis (one shard per "slave").  Plus the static local->global docID map
    parameters (ns, shard id) applied at merge time.
    """

    offsets: jnp.ndarray    # int32[ns, n_terms]
    lengths: jnp.ndarray    # int32[ns, n_terms]
    postings: jnp.ndarray   # int32[ns, P]
    attrs: jnp.ndarray      # int32[ns, P]
    block_max: jnp.ndarray  # int32[ns, P//BLOCK]
    doc_site: jnp.ndarray   # int32[ns, nd_pad]


def build_sharded_index(
    corpus: Corpus, ns: int, *, include_site_terms: bool = True
) -> tuple[ShardedIndex, IndexMeta]:
    parts = partition_corpus(corpus, ns)
    built = [_build_numpy(p, include_site_terms) for p in parts]
    metas = [m for _, m in built]
    arrays = [a for a, _ in built]

    def stack(key: str, pad_value) -> np.ndarray:
        ms = [a[key] for a in arrays]
        width = max(m.shape[0] for m in ms)
        # keep the per-shard alignment of the padded width: postings/attrs
        # stay TILE-aligned (the streaming kernels read them tile-wise;
        # every shard keeps >= its own spare INVALID tile — see
        # flat_tile_pad — since stacking only ever widens the padding).
        if key in ("postings", "attrs"):
            # lint: allow(flat-pad) — widening an already-flat_tile_pad'ed
            # shard can only grow its spare-tile slack, never shrink it
            width = ((width + TILE - 1) // TILE) * TILE
        elif key == "doc_site":
            width = ((width + BLOCK - 1) // BLOCK) * BLOCK
        out = np.full((ns, width), pad_value, dtype=ms[0].dtype)
        for i, m in enumerate(ms):
            out[i, : m.shape[0]] = m
        return out

    sharded = ShardedIndex(
        offsets=jnp.asarray(stack("offsets", 0)),
        lengths=jnp.asarray(stack("lengths", 0)),
        postings=jnp.asarray(stack("postings", INVALID_DOC)),
        attrs=jnp.asarray(stack("attrs", INVALID_ATTR)),
        block_max=jnp.asarray(stack("block_max", INVALID_DOC)),
        doc_site=jnp.asarray(stack("doc_site", INVALID_ATTR)),
    )
    meta = IndexMeta(
        n_docs=corpus.n_docs,
        vocab_size=corpus.vocab_size,
        n_sites=corpus.n_sites,
        n_terms=metas[0].n_terms,
        include_site_terms=include_site_terms,
    )
    return sharded, meta


def local_to_global_docids(local: jnp.ndarray, shard: jnp.ndarray, ns: int):
    """Invert the striping map; INVALID stays INVALID."""
    g = local * ns + shard
    return jnp.where(local == INVALID_DOC, INVALID_DOC, g.astype(jnp.int32))
