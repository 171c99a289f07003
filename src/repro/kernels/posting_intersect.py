"""Pallas TPU kernel: ZigZag posting-list intersection with block skipping.

Paper mechanism (§2, Fig 4(a)): when joining posting lists, the *sub-index*
lets the engine skip the parts of a list that cannot contain matches.

TPU adaptation (DESIGN.md §2): the unit of skippable I/O is a VMEM tile of
``TILE = 1024`` postings (8 sublanes x 128 lanes).  For each driver-list
(A) tile we precompute — from the skip table, *outside* the kernel — the
contiguous range of B tiles whose [min,max] docID span overlaps the A
tile's span.  The kernel's grid is (num_a_tiles, s_max); the B-tile
BlockSpec index_map reads the per-A-tile start from scalar-prefetched SMEM,
so **skipped B tiles are never DMA'd from HBM** (out-of-range steps remap
to an already-resident tile, which Pallas elides).  That is posting
skipping, with HBM->VMEM DMAs playing the role of disk reads.

The membership test itself is a broadcast-compare: each A tile (8,128) is
compared against the B tile one 128-lane row at a time — eight (8,128,128)
vector compares, the VPU-friendly formulation of "is a in b" (sorted merge
would be scalar/branchy; TPUs want dense regular compares).

The embedded-attribute predicate of a limited search (Fig 4(b)) is fused:
the attrs stream rides in a sibling BlockSpec and is applied in the same
pass — the paper's "one sequential scan of the posting list".
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.index import (
    BLOCK,
    DOC_DEAD,
    DOC_SUPERSEDED,
    INVALID_ATTR,
    INVALID_DOC,
    TILE,
    PackedFlatArrays,
)
from repro.kernels import bitonic
from repro.kernels.worklist import (
    FLAG_FIRST,
    FLAG_LAST,
    FLAG_TERM_END,
    FLAG_TERM_START,
    build_intersect_worklist,
)

TILE_ROWS = 8
LANES = 128
#: Rows of an Element-indexed window read: two sublane tiles.  A driver
#: window starts on a BLOCK (one-row) boundary, but Mosaic only accepts
#: Element offsets it can prove are multiples of the 8-row sublane tile,
#: so the kernels read the two aligned tiles covering the TILE_ROWS rows
#: they need and realign them in VMEM (see :func:`window_read`).
WINDOW_READ_ROWS = 2 * TILE_ROWS
# One skippable tile = 1024 postings; the flat arrays are padded to this in
# core.index, so tile addressing and padding cannot desynchronize.
assert TILE == TILE_ROWS * LANES
_NEG = np.int32(-(2**31))  # below every docID; span sentinel


def _tile_member(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(8,128) membership of A-tile entries in the B tile: eight
    (8,128,128) broadcast compares — the VPU-friendly formulation of
    "is a in b" (sorted merge would be scalar/branchy)."""
    m = jnp.zeros(a.shape, dtype=jnp.bool_)
    for r in range(TILE_ROWS):
        row = b[r, :]
        m = m | jnp.any(a[:, :, None] == row[None, None, :], axis=-1)
    return m


def _fused_keep(a, a_attr, attr_filter, enabled, live=None) -> jnp.ndarray:
    """Validity + embedded-attribute predicate — plus, when ``live`` is
    given, the online-update tombstone predicate (repro.indexing): a
    posting whose document was deleted (or superseded by a delta version)
    arrives with live=0 and dies here.  All fused in one pass — the
    paper's "one sequential scan of the posting list" (Fig 4(b))."""
    # Boolean algebra, not a select over booleans: Mosaic cannot lower
    # ``arith.select`` on i1 vectors.
    valid = a != INVALID_DOC
    attr_ok = a_attr == attr_filter
    keep = valid & (attr_ok | ~enabled)
    if live is not None:
        keep = keep & (live != 0)
    return keep.astype(jnp.int32)


def _clamp_s_max(s_max: int | None, num_b: int) -> int:
    if s_max is None:
        s_max = num_b
    return max(1, min(s_max, num_b))


def _intersect_kernel(
    # scalar-prefetch (SMEM):
    b_start_ref,    # int32[num_a]  first overlapping B tile per A tile
    n_b_ref,        # int32[num_a]  number of overlapping B tiles
    attr_ref,       # int32[2]      [attr_filter, attr_enabled]
    # VMEM:
    a_ref,          # (8,128) A docids
    a_attr_ref,     # (8,128) A embedded attrs
    b_ref,          # (8,128) current B tile
    out_ref,        # (8,128) int32 mask (accumulated over j)
    *,
    s_max: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Posting skipping: only the precomputed overlap range does work.
    @pl.when(j < n_b_ref[i])
    def _compare():
        m = _tile_member(a_ref[...], b_ref[...])
        out_ref[...] = out_ref[...] | m.astype(jnp.int32)

    # Final step: fuse validity + embedded-attribute predicate (one pass).
    @pl.when(j == s_max - 1)
    def _finalize():
        keep = _fused_keep(
            a_ref[...], a_attr_ref[...], attr_ref[0], attr_ref[1] != 0
        )
        out_ref[...] = out_ref[...] * keep


def _pad_to_tile(x: jnp.ndarray, fill) -> jnp.ndarray:
    n = x.shape[-1]
    pad = (-n) % TILE
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], constant_values=fill)
    return x


# ---------------------------------------------------------------------------
# BlockSpec index maps — module-level so the contract checker
# (repro.analysis, via the registry at the bottom of this file) evaluates
# the exact same code the pallas_calls run, never a re-derivation.
# ---------------------------------------------------------------------------


def _ibs_a_map(i, j, *_):
    return (i, 0)


def _ibs_b_map(num_b):
    def b_map(i, j, b_start_ref, n_b_ref, attr_ref):
        # Out-of-range steps remap to the last in-range tile: the block is
        # already resident, so Pallas skips the DMA — the "skip" is free.
        jj = jnp.minimum(j, jnp.maximum(n_b_ref[i] - 1, 0))
        return (jnp.minimum(b_start_ref[i] + jj, num_b - 1), 0)

    return b_map


def _batched_a_map(q, i, t, j, *_):
    return (q, i, 0)


def _batched_b_map(num_b):
    def b_map(q, i, t, j, b_start_ref, n_b_ref, active_ref, attr_ref):
        # Out-of-range steps remap to an already-resident tile, so Pallas
        # elides the DMA — the "skip" is free.  Zero-tile slots (inactive
        # or no overlap) pin to block (q,0,0) regardless of t: consecutive
        # inert steps then map to the same block and coalesce instead of
        # pulling one fresh tile per (A-tile, slot).
        nb = n_b_ref[q, t, i]
        jj = jnp.minimum(j, jnp.maximum(nb - 1, 0))
        tt = jnp.where(nb == 0, 0, t)
        bb = jnp.where(
            nb == 0, 0, jnp.minimum(b_start_ref[q, t, i] + jj, num_b - 1)
        )
        return (q, tt, bb, 0)

    return b_map


def _streamed_flat_map(start_idx, n_idx, num_tiles):
    """Flat-array tile walk at the scalar-prefetched per-(q, t, i) range;
    ``start_idx``/``n_idx`` address the range arrays in the prefetch refs."""

    def b_map(q, i, t, j, *refs):
        # Out-of-range steps remap to an already-resident tile (DMA
        # elided); zero-tile slots pin to tile 0 so consecutive inert
        # steps coalesce.
        nb = refs[n_idx][q, t, i]
        jj = jnp.minimum(j, jnp.maximum(nb - 1, 0))
        tile = jnp.minimum(refs[start_idx][q, t, i] + jj, num_tiles - 1)
        return (jnp.where(nb == 0, 0, tile), 0)

    return b_map


def window_read(row, rows_total: int):
    """``(start, shift)`` of the aligned read that covers rows ``[row, row +
    TILE_ROWS)`` of a ``(rows_total, 128)`` flat array.

    ``start`` is a multiple of TILE_ROWS (provably so for Mosaic: a floor
    division times the tile) and ``start + WINDOW_READ_ROWS <= rows_total``;
    the kernel takes ``block[shift:shift + TILE_ROWS]``.  A row in the
    array's last tile clamps to the read ending at the array edge with
    ``shift = TILE_ROWS``: it then delivers the spare INVALID tile
    (:func:`repro.core.index.flat_tile_pad`), and every position it
    covers is past the live range, which the kernels' position masks
    discard anyway.
    """
    start = (
        jnp.minimum(row, rows_total - TILE_ROWS - 1) // TILE_ROWS
    ) * TILE_ROWS
    return start, jnp.minimum(row - start, TILE_ROWS)


def _driver_window_map(rows_total, info_idx):
    """Aligned Element read covering driver tile i: the per-query window
    start rides in prefetch ref ``info_idx`` as ``[row0, n_eff]`` rows."""

    def ad_map(q, i, t, j, *refs):
        row = refs[info_idx][q, 0] + i * TILE_ROWS
        return (window_read(row, rows_total)[0], 0)

    return ad_map


def element_spec(rows, index_map):
    """``(rows, 128)`` Element read of a flat array at the row offset
    ``index_map`` returns (a multiple of TILE_ROWS, see :func:`window_read`)."""
    return pl.BlockSpec((pl.Element(rows), pl.Element(LANES)), index_map)


def _driver_out_map(q, i, t, j, *refs):
    return (q, i, 0)


# ---------------------------------------------------------------------------
# Block-codec decode (core.index.PackedFlatArrays): packed HBM words are
# DMA'd as (chunk_rows, 128) word chunks and expanded to raw int32 docIDs
# right here, in VMEM — on a packed stream HBM never serves a raw posting.
# ---------------------------------------------------------------------------


def _packed_row0(woff_ref, b0c, rows_w: int, chunk_rows: int):
    """First word row of the chunk covering block ``b0c``'s packed words:
    the row holding its first word, rounded down to the sublane tile
    (``pack_flat_postings`` sizes ``chunk_rows`` from such aligned starts).

    The edge clamp mirrors the raw maps' pattern but is provably inert:
    ``packed_word_pad`` keeps >= chunk_rows*BLOCK + TILE zero words past
    the live words, so ``woff[b0c] // LANES <= rows_w - chunk_rows`` for
    every descriptor-clamped ``b0c`` — the packed-space spare-tile
    invariant the contract checker verifies.
    """
    row = jnp.minimum(woff_ref[b0c] // LANES, rows_w - chunk_rows)
    return (row // TILE_ROWS) * TILE_ROWS


def _decode_block(chunk, base, meta, rel):
    """One BLOCK's packed gap fields -> (1, 128) raw docIDs.

    ``chunk`` is the resident (chunk_rows, 128) word chunk, ``rel`` the
    block's first word's flat index inside it.  Lane l's w-bit field sits
    at word ``(l*w) >> 5``, shift ``(l*w) & 31`` (widths divide 32, so no
    field straddles a word boundary); the per-lane word gather is a
    one-hot select-and-sum — the VPU formulation, since VMEM has no
    scalar gather.  A block packs at most 128 words, which never span
    more than two consecutive 128-word rows, so the one-hot runs over
    that row pair (256 words) rather than the whole chunk — the decode
    cost is then independent of ``chunk_rows``.  A padding descriptor
    (meta == 0 => cnt == 0) masks every lane to INVALID, so a clamped or
    stale chunk can never decode into live-looking postings; an
    out-of-window ``rel`` matches nothing and sums to zero, which the
    same mask discards.
    """
    w = meta & 63
    cnt = meta >> 6
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    idx = rel + ((lane * w) >> 5)
    rows = chunk.shape[0]
    # the row pair holding this block's words (chunk_rows is always >= 8;
    # a live block starting in the last row also fits entirely in it, so
    # the clamp only shifts the window start, never drops live words)
    r0b = jnp.minimum(rel >> 7, rows - 2)
    fr = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    top = jnp.sum(jnp.where(fr == r0b, chunk, 0), axis=0)
    bot = jnp.sum(jnp.where(fr == r0b + 1, chunk, 0), axis=0)
    window = jnp.concatenate([top, bot])[:, None]          # (256, 1)
    idx2 = idx - r0b * LANES                               # window-relative
    wid = jax.lax.broadcasted_iota(jnp.int32, (2 * LANES, LANES), 0)
    lane_word = jnp.sum(jnp.where(wid == idx2, window, 0), axis=0)
    shift = (lane * w) & 31
    mask = jnp.where(
        w >= 32, jnp.int32(-1), (jnp.int32(1) << jnp.minimum(w, 31)) - 1
    )
    # logical shift: a 32-bit field may have the sign bit set.
    gaps = jax.lax.shift_right_logical(lane_word[None, :], shift) & mask
    docs = base + bitonic.lane_prefix_sum(gaps)
    return jnp.where(lane < cnt, docs, INVALID_DOC)


def _decode_span(chunk, base_ref, meta_ref, woff_ref, b0c, row0, n_span: int):
    """Decode ``n_span`` consecutive blocks (statically unrolled) from one
    resident word chunk into an (n_span, 128) raw docID tile.

    ``b0c`` is the (descriptor-clamped) first block, ``row0`` the chunk's
    first word row.  Descriptor refs live in SMEM and tolerate reads up to
    DESC_PAD blocks past the live block range — padding descriptors decode
    to all-INVALID rows, exactly what the raw layout's INVALID fill reads.
    """
    out = []
    for k in range(n_span):
        bk = b0c + k
        rel = woff_ref[bk] - row0 * LANES
        out.append(_decode_block(chunk, base_ref[bk], meta_ref[bk], rel))
    return jnp.concatenate(out, axis=0)


def _packed_flat_map(start_idx, n_idx, woff_idx, n_blocks, rows_w, chunk_rows):
    """Packed twin of :func:`_streamed_flat_map`: walks the *word* chunks
    holding the probe tiles' blocks.  Same skip/coalesce behavior (inert
    steps pin to block 0's chunk); the kernel recomputes the identical
    b0c/row0 so the decoded tile always matches the chunk this map DMA'd.
    """

    def b_map(q, i, t, j, *refs):
        nb = refs[n_idx][q, t, i]
        jj = jnp.minimum(j, jnp.maximum(nb - 1, 0))
        tile = jnp.where(nb == 0, 0, refs[start_idx][q, t, i] + jj)
        b0c = jnp.minimum(tile * (TILE // BLOCK), n_blocks)
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return b_map


def _packed_driver_map(info_idx, woff_idx, n_blocks, rows_w, chunk_rows):
    """Packed twin of :func:`_driver_window_map`: the word chunk holding
    driver tile i's blocks (``a_info[q, 0]`` is the window's first block —
    BLOCK-aligned list offsets make row and block indices coincide)."""

    def ad_map(q, i, t, j, *refs):
        b0c = jnp.minimum(
            refs[info_idx][q, 0] + i * (TILE // BLOCK), n_blocks
        )
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return ad_map


def compute_skip_map(
    a_docs: jnp.ndarray, b_docs: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-A-tile (b_start, n_b) overlap ranges from the skip tables.

    This is the sub-index lookup of the paper: tile spans are the skip
    table; searchsorted over them decides which B tiles can join at all.
    """
    at = a_docs.reshape(-1, TILE)
    bt = b_docs.reshape(-1, TILE)

    a_valid = at != INVALID_DOC
    a_min = at[:, 0]
    a_max = jnp.max(jnp.where(a_valid, at, -1), axis=1)
    a_any = jnp.any(a_valid, axis=1)

    b_valid = bt != INVALID_DOC
    b_min = bt[:, 0]
    b_max_v = jnp.max(jnp.where(b_valid, bt, -1), axis=1)
    b_any = jnp.any(b_valid, axis=1)
    # Keep spans monotone: all-pad tiles sit at the end with span [INVALID,INVALID].
    b_max = jnp.where(b_any, b_max_v, INVALID_DOC)

    start = jnp.searchsorted(b_max, a_min, side="left").astype(jnp.int32)
    end = jnp.searchsorted(b_min, a_max, side="right").astype(jnp.int32)
    start = jnp.minimum(start, bt.shape[0])
    n_b = jnp.clip(end - start, 0, bt.shape[0]).astype(jnp.int32)
    n_b = jnp.where(a_any, n_b, 0)
    return start, n_b


@functools.partial(jax.jit, static_argnames=("s_max", "interpret"))
def intersect_block_skip(
    a_docs: jnp.ndarray,
    a_attrs: jnp.ndarray,
    b_docs: jnp.ndarray,
    attr_filter: jnp.ndarray | int = -1,
    *,
    s_max: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Membership mask of a_docs in b_docs (+fused attr predicate).

    Returns int32[len(a_docs)] in {0,1}.  Matches
    :func:`repro.kernels.ref.intersect_mask_ref`.
    """
    n_a = a_docs.shape[0]
    a = _pad_to_tile(a_docs, INVALID_DOC)
    aa = _pad_to_tile(a_attrs, -1)
    b = _pad_to_tile(b_docs, INVALID_DOC)
    num_a = a.shape[0] // TILE
    num_b = b.shape[0] // TILE
    s_max = _clamp_s_max(s_max, num_b)

    b_start, n_b = compute_skip_map(a, b)
    n_b = jnp.minimum(n_b, s_max)  # cap (perf experiments); default = exact
    attr_params = jnp.array(
        [jnp.asarray(attr_filter), jnp.asarray(attr_filter) >= 0], dtype=jnp.int32
    )

    a2 = a.reshape(num_a * TILE_ROWS, LANES)
    aa2 = aa.reshape(num_a * TILE_ROWS, LANES)
    b2 = b.reshape(num_b * TILE_ROWS, LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_a, s_max),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, LANES), _ibs_a_map),
            pl.BlockSpec((TILE_ROWS, LANES), _ibs_a_map),
            pl.BlockSpec((TILE_ROWS, LANES), _ibs_b_map(num_b)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, LANES), _ibs_a_map),
    )
    out = pl.pallas_call(
        functools.partial(_intersect_kernel, s_max=s_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_a * TILE_ROWS, LANES), jnp.int32),
        interpret=interpret,
    )(b_start, n_b, attr_params, a2, aa2, b2)
    return out.reshape(-1)[:n_a]


# ---------------------------------------------------------------------------
# Batched multi-query / multi-term variant (the engine's hot path)
# ---------------------------------------------------------------------------

def _intersect_batched_kernel(
    # scalar-prefetch (SMEM):
    b_start_ref,    # int32[Q, T, num_a]  first overlapping B tile per A tile
    n_b_ref,        # int32[Q, T, num_a]  overlapping B tiles (0 = term inert)
    active_ref,     # int32[Q, T]         1 iff term slot t joins query q
    attr_ref,       # int32[Q, 2]         [attr_filter, attr_enabled] per query
    # VMEM:
    a_ref,          # (1,8,128)   driver-window docids of query q, tile i
    a_attr_ref,     # (1,8,128)   driver attribute stream (embed or gathered)
    a_live_ref,     # (1,8,128)   driver tombstone stream (0 = dead posting)
    b_ref,          # (1,1,8,128) current other-term tile
    out_ref,        # (1,8,128)   int32 final mask (AND over terms)
    member_ref,     # (8,128)     int32 scratch: per-term OR accumulator
    *,
    t_slots: int,
    s_max: int,
):
    q = pl.program_id(0)
    i = pl.program_id(1)
    t = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when((t == 0) & (j == 0))
    def _init_out():
        # ZigZag AND-fold starts all-pass; inactive slots keep it that way,
        # so a single-keyword query degrades to validity + attr predicate.
        out_ref[...] = jnp.ones_like(out_ref)

    @pl.when(j == 0)
    def _init_member():
        member_ref[...] = jnp.zeros_like(member_ref)

    # Posting skipping: only the precomputed overlap range does compares
    # (n_b is pre-zeroed for inactive slots, so they are inert here, and
    # on TPU only overlapping tiles are ever DMA'd — see b_map below).
    @pl.when(j < n_b_ref[q, t, i])
    def _compare():
        m = _tile_member(a_ref[0], b_ref[0, 0])
        member_ref[...] = member_ref[...] | m.astype(jnp.int32)

    # End of this term's B sweep: AND the term's membership into the mask.
    @pl.when(j == s_max - 1)
    def _fold_term():
        active = active_ref[q, t] != 0
        term_ok = jnp.where(active, member_ref[...], 1)
        out_ref[0] = out_ref[0] * term_ok

    # Last term slot: fuse validity + attribute + tombstone predicates.
    @pl.when((t == t_slots - 1) & (j == s_max - 1))
    def _finalize():
        keep = _fused_keep(
            a_ref[0], a_attr_ref[0], attr_ref[q, 0], attr_ref[q, 1] != 0,
            live=a_live_ref[0],
        )
        out_ref[0] = out_ref[0] * keep


@functools.partial(jax.jit, static_argnames=("s_max", "interpret"))
def intersect_batched_block_skip(
    a_docs: jnp.ndarray,       # int32[Q, W]    driver windows
    a_attrs: jnp.ndarray,      # int32[Q, W]    driver attribute streams
    b_docs: jnp.ndarray,       # int32[Q, T, W] other-term windows
    active: jnp.ndarray,       # int32[Q, T]    1 iff slot t joins query q
    attr_filter: jnp.ndarray,  # int32[Q]       NO_ATTR(-1) = unrestricted
    *,
    a_live: jnp.ndarray | None = None,  # int32[Q, W] tombstone stream; None = all live
    s_max: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched ZigZag join: mask of each query's driver postings that occur
    in *every* active other-term window, fused with the per-query embedded-
    attribute predicate, validity, and — when ``a_live`` is given — the
    online-update tombstone predicate (a deleted/superseded posting carries
    live=0 and is filtered in the same finalize pass, so the merge-on-read
    path never needs a separate host-side masking sweep over the driver).
    Returns int32[Q, W] in {0,1}.

    One ``pallas_call`` serves the whole query batch: grid
    ``(Q, num_a_tiles, T, s_max)``, with per-(query, term, A-tile) skip
    ranges scalar-prefetched so non-overlapping B tiles are never DMA'd.
    """
    q_n, n_a = a_docs.shape
    t_slots = b_docs.shape[1]
    if a_live is None:
        a_live = jnp.ones_like(a_docs)
    a = _pad_to_tile(a_docs, INVALID_DOC)
    aa = _pad_to_tile(a_attrs, -1)
    al = _pad_to_tile(a_live.astype(jnp.int32), 0)
    b = _pad_to_tile(b_docs, INVALID_DOC)
    num_a = a.shape[1] // TILE
    num_b = b.shape[2] // TILE
    s_max = _clamp_s_max(s_max, num_b)

    # Skip maps per (query, term) pair; inactive slots get zero tiles so
    # they cost neither compares nor DMAs.  The inner in_axes=None keeps a
    # single copy of each driver window across its term slots.
    b_start, n_b = jax.vmap(
        jax.vmap(compute_skip_map, in_axes=(None, 0))
    )(a, b)
    n_b = jnp.minimum(n_b, s_max)
    active = active.astype(jnp.int32)
    n_b = n_b * active[:, :, None]
    attr_params = jnp.stack(
        [attr_filter.astype(jnp.int32), (attr_filter >= 0).astype(jnp.int32)],
        axis=-1,
    )

    a2 = a.reshape(q_n, num_a * TILE_ROWS, LANES)
    aa2 = aa.reshape(q_n, num_a * TILE_ROWS, LANES)
    al2 = al.reshape(q_n, num_a * TILE_ROWS, LANES)
    b2 = b.reshape(q_n, t_slots, num_b * TILE_ROWS, LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(q_n, num_a, t_slots, s_max),
        in_specs=[
            pl.BlockSpec((1, TILE_ROWS, LANES), _batched_a_map),
            pl.BlockSpec((1, TILE_ROWS, LANES), _batched_a_map),
            pl.BlockSpec((1, TILE_ROWS, LANES), _batched_a_map),
            pl.BlockSpec((1, 1, TILE_ROWS, LANES), _batched_b_map(num_b)),
        ],
        out_specs=pl.BlockSpec((1, TILE_ROWS, LANES), _batched_a_map),
        scratch_shapes=[pltpu.VMEM((TILE_ROWS, LANES), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _intersect_batched_kernel, t_slots=t_slots, s_max=s_max
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (q_n, num_a * TILE_ROWS, LANES), jnp.int32
        ),
        interpret=interpret,
    )(b_start, n_b, active, attr_params, a2, aa2, al2, b2)
    return out.reshape(q_n, -1)[:, :n_a]


# ---------------------------------------------------------------------------
# Streamed variant: other-term windows read straight from the flat index
# ---------------------------------------------------------------------------
#
# The batched kernel above takes a pre-gathered (Q, T, W) other-term operand
# — a per-batch HBM staging buffer the paper's cost model has no term for
# (postings are supposed to stream off storage once).  The streamed variant
# removes it: the B operand *is* the index's flat posting array, and the
# BlockSpec index map walks per-(query, term) tile ranges computed from the
# skip table and scalar-prefetched into SMEM.  A tile holds whatever 1024
# physical postings surround the list (lists are BLOCK-aligned, tiles are
# 8xBLOCK), so the kernel range-masks each tile to the term's logical
# window [offset, offset + min(len, window)) before the membership compare.
#
# Merge-on-read needs no merged other-term windows at all: membership in
# the *logical* (merged) list is membership in the main list OR the delta
# list, each probed against its own flat array in the same grid sweep, with
# the driver posting's tombstone flags deciding which probe may count (a
# superseded doc's main postings are dead everywhere, so only its delta
# occurrences join).  That turns the per-(query, term) host-side merge sort
# of the old path into two streaming probes over the physical structures.


def window_tile_spans(
    block_max: jnp.ndarray, off: jnp.ndarray, n_eff: jnp.ndarray,
    *, s_tiles: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Physical-tile spans of the logical window [off, off+n_eff), from the
    BLOCK skip table.

    Returns ``(tile0, n_tiles, tile_min[s_tiles], tile_max[s_tiles])``:
    tile0 is the first TILE-aligned tile touching the window, n_tiles how
    many tiles the window spans, and tile_min/tile_max conservative span
    surrogates per tile (ascending, INVALID-filled past the window) — a
    tile whose span cannot overlap a driver tile is *skipped* (never
    DMA'd).  tile_min[s] is the previous tile's max (postings ascend inside
    a list, so it lower-bounds the true min); a partially-filled final
    block may report INVALID_DOC (the main index's raw skip table) which
    only widens the span — skipping stays conservative either way.
    """
    bpt = TILE // BLOCK
    hi = off + n_eff
    tile0 = off // TILE
    n_tiles = jnp.where(n_eff > 0, (hi + TILE - 1) // TILE - tile0, 0)
    blk = (
        (tile0 + jnp.arange(s_tiles, dtype=jnp.int32))[:, None] * bpt
        + jnp.arange(bpt, dtype=jnp.int32)[None, :]
    )
    blo = off // BLOCK
    bhi = (hi + BLOCK - 1) // BLOCK
    inside = (blk >= blo) & (blk < bhi)
    bm = jnp.take(block_max, blk, mode="fill", fill_value=INVALID_DOC)
    tmax = jnp.max(jnp.where(inside, bm, _NEG), axis=1)
    any_inside = jnp.any(inside, axis=1)
    tile_max = jnp.where(any_inside, tmax, INVALID_DOC)
    tile_min = jnp.concatenate([jnp.full((1,), _NEG), tile_max[:-1]])
    return tile0, n_tiles, tile_min, tile_max


def _a_tile_spans(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-driver-tile (min, max, any_valid) over TILE-padded (Q, W) docs."""
    at = a.reshape(a.shape[0], -1, TILE)
    valid = at != INVALID_DOC
    a_min = at[:, :, 0]
    a_max = jnp.max(jnp.where(valid, at, -1), axis=2)
    a_any = jnp.any(valid, axis=2)
    return a_min, a_max, a_any


def driver_tile_spans(
    block_max: jnp.ndarray, off: jnp.ndarray, n_eff: jnp.ndarray,
    *, s_tiles: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Driver-side analogue of :func:`_a_tile_spans`, from the BLOCK skip
    table instead of a materialized window: (a_min, a_max, a_any) for the
    *window-aligned* driver tiles ``[off + i*TILE, off + (i+1)*TILE)``.

    ``off`` is BLOCK-aligned (every list start is), so each window tile
    covers exactly ``TILE/BLOCK`` skip-table blocks.  a_max is the max of
    the live blocks' ``block_max`` — an upper bound (the list's final
    partial block reports INVALID_DOC in the main index's raw table, which
    only widens the probe range).  a_min is the previous tile's a_max, a
    lower bound since postings ascend within a list; an INVALID a_max can
    only leak into the span of a tile *past* the live range, whose a_any
    is False and whose probe plan is therefore inert.  Conservative spans
    scan at most a few extra B tiles; they can never skip a match.
    """
    bpt = TILE // BLOCK
    blk0 = off // BLOCK
    n_live_blk = (n_eff + BLOCK - 1) // BLOCK
    rel = (
        jnp.arange(s_tiles, dtype=jnp.int32)[:, None] * bpt
        + jnp.arange(bpt, dtype=jnp.int32)[None, :]
    )
    inside = rel < n_live_blk
    bm = jnp.take(block_max, blk0 + rel, mode="fill", fill_value=INVALID_DOC)
    tmax = jnp.max(jnp.where(inside, bm, _NEG), axis=1)
    a_any = jnp.any(inside, axis=1)
    a_max = jnp.where(a_any, tmax, -1)
    a_min = jnp.concatenate([jnp.full((1,), _NEG), a_max[:-1]])
    return a_min, a_max, a_any


def _probe_plan(
    a_spans,                   # (a_min, a_max, a_any), each (Q, num_a_tiles)
    terms: jnp.ndarray,        # (Q, T)
    offsets: jnp.ndarray, lengths: jnp.ndarray, block_max: jnp.ndarray,
    *, window: int, s_tiles: int,
):
    """Per-(query, term, driver-tile) streaming plan: (b_tile, n_b, bounds).

    b_tile is the first overlapping physical tile in the flat posting
    array, n_b how many consecutive tiles to stream, bounds the logical
    [lo, hi) posting range the kernel masks each tile to.  ``a_spans``
    supplies the driver tiles' docID spans — exact when the driver window
    is materialized (:func:`_a_tile_spans`), skip-table-derived when the
    driver streams too (:func:`driver_tile_spans`).
    """
    tt = jnp.clip(terms, 0, offsets.shape[0] - 1)
    off = jnp.take(offsets, tt)
    ln = jnp.where(terms < 0, 0, jnp.take(lengths, tt))
    n_eff = jnp.minimum(ln, window)
    tile0, n_tiles, tile_min, tile_max = jax.vmap(
        jax.vmap(functools.partial(window_tile_spans, block_max, s_tiles=s_tiles))
    )(off, n_eff)
    a_min, a_max, a_any = a_spans
    start = jax.vmap(
        jax.vmap(
            lambda tm, am: jnp.searchsorted(tm, am, side="left"),
            in_axes=(0, None),
        )
    )(tile_max, a_min).astype(jnp.int32)
    end = jax.vmap(
        jax.vmap(
            lambda tm, am: jnp.searchsorted(tm, am, side="right"),
            in_axes=(0, None),
        )
    )(tile_min, a_max).astype(jnp.int32)
    start = jnp.minimum(start, n_tiles[:, :, None])
    end = jnp.minimum(end, n_tiles[:, :, None])
    n_b = jnp.clip(end - start, 0, None) * a_any[:, None, :].astype(jnp.int32)
    b_tile = tile0[:, :, None] + start
    bounds = jnp.stack([off, off + n_eff], axis=-1)
    return b_tile, n_b, bounds


def _tile_positions(tile_id):
    """Global posting positions of one (8, 128) tile."""
    r = jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, LANES), 1)
    return tile_id * TILE + r * LANES + c


def _streamed_kernel(
    *refs, t_slots: int, s_max: int, has_delta: bool,
    packed_m=None, packed_d=None,
):
    # packed_m / packed_d: static (n_blocks, rows_w, chunk_rows) triples
    # when the corresponding stream is block-codec packed (the operand is
    # then a word chunk decoded below), None when it streams raw tiles.
    packed = packed_m is not None
    if has_delta:
        if packed:
            (bt_ref, nb_ref, mb_ref, dt_ref, nd_ref, db_ref, act_ref,
             attr_ref, mba_ref, mme_ref, mwo_ref, dba_ref, dme_ref, dwo_ref,
             a_ref, aa_ref, al_ref, af_ref, pm_ref, pd_ref,
             out_ref, mm_ref, md_ref) = refs
        else:
            (bt_ref, nb_ref, mb_ref, dt_ref, nd_ref, db_ref, act_ref,
             attr_ref, a_ref, aa_ref, al_ref, af_ref, pm_ref, pd_ref,
             out_ref, mm_ref, md_ref) = refs
    else:
        if packed:
            (bt_ref, nb_ref, mb_ref, act_ref, attr_ref,
             mba_ref, mme_ref, mwo_ref,
             a_ref, aa_ref, al_ref, pm_ref, out_ref, mm_ref) = refs
        else:
            (bt_ref, nb_ref, mb_ref, act_ref, attr_ref,
             a_ref, aa_ref, al_ref, pm_ref, out_ref, mm_ref) = refs
    q = pl.program_id(0)
    i = pl.program_id(1)
    t = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when((t == 0) & (j == 0))
    def _init_out():
        out_ref[...] = jnp.ones_like(out_ref)

    @pl.when(j == 0)
    def _init_members():
        mm_ref[...] = jnp.zeros_like(mm_ref)
        if has_delta:
            md_ref[...] = jnp.zeros_like(md_ref)

    def _probe(start_ref, n_ref, bounds_ref, tile_arr_ref, member_ref,
               desc=None):
        # Posting skipping: only tiles inside the precomputed overlap range
        # are compared (and, on TPU, DMA'd — see the index maps).  The tile
        # is range-masked to the term's logical window so postings of
        # neighboring lists sharing the tile can never produce a match.
        @pl.when(j < n_ref[q, t, i])
        def _():
            tile = start_ref[q, t, i] + j
            if desc is None:
                b = tile_arr_ref[...]
            else:
                # Packed stream: the operand is a word chunk; decode its
                # TILE/BLOCK blocks here, recomputing the index map's
                # exact b0c/row0 (j < n_b implies jj == j in the map).
                base_ref, meta_ref, woff_ref, (nbk, rows_w, cr) = desc
                b0c = jnp.minimum(tile * (TILE // BLOCK), nbk)
                row0 = _packed_row0(woff_ref, b0c, rows_w, cr)
                b = _decode_span(
                    tile_arr_ref[...], base_ref, meta_ref, woff_ref,
                    b0c, row0, TILE_ROWS,
                )
            pos = _tile_positions(tile)
            in_range = (pos >= bounds_ref[q, t, 0]) & (pos < bounds_ref[q, t, 1])
            b = jnp.where(in_range, b, INVALID_DOC)
            m = _tile_member(a_ref[0], b)
            member_ref[...] = member_ref[...] | m.astype(jnp.int32)

    _probe(bt_ref, nb_ref, mb_ref, pm_ref, mm_ref,
           desc=(mba_ref, mme_ref, mwo_ref, packed_m) if packed else None)
    if has_delta:
        _probe(dt_ref, nd_ref, db_ref, pd_ref, md_ref,
               desc=(dba_ref, dme_ref, dwo_ref, packed_d) if packed else None)

    # End of this term's sweep: AND the term's membership into the mask.
    @pl.when(j == s_max - 1)
    def _fold_term():
        active = act_ref[q, t] != 0
        if has_delta:
            # A driver posting joins the term's *logical* list if it occurs
            # in the main list (and its doc is neither deleted nor
            # superseded) or in the delta list (and its doc is not
            # deleted) — the merge-on-read semantics without materializing
            # a merged window.
            flags = af_ref[0]
            main_ok = (flags & jnp.int32(DOC_DEAD | DOC_SUPERSEDED)) == 0
            delta_ok = (flags & jnp.int32(DOC_DEAD)) == 0
            term_ok = (
                ((mm_ref[...] != 0) & main_ok)
                | ((md_ref[...] != 0) & delta_ok)
            ).astype(jnp.int32)
        else:
            term_ok = mm_ref[...]
        out_ref[0] = out_ref[0] * jnp.where(active, term_ok, 1)

    # Last term slot: fuse validity + attribute + tombstone predicates.
    @pl.when((t == t_slots - 1) & (j == s_max - 1))
    def _finalize():
        keep = _fused_keep(
            a_ref[0], aa_ref[0], attr_ref[q, 0], attr_ref[q, 1] != 0,
            live=al_ref[0],
        )
        out_ref[0] = out_ref[0] * keep


@functools.partial(jax.jit, static_argnames=("s_max", "interpret"))
def intersect_batched_streamed(
    a_docs: jnp.ndarray,       # int32[Q, W]  driver windows
    a_attrs: jnp.ndarray,      # int32[Q, W]  driver attribute streams
    a_live: jnp.ndarray,       # int32[Q, W]  driver tombstone stream
    terms: jnp.ndarray,        # int32[Q, T]  term ids per slot (NO_TERM pad)
    active: jnp.ndarray,       # int32[Q, T]  1 iff slot t joins query q
    attr_filter: jnp.ndarray,  # int32[Q]     NO_ATTR(-1) = unrestricted
    postings: jnp.ndarray,     # int32[P]     main flat postings (TILE-padded)
    offsets: jnp.ndarray, lengths: jnp.ndarray, block_max: jnp.ndarray,
    d_postings: jnp.ndarray | None = None,   # delta flat postings (TILE-pad)
    d_offsets: jnp.ndarray | None = None,
    d_lengths: jnp.ndarray | None = None,
    d_block_max: jnp.ndarray | None = None,
    a_flags: jnp.ndarray | None = None,      # int32[Q, W] driver doc_flags
    *,
    packed: PackedFlatArrays | None = None,    # block-codec main postings
    d_packed: PackedFlatArrays | None = None,  # block-codec delta postings
    s_max: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched ZigZag join with other-term windows streamed from the index.

    Same contract as :func:`intersect_batched_block_skip`, but the B
    operand is the index's flat posting array itself: per-(query, term,
    driver-tile) tile ranges — computed from the BLOCK skip table, not
    from gathered windows — are scalar-prefetched, and the BlockSpec index
    map walks them, so the ``(Q, T, W)`` staging gather disappears and
    non-overlapping tiles are never DMA'd.

    Passing the delta arrays (``d_*`` + ``a_flags``, all or none) turns on
    merge-on-read: each term is probed against main *and* delta streams
    and the driver posting's tombstone flags decide which probe counts.

    Passing ``packed`` (and ``d_packed`` whenever the delta arrays are
    given) switches the probe streams to the block codec: HBM serves
    (chunk_rows, 128) packed-word chunks instead of raw tiles, decoded in
    VMEM right after the DMA — same skip ranges, same results, ~3-4x
    fewer posting bytes moved.  Returns int32[Q, W] in {0, 1}.
    """
    has_delta = d_postings is not None
    use_packed = packed is not None
    if use_packed and has_delta and d_packed is None:
        raise ValueError("packed codec needs d_packed when delta arrays are given")
    q_n, n_a = a_docs.shape
    window = n_a
    t_slots = terms.shape[1]
    a = _pad_to_tile(a_docs, INVALID_DOC)
    aa = _pad_to_tile(a_attrs, -1)
    al = _pad_to_tile(a_live.astype(jnp.int32), 0)
    num_a = a.shape[1] // TILE
    assert postings.shape[0] % TILE == 0, "main postings must be TILE-padded"
    num_m = postings.shape[0] // TILE

    # A BLOCK-aligned list offset can straddle one more physical tile than
    # the window itself spans: ceil, not floor, or matches silently drop
    # for windows that are BLOCK- but not TILE-aligned.
    s_tiles_m = -(-window // TILE) + 1
    a_spans = _a_tile_spans(a)
    b_tile, n_b, bounds_m = _probe_plan(
        a_spans, terms, offsets, lengths, block_max,
        window=window, s_tiles=s_tiles_m,
    )
    s_grid = _clamp_s_max(s_max, s_tiles_m)
    n_b = jnp.minimum(n_b, s_grid) * active[:, :, None]

    active = active.astype(jnp.int32)
    attr_params = jnp.stack(
        [attr_filter.astype(jnp.int32), (attr_filter >= 0).astype(jnp.int32)],
        axis=-1,
    )
    a2 = a.reshape(q_n, num_a * TILE_ROWS, LANES)
    aa2 = aa.reshape(q_n, num_a * TILE_ROWS, LANES)
    al2 = al.reshape(q_n, num_a * TILE_ROWS, LANES)
    pm2 = postings.reshape(num_m * TILE_ROWS, LANES)

    scalars = [b_tile, n_b, bounds_m]
    operands = [a2, aa2, al2]
    if has_delta:
        assert d_postings.shape[0] % TILE == 0, "delta must be TILE-padded"
        num_d = d_postings.shape[0] // TILE
        cap = d_block_max.shape[0] * BLOCK // d_offsets.shape[0]
        s_tiles_d = -(-cap // TILE) + 1
        d_tile, n_d, bounds_d = _probe_plan(
            a_spans, terms, d_offsets, d_lengths, d_block_max,
            window=cap, s_tiles=s_tiles_d,
        )
        s_grid = max(s_grid, _clamp_s_max(s_max, s_tiles_d))
        n_d = jnp.minimum(n_d, s_grid) * active[:, :, None]
        scalars += [d_tile, n_d, bounds_d]
        af2 = _pad_to_tile(a_flags.astype(jnp.int32), 0).reshape(
            q_n, num_a * TILE_ROWS, LANES
        )
        operands.append(af2)
        pd2 = d_postings.reshape(num_d * TILE_ROWS, LANES)
    scalars += [active, attr_params]
    # Block-codec descriptors append at the END of the prefetch list so
    # every raw-mode scalar keeps its ref index in the maps and kernel.
    pk_m = pk_d = None
    if use_packed:
        woff_m_idx = len(scalars) + 2
        scalars += [packed.blk_base, packed.blk_meta, packed.blk_woff]
        if has_delta:
            woff_d_idx = len(scalars) + 2
            scalars += [d_packed.blk_base, d_packed.blk_meta, d_packed.blk_woff]
    n_scalars = len(scalars)

    in_specs = [
        pl.BlockSpec((1, TILE_ROWS, LANES), _batched_a_map) for _ in operands
    ]
    if use_packed:
        words_m = packed.words.reshape(-1, LANES)
        pk_m = (packed.n_blocks, words_m.shape[0], packed.chunk_rows)
        in_specs.append(
            element_spec(
                packed.chunk_rows, _packed_flat_map(0, 1, woff_m_idx, *pk_m)
            )
        )
        operands.append(words_m)
    else:
        in_specs.append(
            pl.BlockSpec((TILE_ROWS, LANES), _streamed_flat_map(0, 1, num_m))
        )
        operands.append(pm2)
    scratch = [pltpu.VMEM((TILE_ROWS, LANES), jnp.int32)]
    if has_delta:
        if use_packed:
            words_d = d_packed.words.reshape(-1, LANES)
            pk_d = (d_packed.n_blocks, words_d.shape[0], d_packed.chunk_rows)
            in_specs.append(
                element_spec(
                    d_packed.chunk_rows,
                    _packed_flat_map(3, 4, woff_d_idx, *pk_d),
                )
            )
            operands.append(words_d)
        else:
            in_specs.append(
                pl.BlockSpec((TILE_ROWS, LANES), _streamed_flat_map(3, 4, num_d))
            )
            operands.append(pd2)
        scratch.append(pltpu.VMEM((TILE_ROWS, LANES), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(q_n, num_a, t_slots, s_grid),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TILE_ROWS, LANES), _batched_a_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _streamed_kernel, t_slots=t_slots, s_max=s_grid,
            has_delta=has_delta, packed_m=pk_m, packed_d=pk_d,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (q_n, num_a * TILE_ROWS, LANES), jnp.int32
        ),
        interpret=interpret,
    )(*scalars, *operands)
    return out.reshape(q_n, -1)[:, :n_a]


# ---------------------------------------------------------------------------
# Fully-streamed variant: the DRIVER window also reads straight from the
# flat index — the last random access on the read path is gone
# ---------------------------------------------------------------------------
#
# intersect_batched_streamed still takes a materialized (Q, W) driver
# operand (under merge-on-read that window is the *product* of the delta
# merge kernel, so materializing it is the one buffer the join needs).  On
# the static main index, though, the driver window is just a contiguous
# BLOCK-aligned slice of the flat posting array — gathering it host-side is
# pure waste.  This variant reads driver tiles tile-by-tile from the flat
# ``postings``/``attrs`` arrays through Element-indexed BlockSpecs: the
# per-query window start (off // LANES sublane rows, scalar-prefetched)
# need not sit on a tile boundary, so each step reads the two aligned
# tiles covering its driver tile and takes the tile at the window's row
# shift in VMEM (:func:`window_read`).  Each driver tile is range-masked to the
# window's live range [0, n_eff) by its *intended* window position; the
# spare INVALID tile every flat array carries (core.index.flat_tile_pad)
# guarantees a tile whose read clamps at the array edge is entirely past
# the live range, so the mask discards everything a clamp could corrupt.
# The kernel emits the driver docIDs alongside the join mask — the
# (Q, window) driver materialization now happens exactly once, as kernel
# *output* (the candidate set top-k selects from), never as input staging.


def _driver_streamed_kernel(
    *refs, t_slots: int, s_max: int, rows_total: int, packed=None
):
    # Refs (raw mode), in order:
    #   scalar-prefetch (SMEM):
    #     bt_ref     int32[Q, T, num_a]  first overlapping B tile
    #     nb_ref     int32[Q, T, num_a]  B tiles to stream (0 = inert)
    #     mb_ref     int32[Q, T, 2]      logical [lo, hi) bounds per term
    #     act_ref    int32[Q, T]         1 iff slot t joins query q
    #     attr_ref   int32[Q, 2]         [attr_filter, attr_enabled]
    #     ainfo_ref  int32[Q, 2]         [driver row0, driver n_eff]
    #   VMEM:
    #     ad_ref     (16,128) aligned read covering driver tile i's docIDs
    #     aa_ref     (16,128) the same read of the attrs
    #     pm_ref     (8,128) current other-term tile
    #   outputs:
    #     outd_ref   (1,8,128) driver docIDs (window-aligned, INVALID past n_eff)
    #     outm_ref   (1,8,128) int32 final mask (AND over terms)
    #   scratch:
    #     mm_ref     (8,128) per-term OR accumulator
    # Packed mode (``packed`` = static (n_blocks, rows_w, chunk_rows)):
    # the main-postings descriptors (base, meta, woff) follow ainfo_ref in
    # SMEM; ad_ref/pm_ref become word chunks decoded below (attrs stay
    # raw); adk_ref, an extra (8,128) scratch, caches the decoded driver
    # tile across the (t, j) sweep of each (q, i).
    if packed is not None:
        (bt_ref, nb_ref, mb_ref, act_ref, attr_ref, ainfo_ref,
         mba_ref, mme_ref, mwo_ref,
         ad_ref, aa_ref, pm_ref, outd_ref, outm_ref,
         mm_ref, adk_ref) = refs
        nbk, rows_w, cr = packed
    else:
        (bt_ref, nb_ref, mb_ref, act_ref, attr_ref, ainfo_ref,
         ad_ref, aa_ref, pm_ref, outd_ref, outm_ref, mm_ref) = refs
    q = pl.program_id(0)
    i = pl.program_id(1)
    t = pl.program_id(2)
    j = pl.program_id(3)
    _, shift = window_read(ainfo_ref[q, 0] + i * TILE_ROWS, rows_total)

    if packed is not None:
        # Decode the driver tile once per (q, i) — (t, j) = (0, 0) is the
        # first grid step for every (q, i); the scratch persists across
        # the rest of the sweep like any accumulator.
        @pl.when((t == 0) & (j == 0))
        def _decode_driver():
            b0c = jnp.minimum(
                ainfo_ref[q, 0] + i * (TILE // BLOCK), nbk
            )
            row0 = _packed_row0(mwo_ref, b0c, rows_w, cr)
            adk_ref[...] = _decode_span(
                ad_ref[...], mba_ref, mme_ref, mwo_ref,
                b0c, row0, TILE_ROWS,
            )

        a_tile = adk_ref[...]
    else:
        a_tile = ad_ref[pl.ds(shift, TILE_ROWS), :]

    # The driver tile, masked by *intended* window position: slots at or
    # past n_eff read INVALID no matter what the (possibly clamped) DMA
    # delivered.  Tiles are window-aligned, so tile i holds window
    # positions [i*TILE, (i+1)*TILE).
    in_win = _tile_positions(i) < ainfo_ref[q, 1]
    a = jnp.where(in_win, a_tile, INVALID_DOC)

    @pl.when((t == 0) & (j == 0))
    def _init_out():
        outm_ref[...] = jnp.ones_like(outm_ref)
        outd_ref[0] = a

    @pl.when(j == 0)
    def _init_member():
        mm_ref[...] = jnp.zeros_like(mm_ref)

    # Posting skipping, as in intersect_batched_streamed: only tiles in
    # the precomputed overlap range are compared (or, on TPU, DMA'd).
    @pl.when(j < nb_ref[q, t, i])
    def _probe():
        tile = bt_ref[q, t, i] + j
        if packed is None:
            b = pm_ref[...]
        else:
            b0c = jnp.minimum(tile * (TILE // BLOCK), nbk)
            row0 = _packed_row0(mwo_ref, b0c, rows_w, cr)
            b = _decode_span(
                pm_ref[...], mba_ref, mme_ref, mwo_ref,
                b0c, row0, TILE_ROWS,
            )
        pos = _tile_positions(tile)
        in_range = (pos >= mb_ref[q, t, 0]) & (pos < mb_ref[q, t, 1])
        b = jnp.where(in_range, b, INVALID_DOC)
        m = _tile_member(a, b)
        mm_ref[...] = mm_ref[...] | m.astype(jnp.int32)

    @pl.when(j == s_max - 1)
    def _fold_term():
        active = act_ref[q, t] != 0
        outm_ref[0] = outm_ref[0] * jnp.where(active, mm_ref[...], 1)

    @pl.when((t == t_slots - 1) & (j == s_max - 1))
    def _finalize():
        aa = jnp.where(
            in_win, aa_ref[pl.ds(shift, TILE_ROWS), :], INVALID_ATTR
        )
        keep = _fused_keep(a, aa, attr_ref[q, 0], attr_ref[q, 1] != 0)
        outm_ref[0] = outm_ref[0] * keep


@functools.partial(jax.jit, static_argnames=("window", "s_max", "interpret"))
def intersect_batched_driver_streamed(
    d_off: jnp.ndarray,        # int32[Q]  driver window start (BLOCK-aligned)
    d_neff: jnp.ndarray,       # int32[Q]  live driver postings (<= window)
    terms: jnp.ndarray,        # int32[Q, T]  term ids per slot (NO_TERM pad)
    active: jnp.ndarray,       # int32[Q, T]  1 iff slot t joins query q
    attr_filter: jnp.ndarray,  # int32[Q]     NO_ATTR(-1) = unrestricted
    postings: jnp.ndarray,     # int32[P]  flat postings (TILE-pad + spare)
    attrs: jnp.ndarray,        # int32[P]  flat embedded attrs (same layout)
    offsets: jnp.ndarray, lengths: jnp.ndarray, block_max: jnp.ndarray,
    *,
    window: int,
    packed: PackedFlatArrays | None = None,  # block-codec main postings
    s_max: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched ZigZag join with the DRIVER window streamed from the index.

    The read path of :func:`intersect_batched_streamed` minus its one
    remaining host-side materialization: instead of a gathered ``(Q, W)``
    driver operand, per-query driver tile offsets (``d_off``/``d_neff``,
    supplied by the engine's PostingSource layer) are scalar-prefetched and
    Element-indexed BlockSpecs walk the flat ``postings``/``attrs`` arrays
    directly.  Driver-tile docID spans for the other-term probe plan come
    from the BLOCK skip table (:func:`driver_tile_spans`) — conservative,
    never lossy.

    With ``packed``, both posting streams (driver window and other-term
    probes) read block-codec word chunks instead of raw tiles and decode
    in VMEM; the attrs stream stays raw (attributes don't gap-compress).

    Returns ``(docs, mask)``, both int32[Q, window]: the driver window as
    read by the kernel (INVALID_DOC past the live range) and the join mask
    in {0, 1}.  Top-k selection needs nothing else.
    """
    q_n, t_slots = terms.shape
    assert postings.shape[0] % TILE == 0, "main postings must be TILE-padded"
    num_m = postings.shape[0] // TILE
    rows_total = num_m * TILE_ROWS

    num_a = -(-window // TILE)      # window-aligned driver tiles
    a_spans = jax.vmap(
        functools.partial(driver_tile_spans, block_max, s_tiles=num_a)
    )(d_off, d_neff)
    s_tiles_b = -(-window // TILE) + 1
    b_tile, n_b, bounds = _probe_plan(
        a_spans, terms, offsets, lengths, block_max,
        window=window, s_tiles=s_tiles_b,
    )
    s_grid = _clamp_s_max(s_max, s_tiles_b)
    active = active.astype(jnp.int32)
    n_b = jnp.minimum(n_b, s_grid) * active[:, :, None]
    attr_params = jnp.stack(
        [attr_filter.astype(jnp.int32), (attr_filter >= 0).astype(jnp.int32)],
        axis=-1,
    )
    a_info = jnp.stack(
        [d_off.astype(jnp.int32) // LANES, d_neff.astype(jnp.int32)], axis=-1
    )
    pm2 = postings.reshape(rows_total, LANES)
    pa2 = attrs.reshape(rows_total, LANES)

    ad_map = _driver_window_map(rows_total, 5)
    b_map = _streamed_flat_map(0, 1, num_m)

    scalars = [b_tile, n_b, bounds, active, attr_params, a_info]
    scratch = [pltpu.VMEM((TILE_ROWS, LANES), jnp.int32)]
    if packed is not None:
        # Descriptors append after a_info (indices 6, 7, 8); both posting
        # streams become packed-word chunks sharing one descriptor set.
        scalars += [packed.blk_base, packed.blk_meta, packed.blk_woff]
        words_m = packed.words.reshape(-1, LANES)
        pk = (packed.n_blocks, words_m.shape[0], packed.chunk_rows)
        in_specs = [
            element_spec(packed.chunk_rows, _packed_driver_map(5, 8, *pk)),
            element_spec(WINDOW_READ_ROWS, ad_map),
            element_spec(packed.chunk_rows, _packed_flat_map(0, 1, 8, *pk)),
        ]
        operands = [words_m, pa2, words_m]
        scratch.append(pltpu.VMEM((TILE_ROWS, LANES), jnp.int32))
    else:
        pk = None
        in_specs = [
            element_spec(WINDOW_READ_ROWS, ad_map),
            element_spec(WINDOW_READ_ROWS, ad_map),
            pl.BlockSpec((TILE_ROWS, LANES), b_map),
        ]
        operands = [pm2, pa2, pm2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(q_n, num_a, t_slots, s_grid),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, TILE_ROWS, LANES), _driver_out_map),
            pl.BlockSpec((1, TILE_ROWS, LANES), _driver_out_map),
        ],
        scratch_shapes=scratch,
    )
    shape = jax.ShapeDtypeStruct((q_n, num_a * TILE_ROWS, LANES), jnp.int32)
    docs, mask = pl.pallas_call(
        functools.partial(
            _driver_streamed_kernel, t_slots=t_slots, s_max=s_grid,
            rows_total=rows_total, packed=pk,
        ),
        grid_spec=grid_spec,
        out_shape=[shape, shape],
        interpret=interpret,
    )(*scalars, *operands)
    return (
        docs.reshape(q_n, -1)[:, :window],
        mask.reshape(q_n, -1)[:, :window],
    )


# ---------------------------------------------------------------------------
# Work-list compacted variants: 1-D grids over dense descriptor tables
# ---------------------------------------------------------------------------
#
# The dense streamed grids above are shaped by the *worst* query in the
# batch — (Q, num_a, t_slots, s_grid) — and burn full grid steps on inert
# padding queries, absent term slots and short probe spans, which the
# ``consumed``/``active`` masks then throw away.  The compacted variants
# make kernel work proportional to live work: the host-side builder
# (:mod:`repro.kernels.worklist`) enumerates live (query, driver-tile,
# term, probe-step) items from the same probe plan, packs them into a
# dense int32 descriptor table, and the grid's only dimension is the item
# index.  BlockSpec index maps read (q, i, probe tile) from the
# scalar-prefetched table; the per-item flags replace the dense grid's
# positional edge tests ((t == 0) & (j == 0) etc.) for init / term-reset /
# fold / finalize.  Semantics are bit-identical to the dense kernels —
# the dense grid stays registered as the A/B comparator, like
# ``pallas_staged`` before it.


def _wl_block_map(n, desc_ref, *_):
    """Output / driver-window block of work item ``n``: (q, i)."""
    return (desc_ref[n, 0], desc_ref[n, 1], 0)


def _wl_probe_map(field, num_tiles):
    """Blocked probe-stream map from a descriptor column holding an
    absolute tile index (``-1`` = no probe this item; remapped to tile 0,
    which the kernel never consumes — the ``pl.when(tile >= 0)`` guard)."""

    def b_map(n, desc_ref, *_):
        return (jnp.clip(desc_ref[n, field], 0, num_tiles - 1), 0)

    return b_map


def _wl_packed_probe_map(field, woff_idx, n_blocks, rows_w, chunk_rows):
    """Packed-word analogue of :func:`_wl_probe_map`: descriptor tile ->
    first block -> word row through ``blk_woff``, clamped like
    :func:`_packed_flat_map`."""

    def b_map(n, *refs):
        tile = jnp.maximum(refs[0][n, field], 0)
        b0c = jnp.minimum(tile * (TILE // BLOCK), n_blocks)
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return b_map


def _wl_driver_window_map(rows_total, info_idx):
    """Driver-window map in work-list space: the aligned read covering
    window tile ``desc[n, 1]`` of query ``desc[n, 0]``, edge-clamped like
    :func:`_driver_window_map` (safe iff the spare tile exists and the
    kernel's ``in_win`` mask discards the clamped slots)."""

    def ad_map(n, *refs):
        q = refs[0][n, 0]
        row = refs[info_idx][q, 0] + refs[0][n, 1] * TILE_ROWS
        return (window_read(row, rows_total)[0], 0)

    return ad_map


def _wl_packed_driver_map(info_idx, woff_idx, n_blocks, rows_w, chunk_rows):
    """Packed-word analogue of :func:`_wl_driver_window_map`."""

    def ad_map(n, *refs):
        q = refs[0][n, 0]
        b0c = jnp.minimum(
            refs[info_idx][q, 0] + refs[0][n, 1] * (TILE // BLOCK), n_blocks
        )
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return ad_map


def _streamed_compact_kernel(
    *refs, has_delta: bool, packed_m=None, packed_d=None,
):
    # Work-list twin of _streamed_kernel: one grid step per live work item.
    # Scalar-prefetch order — wl (the descriptor table), bounds_m,
    # [bounds_d,] attr, [packed descriptors (main [, delta])]; operands and
    # scratch as in the dense kernel minus the plan scalars the flags
    # replace (b_tile/n_b live inside the table, ``active`` is implicit:
    # items only exist for active terms).
    packed = packed_m is not None
    if has_delta:
        if packed:
            (wl_ref, mb_ref, db_ref, attr_ref,
             mba_ref, mme_ref, mwo_ref, dba_ref, dme_ref, dwo_ref,
             a_ref, aa_ref, al_ref, af_ref, pm_ref, pd_ref,
             out_ref, mm_ref, md_ref) = refs
        else:
            (wl_ref, mb_ref, db_ref, attr_ref,
             a_ref, aa_ref, al_ref, af_ref, pm_ref, pd_ref,
             out_ref, mm_ref, md_ref) = refs
    else:
        if packed:
            (wl_ref, mb_ref, attr_ref, mba_ref, mme_ref, mwo_ref,
             a_ref, aa_ref, al_ref, pm_ref, out_ref, mm_ref) = refs
        else:
            (wl_ref, mb_ref, attr_ref,
             a_ref, aa_ref, al_ref, pm_ref, out_ref, mm_ref) = refs
    n = pl.program_id(0)
    q = wl_ref[n, 0]
    t = wl_ref[n, 2]
    flags = wl_ref[n, 4]

    @pl.when((flags & FLAG_FIRST) != 0)
    def _init_out():
        out_ref[...] = jnp.ones_like(out_ref)

    @pl.when((flags & FLAG_TERM_START) != 0)
    def _init_members():
        mm_ref[...] = jnp.zeros_like(mm_ref)
        if has_delta:
            md_ref[...] = jnp.zeros_like(md_ref)

    def _probe(field, bounds_ref, tile_arr_ref, member_ref, desc=None):
        tile = wl_ref[n, field]

        @pl.when(tile >= 0)
        def _():
            if desc is None:
                b = tile_arr_ref[...]
            else:
                # Packed stream: recompute the index map's exact b0c/row0
                # (tile >= 0 here, so the max() matches the map's remap).
                base_ref, meta_ref, woff_ref, (nbk, rows_w, cr) = desc
                b0c = jnp.minimum(
                    jnp.maximum(tile, 0) * (TILE // BLOCK), nbk
                )
                row0 = _packed_row0(woff_ref, b0c, rows_w, cr)
                b = _decode_span(
                    tile_arr_ref[...], base_ref, meta_ref, woff_ref,
                    b0c, row0, TILE_ROWS,
                )
            pos = _tile_positions(tile)
            in_range = (pos >= bounds_ref[q, t, 0]) & (pos < bounds_ref[q, t, 1])
            b = jnp.where(in_range, b, INVALID_DOC)
            m = _tile_member(a_ref[0], b)
            member_ref[...] = member_ref[...] | m.astype(jnp.int32)

    _probe(3, mb_ref, pm_ref, mm_ref,
           desc=(mba_ref, mme_ref, mwo_ref, packed_m) if packed else None)
    if has_delta:
        _probe(5, db_ref, pd_ref, md_ref,
               desc=(dba_ref, dme_ref, dwo_ref, packed_d) if packed else None)

    # Term fold — no ``active`` gate: the builder only emits TERM_END items
    # for active terms (inert tiles carry FIRST|LAST only).
    @pl.when((flags & FLAG_TERM_END) != 0)
    def _fold_term():
        if has_delta:
            aflg = af_ref[0]
            main_ok = (aflg & jnp.int32(DOC_DEAD | DOC_SUPERSEDED)) == 0
            delta_ok = (aflg & jnp.int32(DOC_DEAD)) == 0
            term_ok = (
                ((mm_ref[...] != 0) & main_ok)
                | ((md_ref[...] != 0) & delta_ok)
            ).astype(jnp.int32)
        else:
            term_ok = mm_ref[...]
        out_ref[0] = out_ref[0] * term_ok

    @pl.when((flags & FLAG_LAST) != 0)
    def _finalize():
        keep = _fused_keep(
            a_ref[0], aa_ref[0], attr_ref[q, 0], attr_ref[q, 1] != 0,
            live=al_ref[0],
        )
        out_ref[0] = out_ref[0] * keep


@functools.partial(jax.jit, static_argnames=("interpret",))
def _streamed_compact_call(
    desc, bounds_m, bounds_d, attr_filter,
    a_docs, a_attrs, a_live, a_flags,
    postings, d_postings, packed, d_packed, live_q,
    *, interpret,
):
    # The whole post-builder half runs under one jit: operand padding,
    # reshapes, and the pallas launch compile together, so a repeated
    # work-list shape costs one cached dispatch (pow2 bucketing by
    # worklist_pad keeps the shape cache small).
    has_delta = bounds_d is not None
    use_packed = packed is not None
    q_n, n_a = a_docs.shape
    a = _pad_to_tile(a_docs, INVALID_DOC)
    aa = _pad_to_tile(a_attrs, -1)
    al = _pad_to_tile(a_live.astype(jnp.int32), 0)
    num_a = a.shape[1] // TILE
    a2 = a.reshape(q_n, num_a * TILE_ROWS, LANES)
    aa2 = aa.reshape(q_n, num_a * TILE_ROWS, LANES)
    al2 = al.reshape(q_n, num_a * TILE_ROWS, LANES)
    af2 = None
    if has_delta:
        af2 = _pad_to_tile(a_flags.astype(jnp.int32), 0).reshape(
            q_n, num_a * TILE_ROWS, LANES
        )
    attr_params = jnp.stack(
        [attr_filter.astype(jnp.int32), (attr_filter >= 0).astype(jnp.int32)],
        axis=-1,
    )
    pdesc_m = pdesc_d = pk_m = pk_d = stream_d = None
    if use_packed:
        stream_m = packed.words.reshape(-1, LANES)
        pk_m = (packed.n_blocks, stream_m.shape[0], packed.chunk_rows)
        pdesc_m = (packed.blk_base, packed.blk_meta, packed.blk_woff)
        if has_delta:
            stream_d = d_packed.words.reshape(-1, LANES)
            pk_d = (
                d_packed.n_blocks, stream_d.shape[0], d_packed.chunk_rows
            )
            pdesc_d = (
                d_packed.blk_base, d_packed.blk_meta, d_packed.blk_woff
            )
    else:
        stream_m = postings.reshape(-1, LANES)
        if has_delta:
            stream_d = d_postings.reshape(-1, LANES)
    n_steps = desc.shape[0]

    scalars = [desc, bounds_m]
    if has_delta:
        scalars.append(bounds_d)
    scalars.append(attr_params)
    if use_packed:
        woff_m_idx = len(scalars) + 2
        scalars += list(pdesc_m)
        if has_delta:
            woff_d_idx = len(scalars) + 2
            scalars += list(pdesc_d)

    operands = [a2, aa2, al2]
    if has_delta:
        operands.append(af2)
    blk_a = pl.BlockSpec((1, TILE_ROWS, LANES), _wl_block_map)
    in_specs = [blk_a for _ in operands]
    if use_packed:
        in_specs.append(
            element_spec(pk_m[2], _wl_packed_probe_map(3, woff_m_idx, *pk_m))
        )
    else:
        num_m = stream_m.shape[0] // TILE_ROWS
        in_specs.append(
            pl.BlockSpec((TILE_ROWS, LANES), _wl_probe_map(3, num_m))
        )
    operands.append(stream_m)
    scratch = [pltpu.VMEM((TILE_ROWS, LANES), jnp.int32)]
    if has_delta:
        if use_packed:
            in_specs.append(
                element_spec(
                    pk_d[2], _wl_packed_probe_map(5, woff_d_idx, *pk_d)
                )
            )
        else:
            num_d = stream_d.shape[0] // TILE_ROWS
            in_specs.append(
                pl.BlockSpec((TILE_ROWS, LANES), _wl_probe_map(5, num_d))
            )
        operands.append(stream_d)
        scratch.append(pltpu.VMEM((TILE_ROWS, LANES), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=blk_a,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _streamed_compact_kernel, has_delta=has_delta,
            packed_m=pk_m, packed_d=pk_d,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (q_n, num_a * TILE_ROWS, LANES), jnp.int32
        ),
        interpret=interpret,
    )(*scalars, *operands)
    out = out.reshape(q_n, -1)[:, :n_a]
    if live_q is not None:
        out = jnp.where(live_q[:, None], out, 0)
    return out


@functools.partial(jax.jit, static_argnames=("window", "s_tiles"))
def _streamed_plan(a_docs, terms, offsets, lengths, block_max, *, window,
                   s_tiles):
    a = _pad_to_tile(a_docs, INVALID_DOC)
    a_spans = _a_tile_spans(a)
    b_tile, n_b, bounds = _probe_plan(
        a_spans, terms, offsets, lengths, block_max,
        window=window, s_tiles=s_tiles,
    )
    return a_spans[2], b_tile, n_b, bounds


def intersect_batched_streamed_compact(
    a_docs: jnp.ndarray,
    a_attrs: jnp.ndarray,
    a_live: jnp.ndarray,
    terms: jnp.ndarray,
    active: jnp.ndarray,
    attr_filter: jnp.ndarray,
    postings: jnp.ndarray,
    offsets: jnp.ndarray, lengths: jnp.ndarray, block_max: jnp.ndarray,
    d_postings: jnp.ndarray | None = None,
    d_offsets: jnp.ndarray | None = None,
    d_lengths: jnp.ndarray | None = None,
    d_block_max: jnp.ndarray | None = None,
    a_flags: jnp.ndarray | None = None,
    *,
    packed: PackedFlatArrays | None = None,
    d_packed: PackedFlatArrays | None = None,
    s_max: int | None = None,
    interpret: bool = False,
    live_q: np.ndarray | None = None,
) -> jnp.ndarray:
    """Work-list compacted :func:`intersect_batched_streamed`.

    Same arguments and bit-identical results, plus ``live_q`` (host bool[Q];
    ``None`` = all live): inert padding queries contribute zero grid steps
    and their output rows are masked to 0 host-side.  The probe plan is
    computed on device, pulled to the host, and compiled into a dense
    descriptor table; the kernel launch is a 1-D grid over live work items
    only.  An all-inert batch launches nothing.
    """
    has_delta = d_postings is not None
    use_packed = packed is not None
    if use_packed and has_delta and d_packed is None:
        raise ValueError("packed codec needs d_packed when delta arrays are given")
    q_n, n_a = a_docs.shape
    window = n_a
    t_slots = terms.shape[1]
    num_a = -(-n_a // TILE)

    s_tiles_m = -(-window // TILE) + 1
    a_any, b_tile, n_b, bounds_m = _streamed_plan(
        a_docs, terms, offsets, lengths, block_max,
        window=window, s_tiles=s_tiles_m,
    )
    s_grid_m = _clamp_s_max(s_max, s_tiles_m)
    s_grid = s_grid_m
    bounds_d = n_d = d_tile = None
    if has_delta:
        cap = d_block_max.shape[0] * BLOCK // d_offsets.shape[0]
        s_tiles_d = -(-cap // TILE) + 1
        _, d_tile, n_d, bounds_d = _streamed_plan(
            a_docs, terms, d_offsets, d_lengths, d_block_max,
            window=cap, s_tiles=s_tiles_d,
        )
        s_grid = max(s_grid_m, _clamp_s_max(s_max, s_tiles_d))

    # one batched host pull for everything the builder needs
    n_d_h = d_tile_h = None
    if has_delta:
        active_h, n_b_h, b_tile_h, a_any_h, n_d_h, d_tile_h = jax.device_get(
            (active, n_b, b_tile, a_any, n_d, d_tile)
        )
    else:
        active_h, n_b_h, b_tile_h, a_any_h = jax.device_get(
            (active, n_b, b_tile, a_any)
        )
    active_h = np.asarray(active_h).astype(np.int32)
    n_b_h = np.minimum(np.asarray(n_b_h), s_grid_m) * active_h[:, :, None]
    if has_delta:
        n_d_h = np.minimum(np.asarray(n_d_h), s_grid) * active_h[:, :, None]
        d_tile_h = np.asarray(d_tile_h)

    suffix = "_packed" if use_packed else ""
    wl = build_intersect_worklist(
        n_b_h, np.asarray(b_tile_h), active_h, np.asarray(a_any_h),
        n_d=n_d_h, d_tile=d_tile_h, live_q=live_q,
        kernel="intersect_batched_streamed_compact" + suffix,
        dense_steps=q_n * num_a * t_slots * s_grid,
    )
    if wl.n_items == 0:
        return jnp.zeros((q_n, n_a), jnp.int32)

    lq = None if live_q is None else jnp.asarray(np.asarray(live_q))
    return _streamed_compact_call(
        jnp.asarray(wl.desc), bounds_m, bounds_d, attr_filter,
        a_docs, a_attrs, a_live, a_flags,
        postings, d_postings, packed, d_packed, lq,
        interpret=interpret,
    )


def _driver_compact_kernel(*refs, rows_total: int, packed=None):
    # Work-list twin of _driver_streamed_kernel.  Scalar-prefetch order:
    # wl, bounds, attr, a_info, [packed descriptors]; the flags replace the
    # (t, j) edge tests and ``active`` is implicit in item existence.
    if packed is not None:
        (wl_ref, mb_ref, attr_ref, ainfo_ref,
         mba_ref, mme_ref, mwo_ref,
         ad_ref, aa_ref, pm_ref, outd_ref, outm_ref,
         mm_ref, adk_ref) = refs
        nbk, rows_w, cr = packed
    else:
        (wl_ref, mb_ref, attr_ref, ainfo_ref,
         ad_ref, aa_ref, pm_ref, outd_ref, outm_ref, mm_ref) = refs
    n = pl.program_id(0)
    q = wl_ref[n, 0]
    i = wl_ref[n, 1]
    t = wl_ref[n, 2]
    flags = wl_ref[n, 4]
    _, shift = window_read(ainfo_ref[q, 0] + i * TILE_ROWS, rows_total)

    if packed is not None:
        # Decode the driver tile on the group's first item; the scratch
        # persists across the group's contiguous grid steps.
        @pl.when((flags & FLAG_FIRST) != 0)
        def _decode_driver():
            b0c = jnp.minimum(ainfo_ref[q, 0] + i * (TILE // BLOCK), nbk)
            row0 = _packed_row0(mwo_ref, b0c, rows_w, cr)
            adk_ref[...] = _decode_span(
                ad_ref[...], mba_ref, mme_ref, mwo_ref,
                b0c, row0, TILE_ROWS,
            )

        a_tile = adk_ref[...]
    else:
        a_tile = ad_ref[pl.ds(shift, TILE_ROWS), :]

    in_win = _tile_positions(i) < ainfo_ref[q, 1]
    a = jnp.where(in_win, a_tile, INVALID_DOC)

    @pl.when((flags & FLAG_FIRST) != 0)
    def _init_out():
        outm_ref[...] = jnp.ones_like(outm_ref)
        outd_ref[0] = a

    @pl.when((flags & FLAG_TERM_START) != 0)
    def _init_member():
        mm_ref[...] = jnp.zeros_like(mm_ref)

    tile = wl_ref[n, 3]

    @pl.when(tile >= 0)
    def _probe():
        if packed is None:
            b = pm_ref[...]
        else:
            b0c = jnp.minimum(jnp.maximum(tile, 0) * (TILE // BLOCK), nbk)
            row0 = _packed_row0(mwo_ref, b0c, rows_w, cr)
            b = _decode_span(
                pm_ref[...], mba_ref, mme_ref, mwo_ref,
                b0c, row0, TILE_ROWS,
            )
        pos = _tile_positions(tile)
        in_range = (pos >= mb_ref[q, t, 0]) & (pos < mb_ref[q, t, 1])
        b = jnp.where(in_range, b, INVALID_DOC)
        m = _tile_member(a, b)
        mm_ref[...] = mm_ref[...] | m.astype(jnp.int32)

    @pl.when((flags & FLAG_TERM_END) != 0)
    def _fold_term():
        outm_ref[0] = outm_ref[0] * mm_ref[...]

    @pl.when((flags & FLAG_LAST) != 0)
    def _finalize():
        aa = jnp.where(
            in_win, aa_ref[pl.ds(shift, TILE_ROWS), :], INVALID_ATTR
        )
        keep = _fused_keep(a, aa, attr_ref[q, 0], attr_ref[q, 1] != 0)
        outm_ref[0] = outm_ref[0] * keep


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _driver_compact_call(
    desc, bounds, attr_filter, d_off, d_neff,
    postings, attrs, packed, live_q,
    *, window, interpret,
):
    # Post-builder half under one jit (see _streamed_compact_call).
    q_n = attr_filter.shape[0]
    num_a = -(-window // TILE)
    rows_total = attrs.shape[0] // LANES
    n_steps = desc.shape[0]
    attr_params = jnp.stack(
        [attr_filter.astype(jnp.int32), (attr_filter >= 0).astype(jnp.int32)],
        axis=-1,
    )
    a_info = jnp.stack(
        [d_off.astype(jnp.int32) // LANES, d_neff.astype(jnp.int32)], axis=-1
    )
    pa2 = attrs.reshape(rows_total, LANES)
    if packed is not None:
        words_m = packed.words.reshape(-1, LANES)
        pk = (packed.n_blocks, words_m.shape[0], packed.chunk_rows)
        stream_a = stream_b = words_m
        pdesc = (packed.blk_base, packed.blk_meta, packed.blk_woff)
    else:
        pk = None
        pdesc = None
        stream_a = stream_b = postings.reshape(rows_total, LANES)

    scalars = [desc, bounds, attr_params, a_info]
    scratch = [pltpu.VMEM((TILE_ROWS, LANES), jnp.int32)]
    ad_map = _wl_driver_window_map(rows_total, 3)
    if pk is not None:
        scalars += list(pdesc)
        in_specs = [
            element_spec(pk[2], _wl_packed_driver_map(3, 6, *pk)),
            element_spec(WINDOW_READ_ROWS, ad_map),
            element_spec(pk[2], _wl_packed_probe_map(3, 6, *pk)),
        ]
        scratch.append(pltpu.VMEM((TILE_ROWS, LANES), jnp.int32))
    else:
        num_m = stream_b.shape[0] // TILE_ROWS
        in_specs = [
            element_spec(WINDOW_READ_ROWS, ad_map),
            element_spec(WINDOW_READ_ROWS, ad_map),
            pl.BlockSpec((TILE_ROWS, LANES), _wl_probe_map(3, num_m)),
        ]
    operands = [stream_a, pa2, stream_b]

    blk_o = pl.BlockSpec((1, TILE_ROWS, LANES), _wl_block_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=[blk_o, blk_o],
        scratch_shapes=scratch,
    )
    shape = jax.ShapeDtypeStruct((q_n, num_a * TILE_ROWS, LANES), jnp.int32)
    docs, mask = pl.pallas_call(
        functools.partial(
            _driver_compact_kernel, rows_total=rows_total, packed=pk
        ),
        grid_spec=grid_spec,
        out_shape=[shape, shape],
        interpret=interpret,
    )(*scalars, *operands)
    docs = docs.reshape(q_n, -1)[:, :window]
    mask = mask.reshape(q_n, -1)[:, :window]
    if live_q is not None:
        lq = live_q[:, None]
        docs = jnp.where(lq, docs, INVALID_DOC)
        mask = jnp.where(lq, mask, 0)
    return docs, mask


@functools.partial(jax.jit, static_argnames=("window", "num_a", "s_tiles"))
def _driver_plan(
    d_off, d_neff, terms, offsets, lengths, block_max,
    *, window, num_a, s_tiles,
):
    a_spans = jax.vmap(
        functools.partial(driver_tile_spans, block_max, s_tiles=num_a)
    )(d_off, d_neff)
    b_tile, n_b, bounds = _probe_plan(
        a_spans, terms, offsets, lengths, block_max,
        window=window, s_tiles=s_tiles,
    )
    return a_spans[2], b_tile, n_b, bounds


def intersect_batched_driver_streamed_compact(
    d_off: jnp.ndarray,
    d_neff: jnp.ndarray,
    terms: jnp.ndarray,
    active: jnp.ndarray,
    attr_filter: jnp.ndarray,
    postings: jnp.ndarray,
    attrs: jnp.ndarray,
    offsets: jnp.ndarray, lengths: jnp.ndarray, block_max: jnp.ndarray,
    *,
    window: int,
    packed: PackedFlatArrays | None = None,
    s_max: int | None = None,
    interpret: bool = False,
    live_q: np.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Work-list compacted :func:`intersect_batched_driver_streamed`.

    Same arguments and bit-identical ``(docs, mask)``, plus ``live_q``:
    inert queries contribute zero grid steps and their output rows come
    back as (INVALID_DOC, 0).  An all-inert batch launches nothing.
    """
    q_n, t_slots = terms.shape
    num_a = -(-window // TILE)
    s_tiles_b = -(-window // TILE) + 1
    a_any, b_tile, n_b, bounds = _driver_plan(
        d_off, d_neff, terms, offsets, lengths, block_max,
        window=window, num_a=num_a, s_tiles=s_tiles_b,
    )
    s_grid = _clamp_s_max(s_max, s_tiles_b)
    active_h, n_b_h, b_tile_h, a_any_h = jax.device_get(
        (active, n_b, b_tile, a_any)
    )
    active_h = np.asarray(active_h).astype(np.int32)
    n_b_h = np.minimum(np.asarray(n_b_h), s_grid) * active_h[:, :, None]
    suffix = "_packed" if packed is not None else ""
    wl = build_intersect_worklist(
        n_b_h, np.asarray(b_tile_h), active_h, np.asarray(a_any_h),
        live_q=live_q,
        kernel="intersect_batched_driver_streamed_compact" + suffix,
        dense_steps=q_n * num_a * t_slots * s_grid,
    )
    if wl.n_items == 0:
        return (
            jnp.full((q_n, window), INVALID_DOC, jnp.int32),
            jnp.zeros((q_n, window), jnp.int32),
        )

    lq = None if live_q is None else jnp.asarray(np.asarray(live_q))
    return _driver_compact_call(
        jnp.asarray(wl.desc), bounds, attr_filter, d_off, d_neff,
        postings, attrs, packed, lq,
        window=window, interpret=interpret,
    )


def skip_fraction(a_docs: jnp.ndarray, b_docs: jnp.ndarray) -> jnp.ndarray:
    """Diagnostic: fraction of B-tile DMAs avoided by posting skipping."""
    a = _pad_to_tile(a_docs, INVALID_DOC)
    b = _pad_to_tile(b_docs, INVALID_DOC)
    _, n_b = compute_skip_map(a, b)
    num_a = a.shape[0] // TILE
    num_b = b.shape[0] // TILE
    scanned = jnp.sum(n_b)
    return 1.0 - scanned / (num_a * num_b)


# ---------------------------------------------------------------------------
# Contract registration (repro.kernels.registry -> repro.analysis)
# ---------------------------------------------------------------------------
#
# Each pallas_call site above registers a builder that reconstructs its
# grid / BlockSpec geometry on a small canonical index — built through the
# REAL index builder (flat_tile_pad and all) — plus the clamp-safety
# metadata Pallas cannot express: the pre-clamp ``intended`` address of
# every clamping index map and the kernel's ``consumed`` masking.  The
# static checker enumerates the grid and proves the invariants without
# executing a kernel.  The canonical corpus deliberately places a short
# list at the very end of the flat arrays (non-TILE-multiple live extent),
# so the edge-clamp path — the PR 5 bug class — is exercised by contract.

from repro.kernels.registry import (  # noqa: E402
    ELEMENT,
    KernelContract,
    OperandContract,
    kernel_contract,
    site_of,
    synthetic_flat_index,
)

# Canonical list lengths: 1100 (9 blocks) + 100 + 90 postings -> lists at
# flat offsets 0, 1152 and 1280, live extent 1408 (11 rows), flat arrays
# flat_tile_pad'ed to 3072 (24 rows).  The last list (term 2) starts in
# the second tile and ends mid-tile next to the spare tile: streaming its
# window forces the window-read clamp that only the spare INVALID tile
# makes safe.  Under the historical floor+1 pad (2048 = 16 rows) the same
# clamp lands on its live postings, which the checker must catch.
_CANON_LISTS = (1100, 100, 90)
_CANON_OFF = (0, 1152, 1280)


def _aligned(row):
    return (row // TILE_ROWS) * TILE_ROWS


def _driver_window_intended(info_idx):
    """Pre-clamp address of :func:`_driver_window_map` — contract only."""

    def ad_map(q, i, t, j, *refs):
        return (_aligned(refs[info_idx][q, 0] + i * TILE_ROWS), 0)

    return ad_map


def _streamed_flat_intended(start_idx):
    """Pre-clamp address of :func:`_streamed_flat_map` for consumed steps
    (``jj == j`` whenever ``j < n_b``) — contract only."""

    def b_map(q, i, t, j, *refs):
        return (refs[start_idx][q, t, i] + j, 0)

    return b_map


def _streamed_flat_consumed(n_idx):
    def consumed(q, i, t, j, *refs):
        return bool(j < refs[n_idx][q, t, i])

    return consumed


def _packed_flat_intended(start_idx, woff_idx, n_blocks):
    """Pre-rows-clamp address of :func:`_packed_flat_map` for consumed
    steps (``jj == j`` whenever ``j < n_b``).  The descriptor clamp on
    ``b0c`` stays — ``blk_woff`` really does end at ``n_blocks +
    DESC_PAD``, and past-the-live-range chunks carry only zero fill —
    so only the rows_w edge clamp is exposed to the checker, and
    ``packed_word_pad`` guarantees it never engages."""

    def b_map(q, i, t, j, *refs):
        b0c = jnp.minimum(
            (refs[start_idx][q, t, i] + j) * (TILE // BLOCK), n_blocks
        )
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return b_map


def _packed_driver_intended(info_idx, woff_idx, n_blocks):
    """Pre-rows-clamp address of :func:`_packed_driver_map` — contract
    only (same descriptor-clamp caveat as :func:`_packed_flat_intended`)."""

    def ad_map(q, i, t, j, *refs):
        b0c = jnp.minimum(
            refs[info_idx][q, 0] + i * (TILE // BLOCK), n_blocks
        )
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return ad_map


def _packed_stream_op(
    name, pk, start_idx, n_idx, woff_idx
) -> "OperandContract":
    """OperandContract of one packed-word probe stream: bounds in packed
    words, ``intended_map`` in logical blocks via the descriptor table,
    spare-tile per :func:`repro.core.index.packed_word_pad`."""
    rows_w = pk.words.shape[0] // LANES
    live_words = int(np.asarray(pk.blk_woff)[-1])
    return OperandContract(
        name,
        (rows_w, LANES),
        "int32",
        (pk.chunk_rows, LANES),
        _packed_flat_map(
            start_idx, n_idx, woff_idx, pk.n_blocks, rows_w, pk.chunk_rows
        ),
        indexing_mode=ELEMENT,
        intended_map=_packed_flat_intended(start_idx, woff_idx, pk.n_blocks),
        consumed=_streamed_flat_consumed(n_idx),
        padding_from=live_words,
        spare_tile=True,
    )


def _attr_params(attr_filter: np.ndarray) -> np.ndarray:
    return np.stack(
        [attr_filter.astype(np.int32), (attr_filter >= 0).astype(np.int32)],
        axis=-1,
    )


def _host_window(flat: np.ndarray, off: int, n_eff: int, width: int, fill):
    w = np.full(width, fill, dtype=flat.dtype)
    w[:n_eff] = flat[off : off + n_eff]
    return w


@kernel_contract("intersect_block_skip")
def _contract_intersect_block_skip():
    rng = np.random.default_rng(0)
    num_a, num_b = 2, 3
    a = np.sort(rng.choice(50_000, num_a * TILE, replace=False)).astype(np.int32)
    b = np.sort(rng.choice(50_000, num_b * TILE, replace=False)).astype(np.int32)
    s_max = num_b
    b_start, n_b = (
        np.asarray(x) for x in compute_skip_map(jnp.asarray(a), jnp.asarray(b))
    )
    n_b = np.minimum(n_b, s_max)
    tile = (TILE_ROWS, LANES)
    a_shape = (num_a * TILE_ROWS, LANES)
    b_shape = (num_b * TILE_ROWS, LANES)

    def b_intended(i, j, b_start_ref, n_b_ref, attr_ref):
        return (b_start_ref[i] + j, 0)

    def b_consumed(i, j, b_start_ref, n_b_ref, attr_ref):
        return bool(j < n_b_ref[i])

    return KernelContract(
        name="intersect_block_skip",
        site=site_of(intersect_block_skip),
        grid=(num_a, s_max),
        scalars=(b_start, n_b, np.array([-1, 0], np.int32)),
        inputs=(
            OperandContract("a_docs", a_shape, "int32", tile, _ibs_a_map),
            OperandContract("a_attrs", a_shape, "int32", tile, _ibs_a_map),
            OperandContract(
                "b_docs",
                b_shape,
                "int32",
                tile,
                _ibs_b_map(num_b),
                intended_map=b_intended,
                consumed=b_consumed,
            ),
        ),
        outputs=(
            OperandContract("mask", a_shape, "int32", tile, _ibs_a_map),
        ),
        revisit_dims=(1,),
    )


@kernel_contract("intersect_batched_block_skip")
def _contract_intersect_batched():
    arrays, _live = synthetic_flat_index(_CANON_LISTS)
    postings = arrays["postings"]
    q_n, t_slots, window = 2, 2, TILE
    a = np.stack(
        [
            _host_window(postings, 0, 150, window, INVALID_DOC),
            _host_window(postings, _CANON_OFF[2], 90, window, INVALID_DOC),
        ]
    )
    b = np.stack(
        [
            np.stack(
                [
                    _host_window(postings, _CANON_OFF[1], 100, 2 * TILE, INVALID_DOC),
                    _host_window(postings, _CANON_OFF[2], 90, 2 * TILE, INVALID_DOC),
                ]
            ),
            np.stack(
                [
                    _host_window(postings, 0, 150, 2 * TILE, INVALID_DOC),
                    np.full(2 * TILE, INVALID_DOC, np.int32),
                ]
            ),
        ]
    )
    num_a, num_b = 1, 2
    s_max = num_b
    active = np.array([[1, 1], [1, 0]], np.int32)
    b_start, n_b = jax.vmap(jax.vmap(compute_skip_map, in_axes=(None, 0)))(
        jnp.asarray(a), jnp.asarray(b)
    )
    n_b = np.minimum(np.asarray(n_b), s_max) * active[:, :, None]
    scalars = (
        np.asarray(b_start),
        n_b,
        active,
        _attr_params(np.array([-1, -1], np.int32)),
    )
    blk_a = (1, TILE_ROWS, LANES)
    a_shape = (q_n, num_a * TILE_ROWS, LANES)
    b_shape = (q_n, t_slots, num_b * TILE_ROWS, LANES)

    def b_intended(q, i, t, j, b_start_ref, n_b_ref, active_ref, attr_ref):
        return (q, t, b_start_ref[q, t, i] + j, 0)

    def b_consumed(q, i, t, j, b_start_ref, n_b_ref, active_ref, attr_ref):
        return bool(j < n_b_ref[q, t, i])

    ins = [
        OperandContract(nm, a_shape, "int32", blk_a, _batched_a_map)
        for nm in ("a_docs", "a_attrs", "a_live")
    ]
    ins.append(
        OperandContract(
            "b_docs",
            b_shape,
            "int32",
            (1, 1, TILE_ROWS, LANES),
            _batched_b_map(num_b),
            intended_map=b_intended,
            consumed=b_consumed,
        )
    )
    return KernelContract(
        name="intersect_batched_block_skip",
        site=site_of(intersect_batched_block_skip),
        grid=(q_n, num_a, t_slots, s_max),
        scalars=scalars,
        inputs=tuple(ins),
        outputs=(
            OperandContract("mask", a_shape, "int32", blk_a, _batched_a_map),
        ),
        scratch=(((TILE_ROWS, LANES), "int32"),),
        revisit_dims=(2, 3),
    )


def _build_streamed_contract(use_packed: bool) -> KernelContract:
    from repro.core.index import DESC_PAD, pack_flat_postings
    from repro.kernels.registry import synthetic_delta_arrays

    arrays, live = synthetic_flat_index(_CANON_LISTS)
    postings = arrays["postings"]
    offsets = arrays["offsets"]
    lengths = arrays["lengths"]
    block_max = arrays["block_max"]
    delta = synthetic_delta_arrays(3, TILE, fills=(5, 0, 12))

    q_n, t_slots, window = 2, 2, TILE
    terms = np.array([[1, 2], [0, -1]], np.int32)
    active = np.array([[1, 1], [1, 0]], np.int32)
    a = np.stack(
        [
            _host_window(postings, 0, 150, window, INVALID_DOC),
            _host_window(postings, _CANON_OFF[2], 90, window, INVALID_DOC),
        ]
    )
    num_a = 1
    num_m = postings.shape[0] // TILE
    s_tiles_m = -(-window // TILE) + 1
    a_spans = _a_tile_spans(jnp.asarray(a))
    b_tile, n_b, bounds_m = _probe_plan(
        a_spans,
        jnp.asarray(terms),
        jnp.asarray(offsets),
        jnp.asarray(lengths),
        jnp.asarray(block_max),
        window=window,
        s_tiles=s_tiles_m,
    )
    s_grid = _clamp_s_max(None, s_tiles_m)
    n_b = np.minimum(np.asarray(n_b), s_grid) * active[:, :, None]

    d_off, d_len, d_bm = (
        delta["d_offsets"],
        delta["d_lengths"],
        delta["d_block_max"],
    )
    cap = d_bm.shape[0] * BLOCK // d_off.shape[0]
    num_d = delta["d_postings"].shape[0] // TILE
    s_tiles_d = -(-cap // TILE) + 1
    d_tile, n_d, bounds_d = _probe_plan(
        a_spans,
        jnp.asarray(terms),
        jnp.asarray(d_off),
        jnp.asarray(d_len),
        jnp.asarray(d_bm),
        window=cap,
        s_tiles=s_tiles_d,
    )
    s_grid = max(s_grid, _clamp_s_max(None, s_tiles_d))
    n_d = np.minimum(np.asarray(n_d), s_grid) * active[:, :, None]

    scalars = [
        np.asarray(b_tile),
        n_b,
        np.asarray(bounds_m),
        np.asarray(d_tile),
        n_d,
        np.asarray(bounds_d),
        active,
        _attr_params(np.array([-1, -1], np.int32)),
    ]
    blk_a = (1, TILE_ROWS, LANES)
    tile = (TILE_ROWS, LANES)
    a_shape = (q_n, num_a * TILE_ROWS, LANES)
    ins = [
        OperandContract(nm, a_shape, "int32", blk_a, _batched_a_map)
        for nm in ("a_docs", "a_attrs", "a_live", "a_flags")
    ]
    if use_packed:
        pk_m = pack_flat_postings(arrays["postings"])
        pk_d = pack_flat_postings(
            delta["d_postings"], span_blocks=max(DESC_PAD, cap // BLOCK)
        )
        woff_m, woff_d = 10, 13
        for pk in (pk_m, pk_d):
            scalars += [
                np.asarray(pk.blk_base),
                np.asarray(pk.blk_meta),
                np.asarray(pk.blk_woff),
            ]
        ins.append(_packed_stream_op("packed_words(main)", pk_m, 0, 1, woff_m))
        ins.append(_packed_stream_op("packed_words(delta)", pk_d, 3, 4, woff_d))
    else:
        ins.append(
            OperandContract(
                "postings",
                (num_m * TILE_ROWS, LANES),
                "int32",
                tile,
                _streamed_flat_map(0, 1, num_m),
                intended_map=_streamed_flat_intended(0),
                consumed=_streamed_flat_consumed(1),
                padding_from=live,
            )
        )
        ins.append(
            OperandContract(
                "d_postings",
                (num_d * TILE_ROWS, LANES),
                "int32",
                tile,
                _streamed_flat_map(3, 4, num_d),
                intended_map=_streamed_flat_intended(3),
                consumed=_streamed_flat_consumed(4),
                padding_from=int(cap * d_off.shape[0]),
            )
        )
    suffix = "_packed" if use_packed else ""
    return KernelContract(
        name="intersect_batched_streamed" + suffix,
        site=site_of(intersect_batched_streamed),
        grid=(q_n, num_a, t_slots, s_grid),
        scalars=tuple(scalars),
        inputs=tuple(ins),
        outputs=(
            OperandContract("mask", a_shape, "int32", blk_a, _batched_a_map),
        ),
        scratch=(((TILE_ROWS, LANES), "int32"), ((TILE_ROWS, LANES), "int32")),
        revisit_dims=(2, 3),
        notes="merge-on-read configuration (main + delta streams)"
        + (", block-codec probe streams" if use_packed else ""),
    )


@kernel_contract("intersect_batched_streamed")
def _contract_intersect_streamed():
    return _build_streamed_contract(False)


@kernel_contract("intersect_batched_streamed_packed")
def _contract_intersect_streamed_packed():
    return _build_streamed_contract(True)


def _build_driver_streamed_contract(use_packed: bool) -> KernelContract:
    from repro.core.index import pack_flat_postings

    arrays, live = synthetic_flat_index(_CANON_LISTS)
    offsets = arrays["offsets"]
    lengths = arrays["lengths"]
    block_max = arrays["block_max"]
    num_m = arrays["postings"].shape[0] // TILE
    rows_total = num_m * TILE_ROWS

    # window > live extent of the edge list: driver tile 1 of query 1 reads
    # past the array end and clamps — safe iff the spare tile exists.
    q_n, t_slots, window = 2, 2, 2 * TILE
    d_off = np.array([0, _CANON_OFF[2]], np.int32)       # term 0, term 2 (edge list)
    d_neff = np.array([150, 90], np.int32)
    terms = np.array([[1, 2], [0, -1]], np.int32)
    active = np.array([[1, 1], [1, 0]], np.int32)

    num_a = -(-window // TILE)
    a_spans = jax.vmap(
        functools.partial(
            driver_tile_spans, jnp.asarray(block_max), s_tiles=num_a
        )
    )(jnp.asarray(d_off), jnp.asarray(d_neff))
    s_tiles_b = -(-window // TILE) + 1
    b_tile, n_b, bounds = _probe_plan(
        a_spans,
        jnp.asarray(terms),
        jnp.asarray(offsets),
        jnp.asarray(lengths),
        jnp.asarray(block_max),
        window=window,
        s_tiles=s_tiles_b,
    )
    s_grid = _clamp_s_max(None, s_tiles_b)
    n_b = np.minimum(np.asarray(n_b), s_grid) * active[:, :, None]
    a_info = np.stack([d_off // LANES, d_neff], axis=-1).astype(np.int32)
    scalars = [
        np.asarray(b_tile),
        n_b,
        np.asarray(bounds),
        active,
        _attr_params(np.array([-1, -1], np.int32)),
        a_info,
    ]

    def ad_consumed(q, i, t, j, *refs):
        return bool(i * TILE < refs[5][q, 1])

    tile = (TILE_ROWS, LANES)
    win = (WINDOW_READ_ROWS, LANES)
    flat_shape = (rows_total, LANES)
    out_shape = (q_n, num_a * TILE_ROWS, LANES)
    stream_kw = dict(
        indexing_mode=ELEMENT,
        intended_map=_driver_window_intended(5),
        consumed=ad_consumed,
        padding_from=live,
        spare_tile=True,
        spare_elems=TILE,
    )
    if use_packed:
        pk = pack_flat_postings(arrays["postings"])
        scalars += [
            np.asarray(pk.blk_base),
            np.asarray(pk.blk_meta),
            np.asarray(pk.blk_woff),
        ]
        rows_w = pk.words.shape[0] // LANES
        live_words = int(np.asarray(pk.blk_woff)[-1])
        ins = (
            OperandContract(
                "packed_words(driver)",
                (rows_w, LANES),
                "int32",
                (pk.chunk_rows, LANES),
                _packed_driver_map(5, 8, pk.n_blocks, rows_w, pk.chunk_rows),
                indexing_mode=ELEMENT,
                intended_map=_packed_driver_intended(5, 8, pk.n_blocks),
                consumed=ad_consumed,
                padding_from=live_words,
                spare_tile=True,
            ),
            OperandContract(
                "attrs(driver)",
                flat_shape,
                "int32",
                win,
                _driver_window_map(rows_total, 5),
                **stream_kw,
            ),
            _packed_stream_op("packed_words(probe)", pk, 0, 1, 8),
        )
    else:
        ins = (
            OperandContract(
                "postings(driver)",
                flat_shape,
                "int32",
                win,
                _driver_window_map(rows_total, 5),
                **stream_kw,
            ),
            OperandContract(
                "attrs(driver)",
                flat_shape,
                "int32",
                win,
                _driver_window_map(rows_total, 5),
                **stream_kw,
            ),
            OperandContract(
                "postings(probe)",
                flat_shape,
                "int32",
                tile,
                _streamed_flat_map(0, 1, num_m),
                intended_map=_streamed_flat_intended(0),
                consumed=_streamed_flat_consumed(1),
                padding_from=live,
            ),
        )
    blk_o = (1, TILE_ROWS, LANES)
    scratch = [((TILE_ROWS, LANES), "int32")]
    if use_packed:
        scratch.append(((TILE_ROWS, LANES), "int32"))
    suffix = "_packed" if use_packed else ""
    return KernelContract(
        name="intersect_batched_driver_streamed" + suffix,
        site=site_of(intersect_batched_driver_streamed),
        grid=(q_n, num_a, t_slots, s_grid),
        scalars=tuple(scalars),
        inputs=ins,
        outputs=(
            OperandContract("docs", out_shape, "int32", blk_o, _driver_out_map),
            OperandContract("mask", out_shape, "int32", blk_o, _driver_out_map),
        ),
        scratch=tuple(scratch),
        revisit_dims=(2, 3),
        notes="fully-streamed read path: aligned driver window reads"
        + (", block-codec posting streams" if use_packed else ""),
    )


@kernel_contract("intersect_batched_driver_streamed")
def _contract_driver_streamed():
    return _build_driver_streamed_contract(False)


@kernel_contract("intersect_batched_driver_streamed_packed")
def _contract_driver_streamed_packed():
    return _build_driver_streamed_contract(True)


# --- work-list compacted variants ------------------------------------------
#
# The compacted contracts run in *work-list space*: the grid is the 1-D
# item index, the descriptor table is scalars[0], and every index map
# depends on prefetched descriptor columns.  ``revisit_dims=(0,)`` makes
# the alias check degenerate to the contiguity scan — exactly the builder
# invariant (items grouped by (q, i), padding clones the last real item)
# the negative fixture ``fx_worklist_missing_spare`` violates.  The
# clamp-escape check covers the descriptor no-probe sentinel: a ``-1``
# probe field remaps to tile 0, which the kernel must not consume.


def _wl_probe_intended(field):
    """Pre-remap address of :func:`_wl_probe_map` — contract only."""

    def b_map(n, *refs):
        return (refs[0][n, field], 0)

    return b_map


def _wl_field_consumed(field):
    def consumed(n, *refs):
        return bool(refs[0][n, field] >= 0)

    return consumed


def _wl_packed_probe_intended(field, woff_idx, n_blocks):
    """Pre-rows-clamp address of :func:`_wl_packed_probe_map` (descriptor
    clamps stay, as in :func:`_packed_flat_intended`)."""

    def b_map(n, *refs):
        tile = jnp.maximum(refs[0][n, field], 0)
        b0c = jnp.minimum(tile * (TILE // BLOCK), n_blocks)
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return b_map


def _wl_driver_window_intended(info_idx):
    def ad_map(n, *refs):
        q = refs[0][n, 0]
        return (_aligned(refs[info_idx][q, 0] + refs[0][n, 1] * TILE_ROWS), 0)

    return ad_map


def _wl_driver_consumed(info_idx):
    def consumed(n, *refs):
        q = refs[0][n, 0]
        return bool(refs[0][n, 1] * TILE < refs[info_idx][q, 1])

    return consumed


def _wl_packed_driver_intended(info_idx, woff_idx, n_blocks):
    def ad_map(n, *refs):
        q = refs[0][n, 0]
        b0c = jnp.minimum(
            refs[info_idx][q, 0] + refs[0][n, 1] * (TILE // BLOCK), n_blocks
        )
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return ad_map


def _wl_packed_stream_op(name, pk, field, woff_idx) -> "OperandContract":
    rows_w = pk.words.shape[0] // LANES
    live_words = int(np.asarray(pk.blk_woff)[-1])
    return OperandContract(
        name,
        (rows_w, LANES),
        "int32",
        (pk.chunk_rows, LANES),
        _wl_packed_probe_map(
            field, woff_idx, pk.n_blocks, rows_w, pk.chunk_rows
        ),
        indexing_mode=ELEMENT,
        intended_map=_wl_packed_probe_intended(field, woff_idx, pk.n_blocks),
        consumed=_wl_field_consumed(field),
        padding_from=live_words,
        spare_tile=True,
    )


def _build_streamed_compact_contract(use_packed: bool) -> KernelContract:
    from repro.core.index import DESC_PAD, pack_flat_postings
    from repro.kernels.registry import synthetic_delta_arrays

    arrays, live = synthetic_flat_index(_CANON_LISTS)
    postings = arrays["postings"]
    offsets = arrays["offsets"]
    lengths = arrays["lengths"]
    block_max = arrays["block_max"]
    delta = synthetic_delta_arrays(3, TILE, fills=(5, 0, 12))

    q_n, t_slots, window = 2, 2, TILE
    terms = np.array([[1, 2], [0, -1]], np.int32)
    active = np.array([[1, 1], [1, 0]], np.int32)
    a = np.stack(
        [
            _host_window(postings, 0, 150, window, INVALID_DOC),
            _host_window(postings, _CANON_OFF[2], 90, window, INVALID_DOC),
        ]
    )
    num_a = 1
    num_m = postings.shape[0] // TILE
    s_tiles_m = -(-window // TILE) + 1
    a_spans = _a_tile_spans(jnp.asarray(a))
    b_tile, n_b, bounds_m = _probe_plan(
        a_spans,
        jnp.asarray(terms),
        jnp.asarray(offsets),
        jnp.asarray(lengths),
        jnp.asarray(block_max),
        window=window,
        s_tiles=s_tiles_m,
    )
    s_grid = _clamp_s_max(None, s_tiles_m)
    n_b = np.minimum(np.asarray(n_b), s_grid) * active[:, :, None]

    d_off, d_len, d_bm = (
        delta["d_offsets"],
        delta["d_lengths"],
        delta["d_block_max"],
    )
    cap = d_bm.shape[0] * BLOCK // d_off.shape[0]
    num_d = delta["d_postings"].shape[0] // TILE
    s_tiles_d = -(-cap // TILE) + 1
    d_tile, n_d, bounds_d = _probe_plan(
        a_spans,
        jnp.asarray(terms),
        jnp.asarray(d_off),
        jnp.asarray(d_len),
        jnp.asarray(d_bm),
        window=cap,
        s_tiles=s_tiles_d,
    )
    s_grid = max(s_grid, _clamp_s_max(None, s_tiles_d))
    n_d = np.minimum(np.asarray(n_d), s_grid) * active[:, :, None]

    wl = build_intersect_worklist(
        n_b, np.asarray(b_tile), active, np.asarray(a_spans[2]),
        n_d=n_d, d_tile=np.asarray(d_tile),
        kernel="contract", dense_steps=q_n * num_a * t_slots * s_grid,
    )
    scalars = [
        wl.desc,
        np.asarray(bounds_m),
        np.asarray(bounds_d),
        _attr_params(np.array([-1, -1], np.int32)),
    ]
    blk_a = (1, TILE_ROWS, LANES)
    tile = (TILE_ROWS, LANES)
    a_shape = (q_n, num_a * TILE_ROWS, LANES)
    ins = [
        OperandContract(nm, a_shape, "int32", blk_a, _wl_block_map)
        for nm in ("a_docs", "a_attrs", "a_live", "a_flags")
    ]
    if use_packed:
        pk_m = pack_flat_postings(arrays["postings"])
        pk_d = pack_flat_postings(
            delta["d_postings"], span_blocks=max(DESC_PAD, cap // BLOCK)
        )
        woff_m, woff_d = 6, 9
        for pk in (pk_m, pk_d):
            scalars += [
                np.asarray(pk.blk_base),
                np.asarray(pk.blk_meta),
                np.asarray(pk.blk_woff),
            ]
        ins.append(_wl_packed_stream_op("packed_words(main)", pk_m, 3, woff_m))
        ins.append(_wl_packed_stream_op("packed_words(delta)", pk_d, 5, woff_d))
    else:
        ins.append(
            OperandContract(
                "postings",
                (num_m * TILE_ROWS, LANES),
                "int32",
                tile,
                _wl_probe_map(3, num_m),
                intended_map=_wl_probe_intended(3),
                consumed=_wl_field_consumed(3),
                padding_from=live,
            )
        )
        ins.append(
            OperandContract(
                "d_postings",
                (num_d * TILE_ROWS, LANES),
                "int32",
                tile,
                _wl_probe_map(5, num_d),
                intended_map=_wl_probe_intended(5),
                consumed=_wl_field_consumed(5),
                padding_from=int(cap * d_off.shape[0]),
            )
        )
    suffix = "_packed" if use_packed else ""
    return KernelContract(
        name="intersect_batched_streamed_compact" + suffix,
        site=site_of(intersect_batched_streamed_compact),
        grid=(wl.desc.shape[0],),
        scalars=tuple(scalars),
        inputs=tuple(ins),
        outputs=(
            OperandContract("mask", a_shape, "int32", blk_a, _wl_block_map),
        ),
        scratch=(((TILE_ROWS, LANES), "int32"), ((TILE_ROWS, LANES), "int32")),
        revisit_dims=(0,),
        notes="work-list compacted merge-on-read configuration"
        + (", block-codec probe streams" if use_packed else ""),
    )


@kernel_contract("intersect_batched_streamed_compact")
def _contract_intersect_streamed_compact():
    return _build_streamed_compact_contract(False)


@kernel_contract("intersect_batched_streamed_compact_packed")
def _contract_intersect_streamed_compact_packed():
    return _build_streamed_compact_contract(True)


def _build_driver_compact_contract(use_packed: bool) -> KernelContract:
    from repro.core.index import pack_flat_postings

    arrays, live = synthetic_flat_index(_CANON_LISTS)
    offsets = arrays["offsets"]
    lengths = arrays["lengths"]
    block_max = arrays["block_max"]
    num_m = arrays["postings"].shape[0] // TILE
    rows_total = num_m * TILE_ROWS

    # Same canonical instance as the dense driver-streamed contract: the
    # edge list's second window tile still forces the clamp path.
    q_n, t_slots, window = 2, 2, 2 * TILE
    d_off = np.array([0, _CANON_OFF[2]], np.int32)
    d_neff = np.array([150, 90], np.int32)
    terms = np.array([[1, 2], [0, -1]], np.int32)
    active = np.array([[1, 1], [1, 0]], np.int32)

    num_a = -(-window // TILE)
    a_spans = jax.vmap(
        functools.partial(
            driver_tile_spans, jnp.asarray(block_max), s_tiles=num_a
        )
    )(jnp.asarray(d_off), jnp.asarray(d_neff))
    s_tiles_b = -(-window // TILE) + 1
    b_tile, n_b, bounds = _probe_plan(
        a_spans,
        jnp.asarray(terms),
        jnp.asarray(offsets),
        jnp.asarray(lengths),
        jnp.asarray(block_max),
        window=window,
        s_tiles=s_tiles_b,
    )
    s_grid = _clamp_s_max(None, s_tiles_b)
    n_b = np.minimum(np.asarray(n_b), s_grid) * active[:, :, None]
    wl = build_intersect_worklist(
        n_b, np.asarray(b_tile), active, np.asarray(a_spans[2]),
        kernel="contract", dense_steps=q_n * num_a * t_slots * s_grid,
    )
    a_info = np.stack([d_off // LANES, d_neff], axis=-1).astype(np.int32)
    scalars = [
        wl.desc,
        np.asarray(bounds),
        _attr_params(np.array([-1, -1], np.int32)),
        a_info,
    ]

    tile = (TILE_ROWS, LANES)
    win = (WINDOW_READ_ROWS, LANES)
    flat_shape = (rows_total, LANES)
    out_shape = (q_n, num_a * TILE_ROWS, LANES)
    stream_kw = dict(
        indexing_mode=ELEMENT,
        intended_map=_wl_driver_window_intended(3),
        consumed=_wl_driver_consumed(3),
        padding_from=live,
        spare_tile=True,
        spare_elems=TILE,
    )
    if use_packed:
        pk = pack_flat_postings(arrays["postings"])
        scalars += [
            np.asarray(pk.blk_base),
            np.asarray(pk.blk_meta),
            np.asarray(pk.blk_woff),
        ]
        rows_w = pk.words.shape[0] // LANES
        live_words = int(np.asarray(pk.blk_woff)[-1])
        ins = (
            OperandContract(
                "packed_words(driver)",
                (rows_w, LANES),
                "int32",
                (pk.chunk_rows, LANES),
                _wl_packed_driver_map(3, 6, pk.n_blocks, rows_w, pk.chunk_rows),
                indexing_mode=ELEMENT,
                intended_map=_wl_packed_driver_intended(3, 6, pk.n_blocks),
                consumed=_wl_driver_consumed(3),
                padding_from=live_words,
                spare_tile=True,
            ),
            OperandContract(
                "attrs(driver)",
                flat_shape,
                "int32",
                win,
                _wl_driver_window_map(rows_total, 3),
                **stream_kw,
            ),
            _wl_packed_stream_op("packed_words(probe)", pk, 3, 6),
        )
    else:
        ins = (
            OperandContract(
                "postings(driver)",
                flat_shape,
                "int32",
                win,
                _wl_driver_window_map(rows_total, 3),
                **stream_kw,
            ),
            OperandContract(
                "attrs(driver)",
                flat_shape,
                "int32",
                win,
                _wl_driver_window_map(rows_total, 3),
                **stream_kw,
            ),
            OperandContract(
                "postings(probe)",
                flat_shape,
                "int32",
                tile,
                _wl_probe_map(3, num_m),
                intended_map=_wl_probe_intended(3),
                consumed=_wl_field_consumed(3),
                padding_from=live,
            ),
        )
    blk_o = (1, TILE_ROWS, LANES)
    scratch = [((TILE_ROWS, LANES), "int32")]
    if use_packed:
        scratch.append(((TILE_ROWS, LANES), "int32"))
    suffix = "_packed" if use_packed else ""
    return KernelContract(
        name="intersect_batched_driver_streamed_compact" + suffix,
        site=site_of(intersect_batched_driver_streamed_compact),
        grid=(wl.desc.shape[0],),
        scalars=tuple(scalars),
        inputs=ins,
        outputs=(
            OperandContract("docs", out_shape, "int32", blk_o, _wl_block_map),
            OperandContract("mask", out_shape, "int32", blk_o, _wl_block_map),
        ),
        scratch=tuple(scratch),
        revisit_dims=(0,),
        notes="work-list compacted fully-streamed read path"
        + (", block-codec posting streams" if use_packed else ""),
    )


@kernel_contract("intersect_batched_driver_streamed_compact")
def _contract_driver_compact():
    return _build_driver_compact_contract(False)


@kernel_contract("intersect_batched_driver_streamed_compact_packed")
def _contract_driver_compact_packed():
    return _build_driver_compact_contract(True)
