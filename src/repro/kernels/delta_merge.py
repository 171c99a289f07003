"""Pallas TPU kernel: in-VMEM merge of main and delta posting streams.

Merge-on-read (:mod:`repro.indexing`) makes every driver window the merge
of the term's *main* window and its *delta* slab.  The original data path
realized that merge host-side — a jnp ``argsort`` over ``window + cap``
keys per (query, term) — which is exactly the kind of extra pass the
paper's slave cost model (§4, Formula (7)) has no term for.  This kernel
does the merge where the data already is, and reads both inputs where
*they* already are:

- the **main window is streamed straight from the flat posting arrays**:
  per-query window offsets (``m_off``/``m_neff``, from the engine's
  PostingSource layer) are scalar-prefetched and an Element-indexed
  BlockSpec walks the window tile-by-tile, reading the two aligned tiles
  that cover each window tile (:func:`repro.kernels.posting_intersect.
  window_read`) — the former ``(Q, window)`` host-side driver gather no
  longer exists.  Each tile is masked to the window's live range by its
  *intended* position, so the spare INVALID tile every flat array carries
  (:func:`repro.core.index.flat_tile_pad`) makes edge-clamped reads
  provably harmless;
- both inputs are sorted (the main window ascending by construction, the
  delta slab ascending per list), so the merge is a single **bitonic merge
  pass** — ``log2(N)`` data-independent compare-exchange stages over the
  concatenation of the main stream and the *reversed* delta stream (an
  ascending-then-descending, i.e. bitonic, sequence) — not a full
  ``O(log^2 N)`` sort.  The network runs on ``(rows, 128)`` VMEM values
  with lane and sublane rotates (:mod:`repro.kernels.bitonic`);
- the delta slab is **streamed straight from the flat delta arrays** via a
  scalar-prefetched slab index in the BlockSpec index map (no per-query
  gather of delta postings), read as whole sublane tiles from the tile
  boundary at or before the slab's first row;
- the delta's **block-max skip table** is read per query: a slab whose
  occupied-block count is zero short-circuits the whole network to a
  copy-through (at 0% fill the merge costs one VMEM copy);
- each merged slot's **stream id** (``src``: 0 = main, 1 = delta/pad)
  rides through the exchanges as a payload and is emitted alongside the
  docIDs.  The caller derives per-posting liveness from it with one
  elementwise op over the ``doc_flags`` bits it already fetches for the
  join kernel (a main posting dies when its doc is deleted or superseded,
  a delta posting only on delete) — the tombstone semantics of
  :func:`repro.core.engine.merged_term_window` without the kernel ever
  needing a pre-gathered live stream.

Ties (a doc updated in place has a dead main posting *and* a live delta
posting with the same docID) break by stream id (main first), matching the
stable host-side sort this kernel replaces; see
:func:`repro.core.engine.merged_term_window`, which remains the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.index import (
    BLOCK,
    DESC_PAD,
    INVALID_ATTR,
    INVALID_DOC,
    TILE,
    PackedFlatArrays,
    pack_flat_postings,
)
from repro.kernels import bitonic
from repro.kernels.posting_intersect import (
    _CANON_LISTS,
    _CANON_OFF,
    LANES,
    TILE_ROWS,
    WINDOW_READ_ROWS,
    _decode_span,
    _packed_row0,
    _tile_positions,
    element_spec,
    window_read,
)
from repro.kernels.worklist import (
    FLAG_LAST,
    build_merge_worklist,
)

# Slab addressing below (cap_rows = cap // LANES with BLOCK-aligned caps)
# relies on one lane row being exactly one skip-table block.
assert LANES == BLOCK


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _slab_geometry(cap: int) -> tuple[int, int, int, int]:
    """Row geometry of a delta slab of ``cap`` postings:
    ``(cap_rows, load_rows, read_rows, net_rows)``.

    ``load_rows`` is ``cap_rows`` rounded up to the sublane tile: the
    kernel loads that many rows from the slab's row shift inside an
    aligned ``read_rows = load_rows + TILE_ROWS`` read, so the load always
    fits.  ``net_rows`` pads the load to a power of two for the merge
    network's reversal stage.
    """
    cap_rows = cap // LANES
    load_rows = -(-cap_rows // TILE_ROWS) * TILE_ROWS
    return cap_rows, load_rows, load_rows + TILE_ROWS, _next_pow2(load_rows)


def _slab_read(slab_row, rows_d: int, read_rows: int):
    """``(start, shift)`` of the aligned ``read_rows``-row read holding the
    slab that starts at ``slab_row``.  The clamp never engages: the spare
    tile past the last slab leaves room for the read of any slab start
    rounded down to a tile (the contract checker verifies it)."""
    start = (
        jnp.minimum(slab_row, rows_d - read_rows) // TILE_ROWS
    ) * TILE_ROWS
    return start, jnp.minimum(slab_row - start, TILE_ROWS)


def _pad_rows(x, rows: int, fill):
    if x.shape[0] == rows:
        return x
    return jnp.concatenate(
        [x, jnp.full((rows - x.shape[0], LANES), fill, x.dtype)], axis=0
    )


# BlockSpec index maps — module-level so the contract checker
# (repro.analysis, via the registry at the bottom of this file) evaluates
# the exact same code the pallas_call runs, never a re-derivation.


def _main_window_map(rows_total):
    def m_map(q, j, minfo_ref, slab_ref, len_ref, occ_ref, *_):
        # The aligned read covering window tile j; clamped at the array
        # edge (spare-tile invariant keeps clamped tiles masked).
        return (window_read(minfo_ref[q, 0] + j * TILE_ROWS, rows_total)[0], 0)

    return m_map


def _slab_map(rows_d, read_rows, cap_rows):
    def d_map(q, j, minfo_ref, slab_ref, len_ref, occ_ref, *_):
        # empty slabs pin to slab 0: the copy-through never reads the
        # operand, and consecutive skipped queries coalesce onto one
        # already-resident block instead of one slab DMA each
        slab = jnp.where(occ_ref[q] == 0, 0, slab_ref[q])
        return (_slab_read(slab * cap_rows, rows_d, read_rows)[0], 0)

    return d_map


def _merge_out_map(q, j, minfo_ref, slab_ref, len_ref, occ_ref, *_):
    return (q, 0, 0)


def _packed_window_map(woff_idx, n_blocks, rows_w, chunk_rows):
    """Chunk row of the packed words holding window tile ``j``'s span.

    ``minfo[q, 0]`` is the window's start row, which with LANES == BLOCK
    is also its start *block*; clamping the block index into the
    descriptor pad keeps every read in packed bounds (the spare packed
    chunk makes the edge rows-clamp provably inert).
    """

    def m_map(q, j, *refs):
        b0c = jnp.minimum(refs[0][q, 0] + j * TILE_ROWS, n_blocks)
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return m_map


def _packed_slab_map(woff_idx, bpt, n_blocks, rows_w, chunk_rows):
    """Chunk row of the packed delta words holding query ``q``'s slab."""

    def d_map(q, j, *refs):
        b0 = jnp.where(refs[3][q] == 0, 0, refs[1][q]) * bpt
        b0c = jnp.minimum(b0, n_blocks)
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return d_map


def _main_tile(q, j, minfo_ref, mp_ref, ma_ref, *, rows_total, packed_m,
               desc_m):
    """Window tile ``j`` of query ``q``'s main window: (docs, attrs), each
    (8, 128), masked to the live range by intended position."""
    row = minfo_ref[q, 0] + j * TILE_ROWS
    _, shift = window_read(row, rows_total)
    in_win = _tile_positions(j) < minfo_ref[q, 1]
    if packed_m is not None:
        n_bm, rows_wm, cr_m = packed_m
        mba_ref, mme_ref, mwo_ref = desc_m
        b0c = jnp.minimum(row, n_bm)
        row0 = _packed_row0(mwo_ref, b0c, rows_wm, cr_m)
        m_tile = _decode_span(
            mp_ref[...], mba_ref, mme_ref, mwo_ref, b0c, row0, TILE_ROWS
        )
    else:
        m_tile = mp_ref[pl.ds(shift, TILE_ROWS), :]
    return (
        jnp.where(in_win, m_tile, INVALID_DOC),
        jnp.where(in_win, ma_ref[pl.ds(shift, TILE_ROWS), :], INVALID_ATTR),
    )


def _delta_slab(q, slab_ref, occ_ref, dp_ref, da_ref, *, cap, rows_d,
                packed_d, desc_d):
    """Query ``q``'s delta slab as (docs, attrs), each (net_rows, 128):
    the slab's postings first, then whatever follows it (masked by the
    caller through the slab length)."""
    cap_rows, load_rows, read_rows, net_rows = _slab_geometry(cap)
    slab = jnp.where(occ_ref[q] == 0, 0, slab_ref[q])
    _, shift = _slab_read(slab * cap_rows, rows_d, read_rows)
    da = da_ref[pl.ds(shift, load_rows), :]
    if packed_d is not None:
        n_bd, rows_wd, cr_d = packed_d
        dba_ref, dme_ref, dwo_ref = desc_d
        # Same address arithmetic as _packed_slab_map so the decode
        # offsets match the chunk the BlockSpec actually loaded.
        b0d = jnp.minimum(slab * cap_rows, n_bd)
        row0d = _packed_row0(dwo_ref, b0d, rows_wd, cr_d)
        dd = _pad_rows(
            _decode_span(
                dp_ref[...], dba_ref, dme_ref, dwo_ref, b0d, row0d, cap_rows
            ),
            load_rows,
            INVALID_DOC,
        )
    else:
        dd = dp_ref[pl.ds(shift, load_rows), :]
    return (
        _pad_rows(dd, net_rows, INVALID_DOC),
        _pad_rows(da, net_rows, INVALID_ATTR),
    )


def _merged_window(md, ma, dd, da, d_len, n_rows: int):
    """Bitonic merge of the main window ``md``/``ma`` ((out_rows, 128),
    ascending, INVALID past its live range) with the delta slab
    ``dd``/``da`` ((net_rows, 128), ascending in its first ``d_len``
    slots).  Returns the first ``out_rows`` rows of the merged
    (docs, attrs, src)."""
    out_rows, net_rows = md.shape[0], dd.shape[0]
    d_valid = bitonic.flat_index(dd.shape) < d_len
    dd = jnp.where(d_valid, dd, INVALID_DOC)
    da = jnp.where(d_valid, da, INVALID_ATTR)
    pad = n_rows - out_rows - net_rows

    def layout(main, delta, fill):
        # ascending main ++ pad ++ descending delta = bitonic
        parts = [main]
        if pad:
            parts.append(jnp.full((pad, LANES), fill, jnp.int32))
        parts.append(bitonic.reverse(delta))
        return jnp.concatenate(parts, axis=0)

    key = layout(md, dd, INVALID_DOC)
    attr = layout(ma, da, INVALID_ATTR)
    row = jax.lax.broadcasted_iota(jnp.int32, key.shape, 0)
    src = (row >= out_rows).astype(jnp.int32)
    key, src, (attr,) = bitonic.merge(key, src, (attr,))
    return key[:out_rows], attr[:out_rows], src[:out_rows]


def _merge_kernel(
    # raw refs: [minfo, slab, d_len, d_occ] scalars, then
    #   mp (16,128) aligned window read / ma the same read of the attrs /
    #   dp (read_rows,128) aligned slab read / da the same for attrs,
    #   od/oa/os (1,out_rows,128) outputs, sd/sa (out_rows,128) scratch.
    # packed mode appends six descriptor scalars
    #   [m_base, m_meta, m_woff, d_base, d_meta, d_woff]
    # and mp/dp become packed-word chunks (chunk_rows, 128); attrs stay raw.
    *refs,
    cap: int,
    n_rows: int,
    s_w: int,
    rows_total: int,
    rows_d: int,
    packed_m=None,  # (n_blocks, rows_w, chunk_rows) of the main words
    packed_d=None,  # same for the delta words
):
    desc_m = desc_d = None
    if packed_m is not None:
        (
            minfo_ref, slab_ref, len_ref, occ_ref,
            mba_ref, mme_ref, mwo_ref, dba_ref, dme_ref, dwo_ref,
            mp_ref, ma_ref, dp_ref, da_ref,
            od_ref, oa_ref, os_ref, sd_ref, sa_ref,
        ) = refs
        desc_m = (mba_ref, mme_ref, mwo_ref)
        desc_d = (dba_ref, dme_ref, dwo_ref)
    else:
        (
            minfo_ref, slab_ref, len_ref, occ_ref,
            mp_ref, ma_ref, dp_ref, da_ref,
            od_ref, oa_ref, os_ref, sd_ref, sa_ref,
        ) = refs

    q = pl.program_id(0)
    j = pl.program_id(1)

    # Accumulate this window tile into scratch, masked to the live range by
    # its intended window position (tiles are window-aligned): a clamped
    # edge read can only affect fully-masked slots (spare-tile invariant).
    m_tile, a_tile = _main_tile(
        q, j, minfo_ref, mp_ref, ma_ref,
        rows_total=rows_total, packed_m=packed_m, desc_m=desc_m,
    )
    rows = pl.ds(pl.multiple_of(j * TILE_ROWS, TILE_ROWS), TILE_ROWS)
    sd_ref[rows, :] = m_tile
    sa_ref[rows, :] = a_tile

    # Skip-table short-circuit: an empty slab merges to the main window.
    @pl.when((j == s_w - 1) & (occ_ref[q] == 0))
    def _copy_through():
        od_ref[0] = sd_ref[...]
        oa_ref[0] = sa_ref[...]
        os_ref[0] = jnp.zeros_like(os_ref[0])

    @pl.when((j == s_w - 1) & (occ_ref[q] != 0))
    def _merge():
        dd, da = _delta_slab(
            q, slab_ref, occ_ref, dp_ref, da_ref,
            cap=cap, rows_d=rows_d, packed_d=packed_d, desc_d=desc_d,
        )
        key, attr, src = _merged_window(
            sd_ref[...], sa_ref[...], dd, da, len_ref[q], n_rows
        )
        od_ref[0] = key
        oa_ref[0] = attr
        os_ref[0] = src


def _merge_scalars(d_offsets, d_lengths, d_block_max, terms, m_off, m_neff):
    """The four per-query scalar-prefetch operands every merge variant
    shares: [minfo, slab, d_len, d_occ]."""
    n_terms = d_offsets.shape[0]
    cap = d_block_max.shape[0] * BLOCK // n_terms
    bpt = cap // BLOCK
    tt = jnp.clip(terms, 0, n_terms - 1)
    slab = jnp.take(d_offsets, tt) // cap
    d_len = jnp.where(terms < 0, 0, jnp.take(d_lengths, tt))
    occ_per_term = jnp.sum(
        d_block_max.reshape(n_terms, bpt) != INVALID_DOC, axis=1
    ).astype(jnp.int32)
    d_occ = jnp.where(terms < 0, 0, jnp.take(occ_per_term, tt))
    minfo = jnp.stack(
        [m_off.astype(jnp.int32) // LANES, m_neff.astype(jnp.int32)], axis=-1
    )
    return [minfo, slab, d_len, d_occ]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def merge_delta_windows(
    postings: jnp.ndarray,     # int32[P] flat main postings (TILE-pad + spare)
    attrs: jnp.ndarray,        # int32[P] flat main attrs (same layout)
    m_off: jnp.ndarray,        # int32[Q] driver window start (BLOCK-aligned)
    m_neff: jnp.ndarray,       # int32[Q] live main postings (<= window)
    d_postings: jnp.ndarray,   # int32[D] flat delta postings (TILE-padded)
    d_attrs: jnp.ndarray,      # int32[D] flat delta attrs
    d_offsets: jnp.ndarray,    # int32[n_terms]
    d_lengths: jnp.ndarray,    # int32[n_terms]
    d_block_max: jnp.ndarray,  # int32[n_terms * cap / BLOCK] skip table
    terms: jnp.ndarray,        # int32[Q] driver term per query
    *,
    window: int,
    packed: PackedFlatArrays | None = None,
    d_packed: PackedFlatArrays | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merged (docs, attrs, src) driver windows, each int32[Q, window].

    Both inputs stream from their flat arrays: the main window through
    aligned Element reads at the scalar-prefetched per-query offset
    (``m_off``/``m_neff`` — no host-side ``(Q, window)`` gather), the delta
    slab through its prefetched slab index.  ``src`` is each slot's stream
    id (0 = main, 1 = delta or padding); combined with the ``doc_flags``
    tombstone bits the caller turns it into the per-posting live stream:
    ``live = (docs != INVALID) & (src == 0 ? not dead|superseded : not
    dead)``.  That reproduces :func:`repro.core.engine.merged_term_window`
    with ``drop_dead=False`` on (docs, live) exactly; attrs are guaranteed
    only where ``docs != INVALID_DOC`` (padding slots may carry junk
    attributes in a different — equally dead — order than the host sort
    produces).
    """
    q_n = terms.shape[0]
    n_terms = d_offsets.shape[0]
    cap = d_block_max.shape[0] * BLOCK // n_terms
    bpt = cap // BLOCK
    assert postings.shape[0] % TILE == 0, "main postings must be TILE-padded"
    assert d_postings.shape[0] % TILE == 0, "delta must be TILE-padded"
    rows_total = postings.shape[0] // LANES
    rows_d = d_postings.shape[0] // LANES
    cap_rows, _, read_rows, net_rows = _slab_geometry(cap)

    # Tile-pad the window to whole (8, 128) reads; the pad slots carry
    # INVALID keys, which sort last, so merging the padded stream and
    # truncating back to ``window`` is exact for any odd window size.
    s_w = -(-window // TILE)
    out_w = s_w * TILE
    out_rows = s_w * TILE_ROWS
    n_rows = _next_pow2(out_w + net_rows * LANES) // LANES

    if (packed is None) != (d_packed is None):
        raise ValueError(
            "merge_delta_windows: packed and d_packed go together"
        )

    ma2 = attrs.reshape(rows_total, LANES)
    da2 = d_attrs.reshape(-1, LANES)
    m_map = _main_window_map(rows_total)
    d_map = _slab_map(rows_d, read_rows, cap_rows)

    scalars = _merge_scalars(
        d_offsets, d_lengths, d_block_max, terms, m_off, m_neff
    )
    pk_m = pk_d = None
    if packed is not None:
        # Descriptor scalars ride after the raw four so every existing
        # scalar index (and the raw maps' signatures) stays valid.
        scalars += [
            packed.blk_base, packed.blk_meta, packed.blk_woff,
            d_packed.blk_base, d_packed.blk_meta, d_packed.blk_woff,
        ]
        words_m2 = packed.words.reshape(-1, LANES)
        words_d2 = d_packed.words.reshape(-1, LANES)
        pk_m = (packed.n_blocks, words_m2.shape[0], packed.chunk_rows)
        pk_d = (d_packed.n_blocks, words_d2.shape[0], d_packed.chunk_rows)
        in_specs = [
            element_spec(packed.chunk_rows, _packed_window_map(6, *pk_m)),
            element_spec(WINDOW_READ_ROWS, m_map),
            element_spec(
                d_packed.chunk_rows, _packed_slab_map(9, bpt, *pk_d)
            ),
            element_spec(read_rows, d_map),
        ]
        operands = [words_m2, ma2, words_d2, da2]
    else:
        mp2 = postings.reshape(rows_total, LANES)
        dp2 = d_postings.reshape(-1, LANES)
        in_specs = [
            element_spec(WINDOW_READ_ROWS, m_map),
            element_spec(WINDOW_READ_ROWS, m_map),
            element_spec(read_rows, d_map),
            element_spec(read_rows, d_map),
        ]
        operands = [mp2, ma2, dp2, da2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(q_n, s_w),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, out_rows, LANES), _merge_out_map),
            pl.BlockSpec((1, out_rows, LANES), _merge_out_map),
            pl.BlockSpec((1, out_rows, LANES), _merge_out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((out_rows, LANES), jnp.int32),
            pltpu.VMEM((out_rows, LANES), jnp.int32),
        ],
    )
    shape = jax.ShapeDtypeStruct((q_n, out_rows, LANES), jnp.int32)
    docs, oattrs, src = pl.pallas_call(
        functools.partial(
            _merge_kernel,
            cap=cap,
            n_rows=n_rows,
            s_w=s_w,
            rows_total=rows_total,
            rows_d=rows_d,
            packed_m=pk_m,
            packed_d=pk_d,
        ),
        grid_spec=grid_spec,
        out_shape=[shape, shape, shape],
        interpret=interpret,
    )(*scalars, *operands)
    def unroll(x):
        return x.reshape(q_n, -1)[:, :window]

    return unroll(docs), unroll(oattrs), unroll(src)


# ---------------------------------------------------------------------------
# Work-list compacted variant: a 1-D grid over live (query, window-tile)
# items (repro.kernels.worklist) — zero grid steps for inert padding
# queries; window tiles past a query's live main range are never swept.
# ---------------------------------------------------------------------------


def _wl_main_window_map(rows_total):
    def m_map(n, desc_ref, minfo_ref, *_):
        row = minfo_ref[desc_ref[n, 0], 0] + desc_ref[n, 1] * TILE_ROWS
        return (window_read(row, rows_total)[0], 0)

    return m_map


def _wl_slab_map(rows_d, read_rows, cap_rows):
    def d_map(n, desc_ref, minfo_ref, slab_ref, len_ref, occ_ref, *_):
        q = desc_ref[n, 0]
        slab = jnp.where(occ_ref[q] == 0, 0, slab_ref[q])
        return (_slab_read(slab * cap_rows, rows_d, read_rows)[0], 0)

    return d_map


def _wl_merge_out_map(n, desc_ref, *_):
    return (desc_ref[n, 0], 0, 0)


def _wl_packed_window_map(woff_idx, n_blocks, rows_w, chunk_rows):
    def m_map(n, *refs):
        q = refs[0][n, 0]
        b0c = jnp.minimum(refs[1][q, 0] + refs[0][n, 1] * TILE_ROWS, n_blocks)
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return m_map


def _wl_packed_slab_map(woff_idx, bpt, n_blocks, rows_w, chunk_rows):
    def d_map(n, *refs):
        q = refs[0][n, 0]
        b0 = jnp.where(refs[4][q] == 0, 0, refs[2][q]) * bpt
        b0c = jnp.minimum(b0, n_blocks)
        return (_packed_row0(refs[woff_idx], b0c, rows_w, chunk_rows), 0)

    return d_map


def _merge_compact_kernel(
    # Work-list twin of _merge_kernel.  Scalar order: wl (descriptor
    # table), then the dense four [minfo, slab, d_len, d_occ], then (packed
    # mode) the six codec descriptors.  One grid step per live window tile;
    # FLAG_LAST replaces the dense (j == s_w - 1) edge.  Scratch rows this
    # work list never wrote (tiles past the live range, skipped entirely)
    # may hold a previous query's data, so the merge/copy-through applies a
    # full-extent live mask at consume time — reproducing the dense
    # kernel's all-tiles in_win writes bit-exactly.
    *refs,
    cap: int,
    n_rows: int,
    rows_total: int,
    rows_d: int,
    packed_m=None,
    packed_d=None,
):
    desc_m = desc_d = None
    if packed_m is not None:
        (
            wl_ref, minfo_ref, slab_ref, len_ref, occ_ref,
            mba_ref, mme_ref, mwo_ref, dba_ref, dme_ref, dwo_ref,
            mp_ref, ma_ref, dp_ref, da_ref,
            od_ref, oa_ref, os_ref, sd_ref, sa_ref,
        ) = refs
        desc_m = (mba_ref, mme_ref, mwo_ref)
        desc_d = (dba_ref, dme_ref, dwo_ref)
    else:
        (
            wl_ref, minfo_ref, slab_ref, len_ref, occ_ref,
            mp_ref, ma_ref, dp_ref, da_ref,
            od_ref, oa_ref, os_ref, sd_ref, sa_ref,
        ) = refs

    n = pl.program_id(0)
    q = wl_ref[n, 0]
    j = wl_ref[n, 1]
    flags = wl_ref[n, 4]

    m_tile, a_tile = _main_tile(
        q, j, minfo_ref, mp_ref, ma_ref,
        rows_total=rows_total, packed_m=packed_m, desc_m=desc_m,
    )
    rows = pl.ds(pl.multiple_of(j * TILE_ROWS, TILE_ROWS), TILE_ROWS)
    sd_ref[rows, :] = m_tile
    sa_ref[rows, :] = a_tile

    def _live_main():
        live = bitonic.flat_index(sd_ref.shape) < minfo_ref[q, 1]
        return (
            jnp.where(live, sd_ref[...], INVALID_DOC),
            jnp.where(live, sa_ref[...], INVALID_ATTR),
        )

    @pl.when(((flags & FLAG_LAST) != 0) & (occ_ref[q] == 0))
    def _copy_through():
        od_ref[0], oa_ref[0] = _live_main()
        os_ref[0] = jnp.zeros_like(os_ref[0])

    @pl.when(((flags & FLAG_LAST) != 0) & (occ_ref[q] != 0))
    def _merge():
        md, ma = _live_main()
        dd, da = _delta_slab(
            q, slab_ref, occ_ref, dp_ref, da_ref,
            cap=cap, rows_d=rows_d, packed_d=packed_d, desc_d=desc_d,
        )
        key, attr, src = _merged_window(md, ma, dd, da, len_ref[q], n_rows)
        od_ref[0] = key
        oa_ref[0] = attr
        os_ref[0] = src


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _merge_compact_call(
    desc,
    postings, attrs, m_off, m_neff,
    d_postings, d_attrs, d_offsets, d_lengths, d_block_max, terms,
    live_q=None,
    *,
    window: int,
    packed: PackedFlatArrays | None = None,
    d_packed: PackedFlatArrays | None = None,
    interpret: bool = False,
):
    q_n = terms.shape[0]
    n_terms = d_offsets.shape[0]
    cap = d_block_max.shape[0] * BLOCK // n_terms
    bpt = cap // BLOCK
    rows_total = postings.shape[0] // LANES
    rows_d = d_postings.shape[0] // LANES
    cap_rows, _, read_rows, net_rows = _slab_geometry(cap)
    n_steps = desc.shape[0]

    s_w = -(-window // TILE)
    out_w = s_w * TILE
    out_rows = s_w * TILE_ROWS
    n_rows = _next_pow2(out_w + net_rows * LANES) // LANES

    ma2 = attrs.reshape(rows_total, LANES)
    da2 = d_attrs.reshape(-1, LANES)
    m_map = _wl_main_window_map(rows_total)
    d_map = _wl_slab_map(rows_d, read_rows, cap_rows)
    scalars = [desc] + _merge_scalars(
        d_offsets, d_lengths, d_block_max, terms, m_off, m_neff
    )
    pk_m = pk_d = None
    if packed is not None:
        scalars += [
            packed.blk_base, packed.blk_meta, packed.blk_woff,
            d_packed.blk_base, d_packed.blk_meta, d_packed.blk_woff,
        ]
        words_m2 = packed.words.reshape(-1, LANES)
        words_d2 = d_packed.words.reshape(-1, LANES)
        pk_m = (packed.n_blocks, words_m2.shape[0], packed.chunk_rows)
        pk_d = (d_packed.n_blocks, words_d2.shape[0], d_packed.chunk_rows)
        in_specs = [
            element_spec(packed.chunk_rows, _wl_packed_window_map(7, *pk_m)),
            element_spec(WINDOW_READ_ROWS, m_map),
            element_spec(
                d_packed.chunk_rows, _wl_packed_slab_map(10, bpt, *pk_d)
            ),
            element_spec(read_rows, d_map),
        ]
        operands = [words_m2, ma2, words_d2, da2]
    else:
        mp2 = postings.reshape(rows_total, LANES)
        dp2 = d_postings.reshape(-1, LANES)
        in_specs = [
            element_spec(WINDOW_READ_ROWS, m_map),
            element_spec(WINDOW_READ_ROWS, m_map),
            element_spec(read_rows, d_map),
            element_spec(read_rows, d_map),
        ]
        operands = [mp2, ma2, dp2, da2]

    blk_o = pl.BlockSpec((1, out_rows, LANES), _wl_merge_out_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=[blk_o, blk_o, blk_o],
        scratch_shapes=[
            pltpu.VMEM((out_rows, LANES), jnp.int32),
            pltpu.VMEM((out_rows, LANES), jnp.int32),
        ],
    )
    shape = jax.ShapeDtypeStruct((q_n, out_rows, LANES), jnp.int32)
    docs, oattrs, src = pl.pallas_call(
        functools.partial(
            _merge_compact_kernel,
            cap=cap,
            n_rows=n_rows,
            rows_total=rows_total,
            rows_d=rows_d,
            packed_m=pk_m,
            packed_d=pk_d,
        ),
        grid_spec=grid_spec,
        out_shape=[shape, shape, shape],
        interpret=interpret,
    )(*scalars, *operands)

    def unroll(x):
        return x.reshape(q_n, -1)[:, :window]

    docs, oattrs, src = unroll(docs), unroll(oattrs), unroll(src)
    if live_q is not None:
        lq = live_q[:, None]
        docs = jnp.where(lq, docs, INVALID_DOC)
        oattrs = jnp.where(lq, oattrs, INVALID_ATTR)
        src = jnp.where(lq, src, 1)
    return docs, oattrs, src


def merge_delta_windows_compact(
    postings: jnp.ndarray,
    attrs: jnp.ndarray,
    m_off: jnp.ndarray,
    m_neff: jnp.ndarray,
    d_postings: jnp.ndarray,
    d_attrs: jnp.ndarray,
    d_offsets: jnp.ndarray,
    d_lengths: jnp.ndarray,
    d_block_max: jnp.ndarray,
    terms: jnp.ndarray,
    *,
    window: int,
    packed: PackedFlatArrays | None = None,
    d_packed: PackedFlatArrays | None = None,
    interpret: bool = False,
    live_q=None,
):
    """Work-list compacted :func:`merge_delta_windows`.

    Same arguments and bit-identical ``(docs, attrs, src)``, plus
    ``live_q`` (host bool[Q]; ``None`` = all live): inert queries
    contribute zero grid steps and come back as the empty merged window
    (INVALID_DOC, INVALID_ATTR, src=1).  An all-inert batch launches
    nothing.
    """
    if (packed is None) != (d_packed is None):
        raise ValueError(
            "merge_delta_windows_compact: packed and d_packed go together"
        )
    q_n = terms.shape[0]
    s_w = -(-window // TILE)
    suffix = "_packed" if packed is not None else ""
    wl = build_merge_worklist(
        np.asarray(jax.device_get(m_neff)),
        tile=TILE,
        s_w=s_w,
        live_q=live_q,
        kernel="merge_delta_windows_compact" + suffix,
        dense_steps=q_n * s_w,
    )
    if wl.n_items == 0:
        # Result-shaped (Q, window) constants, not flat posting-layout
        # arrays: the empty merged window the kernel itself would emit.
        # lint: allow(posting-alloc)
        docs = jnp.full((q_n, window), INVALID_DOC, jnp.int32)
        # lint: allow(posting-alloc)
        oattrs = jnp.full((q_n, window), INVALID_ATTR, jnp.int32)
        src = jnp.ones((q_n, window), jnp.int32)
        return docs, oattrs, src
    lq = None if live_q is None else jnp.asarray(np.asarray(live_q))
    return _merge_compact_call(
        jnp.asarray(wl.desc),
        postings, attrs, m_off, m_neff,
        d_postings, d_attrs, d_offsets, d_lengths, d_block_max, terms,
        lq,
        window=window, packed=packed, d_packed=d_packed, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Contract registration (repro.kernels.registry -> repro.analysis)
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402

from repro.kernels.registry import (  # noqa: E402
    ELEMENT,
    KernelContract,
    OperandContract,
    kernel_contract,
    site_of,
    synthetic_delta_arrays,
    synthetic_flat_index,
)


#: Canonical delta slab capacity: the writer's default (two BLOCKs), so
#: slabs start mid-tile and the aligned slab read's row shift is exercised.
_CANON_CAP = 2 * BLOCK


def _aligned(row):
    return (row // TILE_ROWS) * TILE_ROWS


def _main_window_intended(q, j, minfo_ref, slab_ref, len_ref, occ_ref, *_):
    """Pre-clamp address of :func:`_main_window_map` — contract only."""
    return (_aligned(minfo_ref[q, 0] + j * TILE_ROWS), 0)


def _main_window_consumed(q, j, minfo_ref, slab_ref, len_ref, occ_ref, *_):
    return bool(j * TILE < minfo_ref[q, 1])


def _slab_intended(cap_rows):
    def intended(q, j, minfo_ref, slab_ref, len_ref, occ_ref, *_):
        return (_aligned(slab_ref[q] * cap_rows), 0)

    return intended


def _slab_consumed(q, j, minfo_ref, slab_ref, len_ref, occ_ref, *_):
    return bool(occ_ref[q] != 0)


def _packed_window_intended(woff_idx, n_blocks):
    """:func:`_packed_window_map` minus the rows clamp (provably inert:
    ``packed_word_pad`` leaves a full spare chunk past the live words)."""

    def intended(q, j, *refs):
        b0c = jnp.minimum(refs[0][q, 0] + j * TILE_ROWS, n_blocks)
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return intended


def _packed_slab_intended(woff_idx, bpt, n_blocks):
    def intended(q, j, *refs):
        b0 = jnp.where(refs[3][q] == 0, 0, refs[1][q]) * bpt
        b0c = jnp.minimum(b0, n_blocks)
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return intended


def _build_merge_contract(use_packed):
    # Canonical main index (posting_intersect._CANON_LISTS): the last list
    # ends mid-tile next to the spare tile, so the last window tile of
    # query 1 clamps — safe only because of the spare INVALID tile.
    arrays, live = synthetic_flat_index(_CANON_LISTS)
    delta = synthetic_delta_arrays(3, _CANON_CAP, fills=(5, 0, 12))
    n_terms, cap = 3, _CANON_CAP
    bpt = cap // BLOCK
    rows_total = arrays["postings"].shape[0] // LANES

    window = 2 * TILE
    s_w = -(-window // TILE)
    out_rows = s_w * TILE_ROWS
    q_n = 3
    terms = np.array([0, 2, -1], np.int32)
    m_off = np.array([0, _CANON_OFF[2], _CANON_OFF[1]], np.int32)
    m_neff = np.array([150, 90, 100], np.int32)

    tt = np.clip(terms, 0, n_terms - 1)
    slab = delta["d_offsets"][tt] // cap
    d_len = np.where(terms < 0, 0, delta["d_lengths"][tt]).astype(np.int32)
    occ_per_term = np.sum(
        delta["d_block_max"].reshape(n_terms, bpt) != INVALID_DOC, axis=1
    ).astype(np.int32)
    d_occ = np.where(terms < 0, 0, occ_per_term[tt]).astype(np.int32)
    minfo = np.stack([m_off // LANES, m_neff], axis=-1).astype(np.int32)
    scalars = (minfo, slab.astype(np.int32), d_len, d_occ)

    win = (WINDOW_READ_ROWS, LANES)
    flat_main = (rows_total, LANES)
    flat_delta = (delta["d_postings"].shape[0] // LANES, LANES)
    cap_rows, _, read_rows, _ = _slab_geometry(cap)
    slab_blk = (read_rows, LANES)
    d_map = _slab_map(flat_delta[0], read_rows, cap_rows)
    d_live = int(cap * n_terms)
    main_kw = dict(
        indexing_mode=ELEMENT,
        intended_map=_main_window_intended,
        consumed=_main_window_consumed,
        padding_from=live,
        spare_tile=True,
        spare_elems=TILE,
    )
    slab_kw = dict(
        indexing_mode=ELEMENT,
        intended_map=_slab_intended(cap_rows),
        consumed=_slab_consumed,
        padding_from=d_live,
    )
    m_map = _main_window_map(rows_total)
    if use_packed:
        pk_m = pack_flat_postings(arrays["postings"])
        pk_d = pack_flat_postings(
            delta["d_postings"], span_blocks=max(DESC_PAD, bpt)
        )
        scalars = scalars + tuple(
            np.asarray(x)
            for pk in (pk_m, pk_d)
            for x in (pk.blk_base, pk.blk_meta, pk.blk_woff)
        )
        rows_wm = np.asarray(pk_m.words).shape[0] // LANES
        rows_wd = np.asarray(pk_d.words).shape[0] // LANES
        mp_op = OperandContract(
            "packed_words(main)",
            (rows_wm, LANES),
            "int32",
            (pk_m.chunk_rows, LANES),
            _packed_window_map(6, pk_m.n_blocks, rows_wm, pk_m.chunk_rows),
            indexing_mode=ELEMENT,
            intended_map=_packed_window_intended(6, pk_m.n_blocks),
            consumed=_main_window_consumed,
            padding_from=int(np.asarray(pk_m.blk_woff)[-1]),
            spare_tile=True,
        )
        dp_op = OperandContract(
            "packed_words(delta)",
            (rows_wd, LANES),
            "int32",
            (pk_d.chunk_rows, LANES),
            _packed_slab_map(9, bpt, pk_d.n_blocks, rows_wd, pk_d.chunk_rows),
            indexing_mode=ELEMENT,
            intended_map=_packed_slab_intended(9, bpt, pk_d.n_blocks),
            consumed=_slab_consumed,
            padding_from=int(np.asarray(pk_d.blk_woff)[-1]),
            spare_tile=True,
        )
    else:
        mp_op = OperandContract(
            "main_postings", flat_main, "int32", win, m_map, **main_kw
        )
        dp_op = OperandContract(
            "delta_postings", flat_delta, "int32", slab_blk, d_map, **slab_kw
        )
    ins = (
        mp_op,
        OperandContract(
            "main_attrs", flat_main, "int32", win, m_map, **main_kw
        ),
        dp_op,
        OperandContract(
            "delta_attrs", flat_delta, "int32", slab_blk, d_map, **slab_kw
        ),
    )
    blk_o = (1, out_rows, LANES)
    out_shape = (q_n, out_rows, LANES)
    outs = tuple(
        OperandContract(nm, out_shape, "int32", blk_o, _merge_out_map)
        for nm in ("docs", "attrs", "src")
    )
    suffix = "_packed" if use_packed else ""
    return KernelContract(
        name="merge_delta_windows" + suffix,
        site=site_of(merge_delta_windows),
        grid=(q_n, s_w),
        scalars=scalars,
        inputs=ins,
        outputs=outs,
        scratch=(
            ((out_rows, LANES), "int32"),
            ((out_rows, LANES), "int32"),
        ),
        revisit_dims=(1,),
        notes="in-kernel bitonic merge of main + delta streams"
        + (" (block-codec decode in VMEM)" if use_packed else ""),
    )


@kernel_contract("merge_delta_windows")
def _contract_merge_delta_windows():
    return _build_merge_contract(False)


@kernel_contract("merge_delta_windows_packed")
def _contract_merge_delta_windows_packed():
    return _build_merge_contract(True)


# --- work-list compacted variant -------------------------------------------


def _wl_main_window_intended(n, desc_ref, minfo_ref, *_):
    return (
        _aligned(minfo_ref[desc_ref[n, 0], 0] + desc_ref[n, 1] * TILE_ROWS),
        0,
    )


def _wl_main_consumed(n, desc_ref, minfo_ref, *_):
    return bool(desc_ref[n, 1] * TILE < minfo_ref[desc_ref[n, 0], 1])


def _wl_slab_intended(cap_rows):
    def intended(n, desc_ref, minfo_ref, slab_ref, *_):
        return (_aligned(slab_ref[desc_ref[n, 0]] * cap_rows), 0)

    return intended


def _wl_slab_consumed(n, desc_ref, minfo_ref, slab_ref, len_ref, occ_ref, *_):
    return bool(occ_ref[desc_ref[n, 0]] != 0)


def _wl_packed_window_intended(woff_idx, n_blocks):
    def intended(n, *refs):
        q = refs[0][n, 0]
        b0c = jnp.minimum(refs[1][q, 0] + refs[0][n, 1] * TILE_ROWS, n_blocks)
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return intended


def _wl_packed_slab_intended(woff_idx, bpt, n_blocks):
    def intended(n, *refs):
        q = refs[0][n, 0]
        b0 = jnp.where(refs[4][q] == 0, 0, refs[2][q]) * bpt
        b0c = jnp.minimum(b0, n_blocks)
        return (_aligned(refs[woff_idx][b0c] // LANES), 0)

    return intended


def _build_merge_compact_contract(use_packed):
    # Same canonical instance as the dense merge contract, with query 1
    # marked inert by live_q: the builder must drop it entirely (its rows
    # never appear in the table) while query 2's occupied-zero slab keeps
    # the slab-pin clamp + consumed=False escape path exercised in
    # work-list space.
    arrays, live = synthetic_flat_index(_CANON_LISTS)
    delta = synthetic_delta_arrays(3, _CANON_CAP, fills=(5, 0, 12))
    n_terms, cap = 3, _CANON_CAP
    bpt = cap // BLOCK
    rows_total = arrays["postings"].shape[0] // LANES

    window = 2 * TILE
    s_w = -(-window // TILE)
    out_rows = s_w * TILE_ROWS
    q_n = 3
    terms = np.array([0, 2, -1], np.int32)
    m_off = np.array([0, _CANON_OFF[2], _CANON_OFF[1]], np.int32)
    m_neff = np.array([150, 90, 100], np.int32)
    live_q = np.array([True, False, True])

    tt = np.clip(terms, 0, n_terms - 1)
    slab = delta["d_offsets"][tt] // cap
    d_len = np.where(terms < 0, 0, delta["d_lengths"][tt]).astype(np.int32)
    occ_per_term = np.sum(
        delta["d_block_max"].reshape(n_terms, bpt) != INVALID_DOC, axis=1
    ).astype(np.int32)
    d_occ = np.where(terms < 0, 0, occ_per_term[tt]).astype(np.int32)
    minfo = np.stack([m_off // LANES, m_neff], axis=-1).astype(np.int32)

    wl = build_merge_worklist(
        m_neff, tile=TILE, s_w=s_w, live_q=live_q,
        kernel="contract", dense_steps=q_n * s_w,
    )
    scalars = (wl.desc, minfo, slab.astype(np.int32), d_len, d_occ)

    win = (WINDOW_READ_ROWS, LANES)
    flat_main = (rows_total, LANES)
    flat_delta = (delta["d_postings"].shape[0] // LANES, LANES)
    cap_rows, _, read_rows, _ = _slab_geometry(cap)
    slab_blk = (read_rows, LANES)
    d_map = _wl_slab_map(flat_delta[0], read_rows, cap_rows)
    d_live = int(cap * n_terms)
    main_kw = dict(
        indexing_mode=ELEMENT,
        intended_map=_wl_main_window_intended,
        consumed=_wl_main_consumed,
        padding_from=live,
        spare_tile=True,
        spare_elems=TILE,
    )
    slab_kw = dict(
        indexing_mode=ELEMENT,
        intended_map=_wl_slab_intended(cap_rows),
        consumed=_wl_slab_consumed,
        padding_from=d_live,
    )
    m_map = _wl_main_window_map(rows_total)
    if use_packed:
        pk_m = pack_flat_postings(arrays["postings"])
        pk_d = pack_flat_postings(
            delta["d_postings"], span_blocks=max(DESC_PAD, bpt)
        )
        scalars = scalars + tuple(
            np.asarray(x)
            for pk in (pk_m, pk_d)
            for x in (pk.blk_base, pk.blk_meta, pk.blk_woff)
        )
        rows_wm = np.asarray(pk_m.words).shape[0] // LANES
        rows_wd = np.asarray(pk_d.words).shape[0] // LANES
        mp_op = OperandContract(
            "packed_words(main)",
            (rows_wm, LANES),
            "int32",
            (pk_m.chunk_rows, LANES),
            _wl_packed_window_map(7, pk_m.n_blocks, rows_wm, pk_m.chunk_rows),
            indexing_mode=ELEMENT,
            intended_map=_wl_packed_window_intended(7, pk_m.n_blocks),
            consumed=_wl_main_consumed,
            padding_from=int(np.asarray(pk_m.blk_woff)[-1]),
            spare_tile=True,
        )
        dp_op = OperandContract(
            "packed_words(delta)",
            (rows_wd, LANES),
            "int32",
            (pk_d.chunk_rows, LANES),
            _wl_packed_slab_map(
                10, bpt, pk_d.n_blocks, rows_wd, pk_d.chunk_rows
            ),
            indexing_mode=ELEMENT,
            intended_map=_wl_packed_slab_intended(10, bpt, pk_d.n_blocks),
            consumed=_wl_slab_consumed,
            padding_from=int(np.asarray(pk_d.blk_woff)[-1]),
            spare_tile=True,
        )
    else:
        mp_op = OperandContract(
            "main_postings", flat_main, "int32", win, m_map, **main_kw
        )
        dp_op = OperandContract(
            "delta_postings", flat_delta, "int32", slab_blk, d_map, **slab_kw
        )
    ins = (
        mp_op,
        OperandContract(
            "main_attrs", flat_main, "int32", win, m_map, **main_kw
        ),
        dp_op,
        OperandContract(
            "delta_attrs", flat_delta, "int32", slab_blk, d_map, **slab_kw
        ),
    )
    blk_o = (1, out_rows, LANES)
    out_shape = (q_n, out_rows, LANES)
    outs = tuple(
        OperandContract(nm, out_shape, "int32", blk_o, _wl_merge_out_map)
        for nm in ("docs", "attrs", "src")
    )
    suffix = "_packed" if use_packed else ""
    return KernelContract(
        name="merge_delta_windows_compact" + suffix,
        site=site_of(merge_delta_windows_compact),
        grid=(wl.desc.shape[0],),
        scalars=scalars,
        inputs=ins,
        outputs=outs,
        scratch=(
            ((out_rows, LANES), "int32"),
            ((out_rows, LANES), "int32"),
        ),
        revisit_dims=(0,),
        notes="work-list compacted bitonic merge"
        + (" (block-codec decode in VMEM)" if use_packed else ""),
    )


@kernel_contract("merge_delta_windows_compact")
def _contract_merge_compact():
    return _build_merge_compact_contract(False)


@kernel_contract("merge_delta_windows_compact_packed")
def _contract_merge_compact_packed():
    return _build_merge_compact_contract(True)
