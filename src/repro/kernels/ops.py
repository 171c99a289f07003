"""Public jit'd entry points for the Pallas kernels.

``interpret=None`` resolves from the default backend
(:func:`resolve_interpret`): compiled Mosaic kernels on a TPU, the Pallas
interpreter anywhere else (CPU tests).  Interpret mode is refused on a
TPU, and so is the packed codec, which only the interpreter runs.  All ops
are validated against :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.delta_merge import (
    merge_delta_windows,
    merge_delta_windows_compact,
)
from repro.kernels.posting_intersect import (
    compute_skip_map,
    driver_tile_spans,
    intersect_batched_block_skip,
    intersect_batched_driver_streamed,
    intersect_batched_driver_streamed_compact,
    intersect_batched_streamed,
    intersect_batched_streamed_compact,
    intersect_block_skip,
    skip_fraction,
    window_tile_spans,
)
from repro.kernels.topk_merge import bitonic_sort, merge_topk, merge_topk_rows


#: Why ``codec="packed"`` has no compiled path: what Mosaic refuses when
#: its kernels are compiled for a v5e (tests/test_tpu_compile.py rehearses
#: the raw path only).
PACKED_NOT_COMPILED = (
    "codec='packed' does not compile for TPU: Mosaic refuses the in-VMEM "
    "block decode (the 1-D concatenate of the two word rows a block's "
    "lane gather reads: 'Input offsets outside of the first tile'), and "
    "every packed kernel scalar-prefetches the whole block-descriptor "
    "arrays into SMEM (12 bytes per BLOCK, ~12 MB at 1M blocks).  It runs "
    "only under the Pallas interpreter; use codec='raw' on a TPU"
)


def resolve_interpret(interpret: bool | None = None, *,
                      packed: bool = False) -> bool:
    """The Pallas execution mode: compiled on a TPU, interpreted elsewhere.

    An explicit ``interpret=True`` on a TPU backend raises (the chip path
    never falls back to the interpreter), and so does the packed codec in
    compiled mode (:data:`PACKED_NOT_COMPILED`)."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas kernels run "
            "compiled there"
        )
    if packed and not interpret:
        raise NotImplementedError(PACKED_NOT_COMPILED)
    return interpret


def intersect(a_docs, a_attrs, b_docs, attr_filter=-1, *, s_max=None,
              interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    return intersect_block_skip(
        a_docs, a_attrs, b_docs, attr_filter, s_max=s_max, interpret=interpret
    )


def intersect_batched(a_docs, a_attrs, b_docs, active, attr_filter, *,
                      a_live=None, s_max=None, interpret: bool | None = None):
    """Batched multi-query/multi-term ZigZag join (the engine's hot path).

    ``a_live`` is the optional per-posting tombstone stream of the driver
    windows (online updates, repro.indexing); omitted = all live.
    """
    interpret = resolve_interpret(interpret)
    return intersect_batched_block_skip(
        a_docs, a_attrs, b_docs, active, attr_filter,
        a_live=a_live, s_max=s_max, interpret=interpret,
    )


def intersect_streamed(a_docs, a_attrs, a_live, terms, active, attr_filter,
                       postings, offsets, lengths, block_max,
                       d_postings=None, d_offsets=None, d_lengths=None,
                       d_block_max=None, a_flags=None, *,
                       packed=None, d_packed=None,
                       s_max=None, interpret: bool | None = None):
    """Batched ZigZag join with other-term windows streamed straight from
    the flat index arrays (no ``(Q, T, W)`` staging gather).  Pass the
    ``d_*`` delta arrays + ``a_flags`` for merge-on-read; pass ``packed``
    (+ ``d_packed`` with deltas) to stream block-codec words decoded in
    VMEM instead of raw posting tiles.
    """
    interpret = resolve_interpret(interpret)
    return intersect_batched_streamed(
        a_docs, a_attrs, a_live, terms, active, attr_filter,
        postings, offsets, lengths, block_max,
        d_postings, d_offsets, d_lengths, d_block_max, a_flags,
        packed=packed, d_packed=d_packed,
        s_max=s_max, interpret=interpret,
    )


def intersect_fullstream(d_off, d_neff, terms, active, attr_filter,
                         postings, attrs, offsets, lengths, block_max, *,
                         window, packed=None, s_max=None,
                         interpret: bool | None = None):
    """Fully-streamed batched ZigZag join: the DRIVER window also reads
    straight from the flat arrays (aligned Element reads at the
    scalar-prefetched per-query offsets) — no ``(Q, window)`` gather
    anywhere.  Returns ``(docs, mask)``, the driver window as kernel
    output plus the join mask.
    """
    interpret = resolve_interpret(interpret)
    return intersect_batched_driver_streamed(
        d_off, d_neff, terms, active, attr_filter,
        postings, attrs, offsets, lengths, block_max,
        window=window, packed=packed, s_max=s_max, interpret=interpret,
    )


def merge_windows(postings, attrs, m_off, m_neff, d_postings, d_attrs,
                  d_offsets, d_lengths, d_block_max, terms, *,
                  window, packed=None, d_packed=None,
                  interpret: bool | None = None):
    """In-VMEM merge of main driver windows with the delta posting streams.
    Both sides stream from their flat arrays (the main window through
    aligned Element reads at the prefetched per-query offset, the
    delta slab via its prefetched slab index; empty slabs short-circuit
    through the delta's block-max skip table).  Returns (docs, attrs, src)
    — ``src`` is each merged slot's stream id, from which the caller
    derives the tombstone/live stream with one elementwise pass over the
    ``doc_flags`` bits it already holds."""
    interpret = resolve_interpret(interpret)
    return merge_delta_windows(
        postings, attrs, m_off, m_neff, d_postings, d_attrs,
        d_offsets, d_lengths, d_block_max, terms,
        window=window, packed=packed, d_packed=d_packed,
        interpret=interpret,
    )


def intersect_streamed_compact(a_docs, a_attrs, a_live, terms, active,
                               attr_filter, postings, offsets, lengths,
                               block_max, d_postings=None, d_offsets=None,
                               d_lengths=None, d_block_max=None,
                               a_flags=None, *, packed=None, d_packed=None,
                               s_max=None, interpret: bool | None = None,
                               live_q=None):
    """Work-list compacted :func:`intersect_streamed`: the grid's single
    dimension enumerates live probe work items only (inert padding queries,
    absent term slots, and empty spans launch zero steps).  ``live_q`` is
    the host-side bool[Q] liveness vector; an all-inert batch launches
    nothing.  Bit-identical to the dense comparator."""
    interpret = resolve_interpret(interpret)
    return intersect_batched_streamed_compact(
        a_docs, a_attrs, a_live, terms, active, attr_filter,
        postings, offsets, lengths, block_max,
        d_postings, d_offsets, d_lengths, d_block_max, a_flags,
        packed=packed, d_packed=d_packed,
        s_max=s_max, interpret=interpret, live_q=live_q,
    )


def intersect_fullstream_compact(d_off, d_neff, terms, active, attr_filter,
                                 postings, attrs, offsets, lengths,
                                 block_max, *, window, packed=None,
                                 s_max=None, interpret: bool | None = None,
                                 live_q=None):
    """Work-list compacted :func:`intersect_fullstream` (driver window as
    kernel output).  Inert queries come back as (INVALID_DOC, 0)."""
    interpret = resolve_interpret(interpret)
    return intersect_batched_driver_streamed_compact(
        d_off, d_neff, terms, active, attr_filter,
        postings, attrs, offsets, lengths, block_max,
        window=window, packed=packed, s_max=s_max, interpret=interpret,
        live_q=live_q,
    )


def merge_windows_compact(postings, attrs, m_off, m_neff, d_postings,
                          d_attrs, d_offsets, d_lengths, d_block_max, terms,
                          *, window, packed=None, d_packed=None,
                          interpret: bool | None = None, live_q=None):
    """Work-list compacted :func:`merge_windows`: one grid step per window
    tile overlapping a live query's main range.  Inert queries come back
    as the empty merged window (INVALID_DOC, INVALID_ATTR, src=1)."""
    interpret = resolve_interpret(interpret)
    return merge_delta_windows_compact(
        postings, attrs, m_off, m_neff, d_postings, d_attrs,
        d_offsets, d_lengths, d_block_max, terms,
        window=window, packed=packed, d_packed=d_packed,
        interpret=interpret, live_q=live_q,
    )


def sort(x, *, interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    return bitonic_sort(x, interpret=interpret)


def topk_merge(cands, k, *, interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    return merge_topk(cands, k, interpret=interpret)


def topk_merge_rows(cands, k, *, interpret: bool | None = None):
    """Row-wise (per-query) top-k merge — the batched master merge."""
    interpret = resolve_interpret(interpret)
    return merge_topk_rows(cands, k, interpret=interpret)


__all__ = [
    "intersect",
    "intersect_batched",
    "intersect_streamed",
    "intersect_streamed_compact",
    "intersect_fullstream",
    "intersect_fullstream_compact",
    "merge_windows",
    "merge_windows_compact",
    "window_tile_spans",
    "driver_tile_spans",
    "sort",
    "topk_merge",
    "topk_merge_rows",
    "compute_skip_map",
    "skip_fraction",
    "ref",
    "resolve_interpret",
]
