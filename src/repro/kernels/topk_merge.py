"""Pallas TPU kernel: bitonic top-k merge (the master's loser tree).

Paper mechanism (§4.1.4, Formula (7)): the master merges ns sorted top-k
streams with a loser tree — k·(⌈log2 ns⌉·t_cmp + t_base) serial compares.

TPU adaptation: a loser tree is pointer-chasing, scalar, and branchy — the
exact opposite of what a VPU wants.  The collective-native equivalent of a
tournament is a **bitonic sorting network**: O(log² n) *data-independent*
compare-exchange stages, each a dense vector min/max over the whole array.
We sort the concatenated (ns·k) candidate docIDs ascending (docID == rank,
DESIGN.md §2) and take the first k.  The network runs on the
(rows, 128) tile as it sits in VMEM: each stage fetches XOR partners with
lane or sublane rotates and keeps min or max (:mod:`repro.kernels.bitonic`)
— no gathers, no branches, no flat reshapes.  Candidates pad to at least
one (8, 128) tile so every block is sublane-tile aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.index import INVALID_DOC, TILE
from repro.kernels import bitonic


def _sort_kernel(x_ref, o_ref):
    o_ref[...] = bitonic.sort(x_ref[...].reshape(o_ref.shape[-2:])).reshape(
        o_ref.shape
    )


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


# BlockSpec index maps — module-level so the contract checker
# (repro.analysis, via the registry at the bottom of this file) evaluates
# the exact same code the pallas_calls run.


def _whole_map():
    return (0, 0)


def _row_map(i):
    return (i, 0, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort(x: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Ascending sort via the Pallas bitonic kernel (pads to pow2/lanes)."""
    n = x.shape[0]
    m = max(TILE, _next_pow2(n))  # >= one (8, 128) tile
    xp = jnp.pad(x, (0, m - n), constant_values=INVALID_DOC)
    rows = m // 128
    out = pl.pallas_call(
        _sort_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 128), x.dtype),
        in_specs=[pl.BlockSpec((rows, 128), _whole_map)],
        out_specs=pl.BlockSpec((rows, 128), _whole_map),
        interpret=interpret,
    )(xp.reshape(rows, 128))
    return out.reshape(-1)[:n]


def merge_topk(
    cands: jnp.ndarray, k: int, *, interpret: bool = False
) -> jnp.ndarray:
    """Merge (ns, k)-stacked sorted candidate ids into the global top-k.

    Matches :func:`repro.kernels.ref.merge_topk_ref` — the loser-tree output.
    """
    flat = cands.reshape(-1)
    return bitonic_sort(flat, interpret=interpret)[:k]


# ---------------------------------------------------------------------------
# Batched (per-query-row) variant — the master merge of the engine
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def merge_topk_rows(
    cands: jnp.ndarray, k: int, *, interpret: bool = False
) -> jnp.ndarray:
    """Row-wise top-k merge: ``(Q, m)`` candidate ids -> ``(Q, k)`` best,
    ascending per row.

    This is the master's loser tree for a whole query batch in ONE
    pallas_call: the grid walks queries, each step bitonic-sorts one row's
    concatenated per-slave candidates (m = 2k for a tournament round,
    ns*k for the centralized all-gather merge) and keeps the k smallest.
    Used by the distributed merge (:mod:`repro.core.parallel`) when the
    engine runs under ``backend="pallas"``.
    """
    q_n, m = cands.shape
    mpad = max(TILE, _next_pow2(m))  # >= one (8, 128) tile
    rows = mpad // 128
    xp = jnp.pad(cands, ((0, 0), (0, mpad - m)), constant_values=INVALID_DOC)
    out = pl.pallas_call(
        _sort_kernel,  # grid block (1, rows, 128): same sort body
        grid=(q_n,),
        out_shape=jax.ShapeDtypeStruct((q_n, rows, 128), cands.dtype),
        in_specs=[pl.BlockSpec((1, rows, 128), _row_map)],
        out_specs=pl.BlockSpec((1, rows, 128), _row_map),
        interpret=interpret,
    )(xp.reshape(q_n, rows, 128))
    return out.reshape(q_n, -1)[:, :k]


# ---------------------------------------------------------------------------
# Contract registration (repro.kernels.registry -> repro.analysis)
# ---------------------------------------------------------------------------

from repro.kernels.registry import (  # noqa: E402
    KernelContract,
    OperandContract,
    kernel_contract,
    site_of,
)


@kernel_contract("bitonic_sort")
def _contract_bitonic_sort():
    # Canonical: n = 2048 candidates -> one (16, 128) block, no grid.
    rows = max(TILE, _next_pow2(2048)) // 128
    shape = (rows, 128)
    return KernelContract(
        name="bitonic_sort",
        site=site_of(bitonic_sort),
        grid=(),
        scalars=(),
        inputs=(OperandContract("cands", shape, "int32", shape, _whole_map),),
        outputs=(OperandContract("sorted", shape, "int32", shape, _whole_map),),
    )


@kernel_contract("merge_topk_rows")
def _contract_merge_topk_rows():
    # Canonical: Q = 4 queries, m = 1024 candidates per row.
    q_n = 4
    rows = max(TILE, _next_pow2(1024)) // 128
    shape = (q_n, rows, 128)
    blk = (1, rows, 128)
    return KernelContract(
        name="merge_topk_rows",
        site=site_of(merge_topk_rows),
        grid=(q_n,),
        scalars=(),
        inputs=(OperandContract("cands", shape, "int32", blk, _row_map),),
        outputs=(OperandContract("sorted", shape, "int32", blk, _row_map),),
    )
