"""BlockSpec geometry: grid enumeration, index-map evaluation, bounds.

The contracts hand us the *real* index-map callables the kernels pass to
``pl.BlockSpec`` (hoisted to module level in the kernel files precisely so
both sides share them).  Those closures are written in jnp, but jnp ops on
concrete numpy scalars execute eagerly, so evaluating a map at a concrete
grid point is just calling it and coercing the result to python ints — no
tracing, no kernel execution, no TPU.
"""

from __future__ import annotations

import itertools
from typing import Iterator, Sequence

import numpy as np

from repro.kernels.registry import ELEMENT, OperandContract

#: Minimum tile of a TPU vector register, by dtype itemsize: the second-
#: minor block dim must be a multiple of the sublane count, the minor dim
#: a multiple of the 128-lane width.
SUBLANES_BY_ITEMSIZE = {8: 8, 4: 8, 2: 16, 1: 32}
LANES = 128


def iter_grid(grid: Sequence[int]) -> Iterator[tuple[int, ...]]:
    """All grid points in Pallas iteration order (row-major, last dim
    fastest).  An empty grid has exactly one point: ``()``."""
    if not grid:
        yield ()
        return
    yield from itertools.product(*(range(int(n)) for n in grid))


def eval_map(index_map, point: tuple[int, ...], scalars) -> tuple[int, ...]:
    """Evaluate an index map at a concrete grid point.

    Scalar-prefetch operands are passed through as numpy arrays — exactly
    the refs the map indexes on-device.  jnp ops on these run eagerly;
    results are coerced to plain ints.
    """
    out = index_map(*point, *scalars)
    if not isinstance(out, tuple):
        out = (out,)
    return tuple(int(v) for v in out)


def block_origin(
    op: OperandContract, mapped: tuple[int, ...]
) -> tuple[int, ...]:
    """Element-space origin of the mapped block.

    Blocked mode scales the map's output by the block shape; Element
    mode treats it as an element offset directly.
    """
    if op.indexing_mode == ELEMENT:
        return tuple(int(m) for m in mapped)
    return tuple(int(m) * b for m, b in zip(mapped, op.block_shape))


def block_in_bounds(op: OperandContract, origin: tuple[int, ...]) -> bool:
    """Does the block at ``origin`` lie fully inside the operand array?"""
    return all(
        0 <= o and o + b <= s
        for o, b, s in zip(origin, op.block_shape, op.array_shape)
    )


def flat_offset(op: OperandContract, origin: tuple[int, ...]) -> int:
    """Flat (C-order) element offset of a block origin — the coordinate
    the ``padding_from`` live extent is expressed in."""
    return int(np.ravel_multi_index(origin, op.array_shape, mode="clip"))


def alignment_errors(op: OperandContract) -> list[str]:
    """(8,128)-tile alignment of the block shape, scaled per dtype.

    The minor dim must be a multiple of 128 lanes; the second-minor a
    multiple of the dtype's sublane count.  Leading dims are unconstrained
    (they become grid-block indices).  1-D blocks only need lane checks
    when they are >= a lane row; smaller 1-D scratch is register-resident.
    """
    errs: list[str] = []
    blk = op.block_shape
    itemsize = op.itemsize
    sub = SUBLANES_BY_ITEMSIZE.get(itemsize, 8)
    if len(blk) >= 1 and blk[-1] % LANES != 0:
        errs.append(
            f"minor block dim {blk[-1]} is not a multiple of {LANES} lanes"
        )
    if len(blk) >= 2 and blk[-2] % sub != 0:
        errs.append(
            f"second-minor block dim {blk[-2]} is not a multiple of the "
            f"{sub}-sublane tile for itemsize {itemsize}"
        )
    return errs


def element_offset_errors(
    op: OperandContract, origin: tuple[int, ...]
) -> list[str]:
    """Tile alignment of an Element read's origin: Mosaic accepts an
    Element offset only when it can prove the second-minor offset a
    multiple of the dtype's sublane tile and the minor offset a multiple
    of the 128 lanes."""
    if op.indexing_mode != ELEMENT or len(origin) < 2:
        return []
    sub = SUBLANES_BY_ITEMSIZE.get(op.itemsize, 8)
    errs = []
    if origin[-2] % sub:
        errs.append(
            f"row offset {origin[-2]} is not a multiple of the {sub}-row "
            f"sublane tile"
        )
    if origin[-1] % LANES:
        errs.append(
            f"lane offset {origin[-1]} is not a multiple of {LANES} lanes"
        )
    return errs


def vmem_bytes(
    contract, *, buffer_factor: int = 2
) -> tuple[int, list[tuple[str, int]]]:
    """Estimated VMEM residency: every operand's block double-buffered
    (Pallas pipelines the DMAs) plus the scratch allocations."""
    parts: list[tuple[str, int]] = []
    for op in (*contract.inputs, *contract.outputs):
        parts.append((op.name, op.block_elems * op.itemsize * buffer_factor))
    for i, (shape, dtype) in enumerate(contract.scratch):
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        parts.append((f"scratch[{i}]", n))
    return sum(p[1] for p in parts), parts
