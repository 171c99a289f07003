"""Kernel contract registry: every ``pallas_call`` site, symbolically.

The streamed read path (ROADMAP "Fully-streamed read path") made kernel
correctness hinge on invariants that no runtime test can see until they
bite: Element-indexed BlockSpecs must start on a sublane-tile boundary
(Mosaic refuses any offset it cannot prove aligned) and their edge
clamps must land inside the spare INVALID tile
that :func:`repro.core.index.flat_tile_pad` guarantees, scalar-prefetched
index maps must never alias two grid steps onto one output block, and
VMEM residency must fit real hardware budgets that ``interpret=True``
never enforces.  This module is the *contract layer* those invariants are
declared in: each kernel module registers, per ``pallas_call`` site, a
builder that reconstructs the call's geometry — grid, BlockSpecs (block
shape + the **same index-map code the kernel runs**), scalar-prefetch
operands, scratch shapes — on a small canonical instance, as concrete
numpy values the static checker (:mod:`repro.analysis`) can enumerate
without executing the kernel.

Beyond the raw geometry, a contract declares what Pallas cannot express:

- ``intended_map``: the pre-clamp address a block *means* to read.  The
  real index maps clamp at array edges (``jnp.minimum``); the checker
  proves that whenever the clamp engages, nothing the kernel *keeps* came
  from the clamped read.
- ``consumed``: whether any loaded position of the block can affect the
  kernel's output at a given grid point (the kernels' intended-position /
  range masks, mirrored).
- ``padding_from`` + ``spare_tile``: the flat-array live extent and the
  spare-tile requirement — the checkable form of the ``flat_tile_pad``
  padding contract.

Builders run at check time so the contract always reflects the current
index-layout helpers (monkeypatching ``flat_tile_pad`` to the historical
floor+1 bug makes the checker fail — see ``tests/test_analysis.py``).
"""

from __future__ import annotations

import dataclasses
import inspect
import os
from typing import Callable, Sequence

import numpy as np

BLOCKED = "blocked"
#: ``pl.Element`` dims: the index map returns element offsets, not block
#: indices.  Row offsets must be multiples of the dtype's sublane tile.
ELEMENT = "element"


@dataclasses.dataclass(frozen=True)
class OperandContract:
    """One BlockSpec'd operand (input or output) of a ``pallas_call``."""

    name: str
    array_shape: tuple[int, ...]
    dtype: str
    block_shape: tuple[int, ...]
    index_map: Callable
    indexing_mode: str = BLOCKED
    # Pre-clamp address map: where the block *means* to read.  The checker
    # flags grid points where the actual map diverges (a clamp engaged)
    # while ``consumed`` says the kernel keeps data from this block.
    intended_map: Callable | None = None
    # (*grid_point, *scalars) -> bool: can any loaded position of this
    # block affect the output at this grid point?  (Mirrors the kernel's
    # intended-position / range masking.)
    consumed: Callable | None = None
    # Flat live extent: every element at offset >= padding_from (in the
    # flattened array) is guaranteed INVALID fill.
    padding_from: int | None = None
    # Require a spare block of padding past ``padding_from`` — the
    # flat_tile_pad invariant an edge-clamped Element read relies on.
    spare_tile: bool = False
    # Elements of the spare region ``spare_tile`` demands; None = the whole
    # block.  A window read that keeps only one TILE of a two-tile block
    # needs only that TILE past the live extent.
    spare_elems: int | None = None

    @property
    def block_elems(self) -> int:
        return int(np.prod(self.block_shape))

    @property
    def array_elems(self) -> int:
        return int(np.prod(self.array_shape))

    @property
    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Symbolic description of one ``pallas_call`` site."""

    name: str
    site: str                                 # "path/to/file.py:lineno"
    grid: tuple[int, ...]
    scalars: tuple[np.ndarray, ...]           # scalar-prefetch operands
    inputs: tuple[OperandContract, ...]
    outputs: tuple[OperandContract, ...]
    scratch: tuple[tuple[tuple[int, ...], str], ...] = ()
    # Grid dims allowed to revisit the same output block (accumulation /
    # multi-step dims).  Two grid points that differ OUTSIDE these dims
    # must write distinct output blocks.
    revisit_dims: tuple[int, ...] = ()
    notes: str = ""


_REGISTRY: dict[str, Callable[[], "KernelContract | list[KernelContract]"]] = {}

# Modules whose import registers the in-tree kernel contracts.
_KERNEL_MODULES = (
    "repro.kernels.posting_intersect",
    "repro.kernels.delta_merge",
    "repro.kernels.topk_merge",
    "repro.kernels.flash_attention",
)


def kernel_contract(name: str):
    """Decorator: register ``builder`` as the contract of kernel ``name``."""

    def deco(builder):
        if name in _REGISTRY:
            raise ValueError(f"duplicate kernel contract {name!r}")
        _REGISTRY[name] = builder
        return builder

    return deco


def site_of(fn) -> str:
    """Repo-relative ``file:line`` of a function — the diagnostic anchor."""
    # Unwrap jax.jit / functools.wraps layers down to the plain function.
    seen = 0
    while hasattr(fn, "__wrapped__") and seen < 8:
        fn = fn.__wrapped__
        seen += 1
    try:
        path = inspect.getsourcefile(fn) or "<unknown>"
    except TypeError:
        return f"{getattr(fn, '__module__', '<unknown>')}:0"
    try:
        line = inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        line = 0
    parts = path.replace(os.sep, "/").rsplit("src/repro/", 1)
    if len(parts) == 2:
        path = "src/repro/" + parts[1]
    return f"{path}:{line}"


def registered_names() -> list[str]:
    return sorted(_REGISTRY)


def load_contracts(names: Sequence[str] | None = None) -> list[KernelContract]:
    """Import the kernel modules and build their registered contracts."""
    import importlib

    for mod in _KERNEL_MODULES:
        importlib.import_module(mod)
    out: list[KernelContract] = []
    for name in sorted(_REGISTRY):
        if names is not None and name not in names:
            continue
        built = _REGISTRY[name]()
        out.extend(built if isinstance(built, list) else [built])
    return out


# ---------------------------------------------------------------------------
# Canonical fixture: a tiny index with the production flat-array layout
# ---------------------------------------------------------------------------


def synthetic_flat_index(list_lengths: Sequence[int], *, n_sites: int = 2):
    """CSR flat-posting fixture built through the REAL index builder.

    ``list_lengths[t]`` postings per term, docIDs ascending per list, lists
    BLOCK-aligned, flat arrays padded via ``flat_tile_pad`` — exactly the
    layout the streamed kernels address.  Returns ``(arrays, live_extent)``
    where ``live_extent`` is the first flat offset past every list's slot
    (everything at or beyond it is INVALID fill).

    Built at contract-build time through :mod:`repro.core.index` module
    attributes, so layout-helper changes (or deliberate breakage in tests)
    are always reflected in the contracts.
    """
    from repro.core import index as core_index
    from repro.data.corpus import Corpus

    counts = [int(c) for c in list_lengths]
    n_docs = max(counts)
    doc_terms: list[int] = []
    doc_offsets = [0]
    for d in range(n_docs):
        doc_terms.extend(t for t, c in enumerate(counts) if d < c)
        doc_offsets.append(len(doc_terms))
    corpus = Corpus(
        doc_offsets=np.asarray(doc_offsets, np.int64),
        doc_terms=np.asarray(doc_terms, np.int32),
        doc_site=(np.arange(n_docs) % n_sites).astype(np.int32),
        n_docs=n_docs,
        vocab_size=len(counts),
        n_sites=n_sites,
    )
    arrays, _meta = core_index._build_numpy(corpus, False)
    live = core_index.flat_live_extent(arrays["offsets"], arrays["lengths"])
    return arrays, live


def synthetic_delta_arrays(
    n_terms: int, cap: int, fills: Sequence[int], *, doc_base: int = 10_000
):
    """Delta flat-array fixture with the :mod:`repro.indexing.delta` layout:
    per-term slabs of ``cap`` postings, flat arrays ``flat_tile_pad``'ed, a
    per-BLOCK ``block_max`` skip table (INVALID where a block is empty).
    """
    from repro.core import index as core_index

    BLOCK = core_index.BLOCK
    assert cap % BLOCK == 0
    flat_len = core_index.flat_tile_pad(n_terms * cap)
    d_postings = np.full(flat_len, core_index.INVALID_DOC, np.int32)
    d_attrs = np.full(flat_len, core_index.INVALID_ATTR, np.int32)
    d_offsets = (np.arange(n_terms, dtype=np.int32) * cap).astype(np.int32)
    d_lengths = np.zeros(n_terms, np.int32)
    for t, fill in enumerate(fills):
        fill = min(int(fill), cap)
        docs = doc_base + np.arange(fill, dtype=np.int32) * (t + 2)
        d_postings[t * cap : t * cap + fill] = docs
        d_attrs[t * cap : t * cap + fill] = t % 2
        d_lengths[t] = fill
    d_block_max = (
        d_postings[: n_terms * cap].reshape(-1, BLOCK).max(axis=1).astype(np.int32)
    )
    return {
        "d_postings": d_postings,
        "d_attrs": d_attrs,
        "d_offsets": d_offsets,
        "d_lengths": d_lengths,
        "d_block_max": d_block_max,
    }
