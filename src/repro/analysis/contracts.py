"""The contract checker: prove BlockSpec invariants per ``pallas_call``.

For each :class:`repro.kernels.registry.KernelContract` the checker
enumerates the grid and proves, without executing the kernel:

- **bounds**: every input/output block the index maps select lies fully
  inside its operand array;
- **clamp-escape**: wherever an index map's actual address diverges from
  its declared ``intended_map`` (an edge clamp engaged), the kernel must
  not consume the block (``consumed`` mirrors the kernel's masking) — the
  PR 5 bug class: a clamped edge read serving a *different* list's live
  postings into an unmasked slot;
- **spare-tile**: operands declared ``spare_tile`` must structurally have
  a whole spare block of padding past their live extent
  (``array_elems - block_elems >= padding_from`` — the checkable form of
  the ``flat_tile_pad`` ceil+1 contract);
- **alias**: no two grid points may write the same output block unless
  they differ only in declared ``revisit_dims``, and revisits must be
  contiguous in grid iteration order (Pallas only guarantees coherent
  output accumulation for contiguous revisits);
- **alignment**: block shapes must be (sublane, 128)-tile aligned for
  their dtype, and every offset an Element-indexed map returns must be a
  multiple of that tile (Mosaic refuses an Element read it cannot prove
  aligned: "Couldn't prove that tiling (8) divides index");
- **vmem**: double-buffered blocks + scratch must fit the per-core budget.

Every finding carries the kernel's registered ``file:line`` site.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.analysis import blockspec
from repro.kernels.registry import ELEMENT, KernelContract, load_contracts

#: Default per-core VMEM budget (bytes) — v4/v5 class cores carry 16 MiB.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

#: Cap on exhaustive grid enumeration; canonical contracts are tiny.
MAX_GRID_POINTS = 1 << 16


@dataclasses.dataclass(frozen=True)
class Finding:
    kernel: str
    check: str      # bounds | clamp-escape | spare-tile | alias | alignment | vmem
    message: str
    site: str       # "path/to/file.py:lineno"
    severity: str = "error"

    def __str__(self) -> str:
        return f"{self.site}: [{self.kernel}/{self.check}] {self.message}"


def _check_bounds_and_clamps(c: KernelContract) -> list[Finding]:
    finds: list[Finding] = []
    ops = [("input", op) for op in c.inputs] + [
        ("output", op) for op in c.outputs
    ]
    for point in blockspec.iter_grid(c.grid):
        for role, op in ops:
            mapped = blockspec.eval_map(op.index_map, point, c.scalars)
            origin = blockspec.block_origin(op, mapped)
            if not blockspec.block_in_bounds(op, origin):
                finds.append(
                    Finding(
                        c.name,
                        "bounds",
                        f"{role} {op.name!r}: block origin {origin} "
                        f"(shape {op.block_shape}) escapes array "
                        f"{op.array_shape} at grid point {point}",
                        c.site,
                    )
                )
                continue
            if op.intended_map is None:
                continue
            intended = blockspec.block_origin(
                op, blockspec.eval_map(op.intended_map, point, c.scalars)
            )
            if intended == origin:
                continue
            # The clamp engaged.  Safe only if the kernel fully masks this
            # block at this grid point.
            consumed = (
                op.consumed(*point, *c.scalars)
                if op.consumed is not None
                else True
            )
            if consumed:
                finds.append(
                    Finding(
                        c.name,
                        "clamp-escape",
                        f"{role} {op.name!r}: edge clamp rewrote origin "
                        f"{intended} -> {origin} at grid point {point}, but "
                        f"the kernel consumes the block there — a clamped "
                        f"read would serve live data into unmasked slots",
                        c.site,
                    )
                )
    return finds


def _check_spare_tile(c: KernelContract) -> list[Finding]:
    finds: list[Finding] = []
    for op in (*c.inputs, *c.outputs):
        if not op.spare_tile:
            continue
        if op.padding_from is None:
            finds.append(
                Finding(
                    c.name,
                    "spare-tile",
                    f"{op.name!r} declares spare_tile but no padding_from "
                    f"(live extent) to check it against",
                    c.site,
                )
            )
            continue
        slack = op.array_elems - op.padding_from
        need = op.block_elems if op.spare_elems is None else op.spare_elems
        if slack < need:
            finds.append(
                Finding(
                    c.name,
                    "spare-tile",
                    f"{op.name!r}: only {slack} padded elements past the "
                    f"live extent {op.padding_from}, need a spare region "
                    f"of {need} — an edge-clamped read can "
                    f"land on live data (flat_tile_pad must round UP before "
                    f"adding the spare tile)",
                    c.site,
                )
            )
    return finds


def _check_alias(c: KernelContract) -> list[Finding]:
    finds: list[Finding] = []
    n_dims = len(c.grid)
    free_dims = [d for d in range(n_dims) if d not in c.revisit_dims]
    for op in c.outputs:
        origins: list[tuple[tuple[int, ...], tuple[int, ...]]] = []
        for point in blockspec.iter_grid(c.grid):
            mapped = blockspec.eval_map(op.index_map, point, c.scalars)
            origins.append((point, blockspec.block_origin(op, mapped)))
        by_free: dict[tuple[int, ...], set[tuple[int, ...]]] = {}
        for point, origin in origins:
            proj = tuple(point[d] for d in free_dims)
            by_free.setdefault(proj, set()).add(origin)
        seen: dict[tuple[int, ...], tuple[int, ...]] = {}
        for proj, blocks in by_free.items():
            for origin in blocks:
                if origin in seen and seen[origin] != proj:
                    finds.append(
                        Finding(
                            c.name,
                            "alias",
                            f"output {op.name!r}: grid points {seen[origin]} "
                            f"and {proj} (projected to non-revisit dims "
                            f"{free_dims}) both write block {origin} — "
                            f"write race",
                            c.site,
                        )
                    )
                    break
                seen[origin] = proj
        # Revisits must be contiguous in iteration order.
        last_seen: dict[tuple[int, ...], int] = {}
        current: tuple[int, ...] | None = None
        for i, (_point, origin) in enumerate(origins):
            if origin != current:
                if origin in last_seen:
                    finds.append(
                        Finding(
                            c.name,
                            "alias",
                            f"output {op.name!r}: block {origin} is "
                            f"revisited non-contiguously (left after step "
                            f"{last_seen[origin]}, returned at step {i}) — "
                            f"Pallas only keeps revisited output blocks "
                            f"resident across contiguous grid steps",
                            c.site,
                        )
                    )
                    break
                if current is not None:
                    last_seen[current] = i - 1
                current = origin
    return finds


def _check_alignment(c: KernelContract) -> list[Finding]:
    finds: list[Finding] = []
    for op in (*c.inputs, *c.outputs):
        for err in blockspec.alignment_errors(op):
            finds.append(
                Finding(c.name, "alignment", f"{op.name!r}: {err}", c.site)
            )
        if op.indexing_mode != ELEMENT:
            continue
        for point in blockspec.iter_grid(c.grid):
            origin = blockspec.block_origin(
                op, blockspec.eval_map(op.index_map, point, c.scalars)
            )
            errs = blockspec.element_offset_errors(op, origin)
            if errs:
                finds.append(
                    Finding(
                        c.name,
                        "alignment",
                        f"{op.name!r}: Element read at grid point {point}: "
                        + "; ".join(errs),
                        c.site,
                    )
                )
                break
    for i, (shape, dtype) in enumerate(c.scratch):
        if len(shape) < 2:
            continue  # small 1-D scratch is register/SMEM-resident
        import numpy as np

        sub = blockspec.SUBLANES_BY_ITEMSIZE.get(np.dtype(dtype).itemsize, 8)
        if shape[-1] % blockspec.LANES != 0 or shape[-2] % sub != 0:
            finds.append(
                Finding(
                    c.name,
                    "alignment",
                    f"scratch[{i}] shape {shape} ({dtype}) is not "
                    f"({sub}, {blockspec.LANES})-tile aligned",
                    c.site,
                )
            )
    return finds


def _check_vmem(c: KernelContract, budget: int) -> list[Finding]:
    total, parts = blockspec.vmem_bytes(c)
    if total <= budget:
        return []
    detail = ", ".join(f"{name}={n_bytes}" for name, n_bytes in parts)
    return [
        Finding(
            c.name,
            "vmem",
            f"estimated VMEM residency {total} bytes exceeds the "
            f"{budget}-byte per-core budget ({detail})",
            c.site,
        )
    ]


def check_contract(
    c: KernelContract, *, vmem_budget: int = DEFAULT_VMEM_BUDGET
) -> list[Finding]:
    """All findings for one contract (empty list == kernel proven clean)."""
    n_points = 1
    for g in c.grid:
        n_points *= int(g)
    if n_points > MAX_GRID_POINTS:
        return [
            Finding(
                c.name,
                "bounds",
                f"grid {c.grid} has {n_points} points, beyond the "
                f"{MAX_GRID_POINTS}-point enumeration cap — register a "
                f"smaller canonical instance",
                c.site,
            )
        ]
    finds = _check_bounds_and_clamps(c)
    finds += _check_spare_tile(c)
    finds += _check_alias(c)
    finds += _check_alignment(c)
    finds += _check_vmem(c, vmem_budget)
    return finds


def check_all(
    names: Sequence[str] | None = None,
    *,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> tuple[list[KernelContract], list[Finding]]:
    """Build and check every registered contract (or the named subset)."""
    contracts = load_contracts(names)
    finds: list[Finding] = []
    for c in contracts:
        finds.extend(check_contract(c, vmem_budget=vmem_budget))
    return contracts, finds
