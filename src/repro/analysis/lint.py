"""Repo-invariant lints: AST rules over ``src/`` the contract checker
cannot see from a single call site.

Rules (suppress a line with ``# lint: allow(<rule>)``):

- ``flat-pad`` — flat posting arrays may only be sized through
  :func:`repro.core.index.flat_tile_pad`.  Flags hand-rolled
  ``(n // TILE ...) * TILE`` padding arithmetic anywhere outside that
  function: every re-derivation is a chance to reintroduce the floor+1
  bug the spare-tile contract exists to prevent.
- ``posting-gather`` — no ``jnp.take`` / ``jnp.take_along_axis`` on
  posting/attr arrays inside the kernel layer.  The streamed read path's
  entire point is that windows stream from the flat arrays through
  BlockSpec index maps; a host-side gather on the posting data would
  silently reintroduce the materialization the CI bench gate measures
  away.  (Gathers on *metadata* — offsets, lengths, skip tables — are the
  mechanism and stay legal.)
- ``interpret-literal`` — ``interpret=`` must be threaded (a variable or
  function default), never hard-coded as a ``True``/``False`` literal at
  a call site: hard-coding forks CPU-CI behavior from TPU behavior.
- ``posting-alloc`` — flat posting/attr arrays may only be allocated
  with sizes derived from the layout/codec layer
  (:func:`repro.core.index.flat_tile_pad` /
  :func:`repro.core.index.packed_word_pad`).  Flags ``np.zeros`` /
  ``np.full`` / ... bound to a posting/attrs name whose size expression
  neither calls those helpers nor references a name assigned from them:
  an ad-hoc size is how an array misses the spare tile (or spare packed
  chunk) every streamed BlockSpec read relies on.  Host-side mirrors
  with deliberately different layouts carry the pragma.
- ``worklist-pad`` — work-list descriptor tables (any array a work-item
  grid dimension indexes) may only be sized through
  :func:`repro.kernels.worklist.worklist_pad`.  Flags ``np.zeros`` /
  ``np.full`` / ... bound to a descriptor-table name (``*worklist*``,
  ``desc``, ``*_desc``, ``desc_*``) whose size expression neither calls
  that helper nor references a name assigned from it: an exact-size
  table has no spare entry for the clone-the-last-item padding rule, so
  a pow2-boundary item count walks the grid off the table (the
  ``fx_worklist_missing_spare`` contract fixture shows the failure as a
  non-contiguous output revisit).
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Iterable

RULES = (
    "flat-pad",
    "posting-gather",
    "interpret-literal",
    "posting-alloc",
    "worklist-pad",
)

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([a-z-]+)\)")

#: Identifier substrings that mark an array as posting/attr payload data.
_POSTING_NAMES = ("posting", "attr")

#: Files exempt from posting-gather: the reference oracles are *defined*
#: by their gather formulation.
_GATHER_EXEMPT = ("kernels/ref.py",)

#: Array constructors whose result is a fresh allocation.
_ALLOC_FNS = ("zeros", "empty", "full", "ones")
_ALLOC_MODULES = ("np", "jnp", "numpy")

#: Size helpers from the layout/codec layer.  An allocation whose size
#: expression calls one of these (or references a name assigned from
#: one) carries the spare tile / spare packed chunk by construction.
_PAD_FNS = ("flat_tile_pad", "packed_word_pad")

#: The work-list layer's pad helper: descriptor tables sized through it
#: carry the spare no-op entry the compacted kernels' padding rule needs.
_WL_PAD_FNS = ("worklist_pad",)

#: The layout layer itself — where the pad helpers live and the one
#: place allowed to size posting arrays from first principles.
_ALLOC_EXEMPT = ("repro/core/index.py",)


def _is_payload_name(name: str) -> bool:
    """Posting/attr *payload* arrays — not scalars like a query's single
    ``attr`` filter value; the flat attr payloads are always plural."""
    low = name.lower()
    return "posting" in low or "attrs" in low


def _is_alloc_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr in _ALLOC_FNS
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id in _ALLOC_MODULES
    )


def _calls_pad_fn(node: ast.AST, fns: tuple[str, ...] = _PAD_FNS) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            fn = sub.func
            fname = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else ""
            )
            if fname in fns:
                return True
    return False


def _is_desc_name(name: str) -> bool:
    """Work-list descriptor-table names: the arrays a work-item grid
    dimension indexes."""
    low = name.lower()
    return (
        "worklist" in low
        or low == "desc"
        or low.endswith("_desc")
        or low.startswith("desc_")
    )


@dataclasses.dataclass(frozen=True)
class LintFinding:
    rule: str
    message: str
    path: str
    line: int

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed(source_lines: list[str], node: ast.AST) -> set[str]:
    """Rules suppressed on this node's lines, trailing comments included,
    plus any comment-only lines immediately above the statement."""
    out: set[str] = set()
    first = getattr(node, "lineno", 0)
    for lineno in {first, getattr(node, "end_lineno", 0)}:
        if 1 <= lineno <= len(source_lines):
            out.update(_ALLOW_RE.findall(source_lines[lineno - 1]))
    lineno = first - 1
    while 1 <= lineno <= len(source_lines):
        stripped = source_lines[lineno - 1].strip()
        if not stripped.startswith("#"):
            break
        out.update(_ALLOW_RE.findall(stripped))
        lineno -= 1
    return out


def _contains_tile_floordiv(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.FloorDiv):
            if isinstance(sub.right, ast.Name) and sub.right.id == "TILE":
                return True
            if (
                isinstance(sub.left, ast.UnaryOp)
                and isinstance(sub.right, ast.UnaryOp)
            ):  # -(-n // TILE) spelled with the div nested
                return _contains_tile_floordiv(sub.left) or (
                    _contains_tile_floordiv(sub.right)
                )
    return False


def _is_tile_name(node: ast.AST) -> bool:
    return isinstance(node, ast.Name) and node.id == "TILE"


class _FileLinter(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, source: str):
        self.rel = rel
        self.lines = source.splitlines()
        self.findings: list[LintFinding] = []
        self._func_stack: list[str] = []
        self._gather_scoped = rel.startswith("repro/kernels/") and not any(
            rel.endswith(e.split("/")[-1]) and e in rel for e in _GATHER_EXEMPT
        )
        self._alloc_scoped = rel not in _ALLOC_EXEMPT
        # Per-scope sets of names assigned from flat_tile_pad /
        # packed_word_pad (or from another tracked name) — sizes built
        # from these inherit the spare tile.
        self._pad_names: list[set[str]] = [set()]
        # Same tracking for worklist_pad-derived sizes (worklist-pad rule).
        self._wl_names: list[set[str]] = [set()]

    def _emit(self, rule: str, message: str, node: ast.AST):
        if rule in _allowed(self.lines, node):
            return
        self.findings.append(
            LintFinding(rule, message, self.rel, getattr(node, "lineno", 0))
        )

    # -- flat-pad ----------------------------------------------------------
    def visit_FunctionDef(self, node: ast.FunctionDef):
        self._func_stack.append(node.name)
        self._pad_names.append(set())
        self._wl_names.append(set())
        self.generic_visit(node)
        self._wl_names.pop()
        self._pad_names.pop()
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- posting-alloc -----------------------------------------------------
    def _pad_tracked(self, node: ast.AST) -> bool:
        """Does this expression reference a pad-derived name?  Closures
        see enclosing scopes, so check the whole stack."""
        tracked = set().union(*self._pad_names)
        return any(
            isinstance(sub, ast.Name) and sub.id in tracked
            for sub in ast.walk(node)
        )

    def _pad_derived(self, value: ast.AST) -> bool:
        return _calls_pad_fn(value) or self._pad_tracked(value)

    def _check_alloc(self, name: str, value: ast.AST, node: ast.AST):
        if not (
            self._alloc_scoped
            and _is_alloc_call(value)
            and _is_payload_name(name)
        ):
            return
        size_ok = any(
            self._pad_derived(arg)
            for arg in list(value.args) + [kw.value for kw in value.keywords]  # type: ignore[attr-defined]
        )
        if not size_ok:
            self._emit(
                "posting-alloc",
                f"posting/attr array {name!r} allocated with an ad-hoc "
                "size — derive it from flat_tile_pad()/packed_word_pad() "
                "(or pragma a deliberately different host-side layout)",
                node,
            )

    # -- worklist-pad ------------------------------------------------------
    def _wl_tracked(self, node: ast.AST) -> bool:
        tracked = set().union(*self._wl_names)
        return any(
            isinstance(sub, ast.Name) and sub.id in tracked
            for sub in ast.walk(node)
        )

    def _wl_derived(self, value: ast.AST) -> bool:
        return _calls_pad_fn(value, _WL_PAD_FNS) or self._wl_tracked(value)

    def _check_wl_alloc(self, name: str, value: ast.AST, node: ast.AST):
        if not (_is_alloc_call(value) and _is_desc_name(name)):
            return
        size_ok = any(
            self._wl_derived(arg)
            for arg in list(value.args) + [kw.value for kw in value.keywords]  # type: ignore[attr-defined]
        )
        if not size_ok:
            self._emit(
                "worklist-pad",
                f"work-list descriptor table {name!r} allocated with an "
                "ad-hoc size — derive it from worklist_pad() so the spare "
                "no-op entry the compacted grids rely on exists",
                node,
            )

    def visit_Assign(self, node: ast.Assign):
        for target in node.targets:
            if isinstance(target, ast.Name):
                if self._pad_derived(node.value):
                    self._pad_names[-1].add(target.id)
                if self._wl_derived(node.value):
                    self._wl_names[-1].add(target.id)
                self._check_alloc(target.id, node.value, node)
                self._check_wl_alloc(target.id, node.value, node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        if isinstance(node.target, ast.Name) and node.value is not None:
            if self._pad_derived(node.value):
                self._pad_names[-1].add(node.target.id)
            if self._wl_derived(node.value):
                self._wl_names[-1].add(node.target.id)
            self._check_alloc(node.target.id, node.value, node)
            self._check_wl_alloc(node.target.id, node.value, node)
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp):
        in_flat_tile_pad = "flat_tile_pad" in self._func_stack
        if (
            not in_flat_tile_pad
            and isinstance(node.op, ast.Mult)
            and (_is_tile_name(node.left) or _is_tile_name(node.right))
        ):
            other = node.right if _is_tile_name(node.left) else node.left
            if _contains_tile_floordiv(other):
                self._emit(
                    "flat-pad",
                    "hand-rolled TILE padding arithmetic — size flat "
                    "posting arrays through flat_tile_pad() so the "
                    "spare-tile contract holds",
                    node,
                )
        self.generic_visit(node)

    # -- posting-gather / interpret-literal --------------------------------
    def visit_Call(self, node: ast.Call):
        fn = node.func
        if (
            self._gather_scoped
            and isinstance(fn, ast.Attribute)
            and fn.attr in ("take", "take_along_axis")
            and isinstance(fn.value, ast.Name)
            and fn.value.id == "jnp"
            and node.args
        ):
            target = node.args[0]
            name = ""
            if isinstance(target, ast.Name):
                name = target.id
            elif isinstance(target, ast.Attribute):
                name = target.attr
            if any(p in name.lower() for p in _POSTING_NAMES):
                self._emit(
                    "posting-gather",
                    f"jnp.{fn.attr} on posting/attr array {name!r} in the "
                    "kernel layer — stream it through a BlockSpec index "
                    "map instead",
                    node,
                )
        for kw in node.keywords:
            if kw.arg is not None and not _is_alloc_call(node):
                self._check_alloc(kw.arg, kw.value, kw.value)
                self._check_wl_alloc(kw.arg, kw.value, kw.value)
            if kw.arg == "interpret" and isinstance(kw.value, ast.Constant):
                if isinstance(kw.value.value, bool):
                    self._emit(
                        "interpret-literal",
                        f"interpret={kw.value.value} hard-coded at a call "
                        "site — thread it (default None resolves via "
                        "ops.resolve_interpret())",
                        kw.value,
                    )
        self.generic_visit(node)


def lint_source(source: str, rel: str) -> list[LintFinding]:
    """Lint a source string as if it lived at ``rel`` under ``src/``."""
    try:
        tree = ast.parse(source, filename=rel)
    except SyntaxError as e:
        return [LintFinding("flat-pad", f"unparseable: {e}", rel, e.lineno or 0)]
    linter = _FileLinter(rel, rel, source)
    linter.visit(tree)
    return linter.findings


def lint_file(path: str, rel: str) -> list[LintFinding]:
    with open(path, encoding="utf-8") as f:
        source = f.read()
    return lint_source(source, rel)


def lint_tree(root: str) -> list[LintFinding]:
    """Lint every ``.py`` file under ``root`` (typically ``src/``)."""
    findings: list[LintFinding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            findings.extend(lint_file(path, rel))
    return findings


def default_root() -> str:
    """The ``src/`` tree this installed package was imported from."""
    here = os.path.dirname(os.path.abspath(__file__))   # .../src/repro/analysis
    return os.path.dirname(os.path.dirname(here))        # .../src


def format_findings(findings: Iterable[LintFinding]) -> str:
    return "\n".join(str(f) for f in findings)
