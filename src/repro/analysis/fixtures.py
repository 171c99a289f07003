"""Deliberately-broken kernel contracts the checker MUST reject.

Each fixture is a tiny synthetic :class:`KernelContract` carrying exactly
one violation, paired with the check id expected to fire.  They serve two
masters: ``tests/test_analysis.py`` asserts each is rejected with a
location-bearing diagnostic, and ``python -m repro.analysis selftest``
runs them in CI so a refactor that quietly lobotomizes a check fails the
gate even with a clean tree.
"""

from __future__ import annotations

import numpy as np

from repro.kernels.registry import ELEMENT, KernelContract, OperandContract

_SITE = "src/repro/analysis/fixtures.py"


def _line(tag: str) -> str:
    return f"{_SITE}:{tag}"


def _id_map(i, *_scalars):
    return (i, 0)


def _oob_map(i, *_scalars):
    # walks one block past the end of a 4-block array
    return (i + 1, 0)


def _alias_map(i, *_scalars):
    # grid points 0/1 and 2/3 collapse onto the same output block with no
    # revisit_dims declared
    return (i // 2, 0)


def _flipflop_map(i, *_scalars):
    # revisits block 0 at steps 0 and 2 with step 1 elsewhere: a
    # non-contiguous revisit even when dim 0 IS declared revisitable
    return (int(i) % 2, 0)


def _edge_clamp_map(i, off_ref):
    return (int(np.minimum(off_ref[i] + i * 8, 24)), 0)


def _edge_clamp_intended(i, off_ref):
    return (off_ref[i] + i * 8, 0)


def _consume_all(i, off_ref):
    return True


TILE8 = (8, 128)


def _flat_op(name, n_blocks, index_map, **kw):
    return OperandContract(
        name, (n_blocks * 8, 128), "int32", TILE8, index_map, **kw
    )


def broken_contracts() -> list[tuple[KernelContract, str]]:
    """``(contract, expected_check)`` pairs — one violation each."""
    out: list[tuple[KernelContract, str]] = []

    out.append(
        (
            KernelContract(
                name="fx_oob_index_map",
                site=_line("fx_oob_index_map"),
                grid=(4,),
                scalars=(),
                inputs=(_flat_op("x", 4, _oob_map),),
                outputs=(_flat_op("o", 4, _id_map),),
            ),
            "bounds",
        )
    )

    # Element stream over a flat array whose padding stops exactly at
    # the live extent: no spare tile for the edge clamp to land in.
    out.append(
        (
            KernelContract(
                name="fx_missing_spare_tile",
                site=_line("fx_missing_spare_tile"),
                grid=(4,),
                scalars=(np.zeros(4, np.int32),),
                inputs=(
                    OperandContract(
                        "stream",
                        (32, 128),
                        "int32",
                        TILE8,
                        _edge_clamp_map,
                        indexing_mode=ELEMENT,
                        padding_from=32 * 128,  # live to the very end
                        spare_tile=True,
                    ),
                ),
                outputs=(_flat_op("o", 4, _id_map),),
            ),
            "spare-tile",
        )
    )

    # Edge clamp engages at the last grid step while the kernel still
    # consumes the block — the PR 5 bug, distilled.
    out.append(
        (
            KernelContract(
                name="fx_clamped_read_consumed",
                site=_line("fx_clamped_read_consumed"),
                grid=(5,),
                scalars=(np.zeros(5, np.int32),),
                inputs=(
                    OperandContract(
                        "stream",
                        (32, 128),
                        "int32",
                        TILE8,
                        _edge_clamp_map,
                        indexing_mode=ELEMENT,
                        intended_map=_edge_clamp_intended,
                        consumed=_consume_all,
                        padding_from=24 * 128,
                        spare_tile=True,
                    ),
                ),
                outputs=(
                    OperandContract(
                        "o", (5 * 8, 128), "int32", TILE8, _id_map
                    ),
                ),
            ),
            "clamp-escape",
        )
    )

    # Block-codec words truncated to their live extent — reverting the
    # spare packed chunk ``packed_word_pad`` reserves.  The rows clamp the
    # packed index maps carry (min(woff // 128, rows - chunk_rows)) then
    # lands edge chunks on live words of *other* block spans with no
    # dead region to absorb them: the packed-space spare-tile violation.
    from repro.core.index import pack_flat_postings
    from repro.kernels.registry import synthetic_flat_index

    arrays, _live = synthetic_flat_index((150, 100, 90))
    pk = pack_flat_postings(arrays["postings"])
    live_w = int(np.asarray(pk.blk_woff)[-1])
    cr = pk.chunk_rows
    rows_t = max(-(-live_w // 1024) * 8, cr)  # spare chunk reverted
    woff = np.asarray(pk.blk_woff)

    def _truncated_packed_map(i, woff_ref):
        row = int(np.minimum(woff_ref[i] // 128, rows_t - cr))
        return (row // 8 * 8, 0)

    out.append(
        (
            KernelContract(
                name="fx_packed_words_no_spare_chunk",
                site=_line("fx_packed_words_no_spare_chunk"),
                grid=(4,),
                scalars=(woff,),
                inputs=(
                    OperandContract(
                        "packed_words",
                        (rows_t, 128),
                        "int32",
                        (cr, 128),
                        _truncated_packed_map,
                        indexing_mode=ELEMENT,
                        padding_from=live_w,
                        spare_tile=True,
                    ),
                ),
                outputs=(_flat_op("o", 4, _id_map),),
            ),
            "spare-tile",
        )
    )

    # The driver window as the streamed kernels first read it: an Element
    # read of one (8, 128) tile at the list's BLOCK-aligned start row
    # (offset 384 -> row 3).  Interpret mode runs it; Mosaic refuses it
    # ("Couldn't prove that tiling (8) divides index"), and so must the
    # checker.
    def _block_aligned_window_map(i, info_ref):
        return (int(info_ref[0]) + i * 8, 0)

    out.append(
        (
            KernelContract(
                name="fx_block_aligned_driver_window",
                site=_line("fx_block_aligned_driver_window"),
                grid=(2,),
                scalars=(np.array([384 // 128], np.int32),),
                inputs=(
                    OperandContract(
                        "postings(driver)",
                        (32, 128),
                        "int32",
                        TILE8,
                        _block_aligned_window_map,
                        indexing_mode=ELEMENT,
                    ),
                ),
                outputs=(_flat_op("o", 2, _id_map),),
            ),
            "alignment",
        )
    )

    out.append(
        (
            KernelContract(
                name="fx_aliased_output",
                site=_line("fx_aliased_output"),
                grid=(4,),
                scalars=(),
                inputs=(_flat_op("x", 4, _id_map),),
                outputs=(_flat_op("o", 4, _alias_map),),
            ),
            "alias",
        )
    )

    out.append(
        (
            KernelContract(
                name="fx_noncontiguous_revisit",
                site=_line("fx_noncontiguous_revisit"),
                grid=(4,),
                scalars=(),
                inputs=(_flat_op("x", 4, _id_map),),
                outputs=(_flat_op("o", 2, _flipflop_map),),
                revisit_dims=(0,),
            ),
            "alias",
        )
    )

    # Work-list descriptor table missing its spare entry: a table sized
    # exactly to the item count has nowhere for the clone-the-last-item
    # padding rule to live, so padding rows fall back to zero-filled
    # descriptors — query 0, tile 0 — and the compacted grid's output
    # walk jumps BACK to block 0 after having left it.  In work-list
    # space that manifests as a non-contiguous revisit of the output
    # block, which the alias scan rejects.
    desc_missing_spare = np.zeros((4, 8), np.int32)  # lint: allow(worklist-pad)
    desc_missing_spare[:3, 0] = (0, 1, 1)  # rows 3.. stay zeros: q jumps to 0

    def _wl_out_map(n, desc_ref):
        return (int(desc_ref[n, 0]), 0)

    out.append(
        (
            KernelContract(
                name="fx_worklist_missing_spare",
                site=_line("fx_worklist_missing_spare"),
                grid=(4,),
                scalars=(desc_missing_spare,),
                inputs=(_flat_op("x", 4, _id_map),),
                outputs=(_flat_op("o", 2, _wl_out_map),),
                revisit_dims=(0,),
            ),
            "alias",
        )
    )

    out.append(
        (
            KernelContract(
                name="fx_misaligned_tile",
                site=_line("fx_misaligned_tile"),
                grid=(4,),
                scalars=(),
                inputs=(
                    OperandContract(
                        "x", (32, 100), "int32", (8, 100), _id_map
                    ),
                ),
                outputs=(_flat_op("o", 4, _id_map),),
            ),
            "alignment",
        )
    )

    out.append(
        (
            KernelContract(
                name="fx_vmem_blowout",
                site=_line("fx_vmem_blowout"),
                grid=(2,),
                scalars=(),
                inputs=(
                    OperandContract(
                        "x",
                        (2 * 2048, 128),
                        "float32",
                        (2048, 128),
                        _id_map,
                    ),
                ),
                outputs=(
                    OperandContract(
                        "o",
                        (2 * 2048, 128),
                        "float32",
                        (2048, 128),
                        _id_map,
                    ),
                ),
                scratch=(((4096, 4096), "float32"),),
            ),
            "vmem",
        )
    )

    return out


def broken_lint_sources() -> list[tuple[str, str, str, str]]:
    """``(name, rel_path, source, expected_rule)`` — deliberately-bad
    source snippets each lint rule MUST flag, the lint-side twin of
    :func:`broken_contracts`.  ``python -m repro.analysis selftest``
    runs both families."""
    return [
        (
            "fx_lint_handrolled_pad",
            "repro/core/bad_pad.py",
            "TILE = 1024\n"
            "def pad(n):\n"
            "    return (n // TILE + 1) * TILE\n",
            "flat-pad",
        ),
        (
            "fx_lint_posting_gather",
            "repro/kernels/bad_gather.py",
            "import jax.numpy as jnp\n"
            "def f(postings, idx):\n"
            "    return jnp.take(postings, idx)\n",
            "posting-gather",
        ),
        (
            "fx_lint_hardcoded_interpret",
            "repro/launch/bad_call.py",
            "def h(g):\n"
            "    g(interpret=True)\n",
            "interpret-literal",
        ),
        (
            "fx_lint_adhoc_posting_alloc",
            "repro/indexing/bad_alloc.py",
            "import numpy as np\n"
            "def build(n):\n"
            "    postings = np.full(n * 1024, -1, dtype=np.int32)\n"
            "    return postings\n",
            "posting-alloc",
        ),
        (
            "fx_lint_adhoc_attrs_kwarg_alloc",
            "repro/indexing/bad_kwarg.py",
            "import numpy as np\n"
            "def build(shard, n):\n"
            "    return shard._replace(attrs=np.zeros(n, dtype=np.int32))\n",
            "posting-alloc",
        ),
        (
            "fx_lint_adhoc_worklist_alloc",
            "repro/kernels/bad_worklist.py",
            "import numpy as np\n"
            "def build(n):\n"
            "    desc = np.zeros((n + 1, 8), dtype=np.int32)\n"
            "    return desc\n",
            "worklist-pad",
        ),
    ]
