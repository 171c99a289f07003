"""Master merge of a set of slaves: the device time of the merge's
operations, per traced batch and chip, in ms.  They are the ones the
program puts under ``jax.named_scope("odys.set_merge")``
(``core/parallel.py``): the bitonic merge kernel ``merge_topk_rows`` at
each round of the tournament, the ``collective-permute`` exchanges between
the rounds, and the ``all-reduce`` that sums ``n_hits``.  The trace's
events carry the HLO instruction, not its scope, so each is found by the
kernel's name or by its opcode (async halves included).  Summed over every
chip's plane, so the value is one chip's merge time a batch."""
import re

from bench import trace

KERNEL = "merge_topk_rows"
COLLECTIVES = ("collective-permute", "all-reduce")
#: ``%name = <shape> opcode(operands), ...``: the opcode is the first word
#: after the shape that opens an operand list.
_OPCODE = re.compile(r"^%\S+ = .*?\s([a-z][a-z0-9-]*)\(")


def opcode(label: str) -> str | None:
    m = _OPCODE.match(label)
    return m.group(1) if m else None


def is_merge_op(op: trace.Op) -> bool:
    if KERNEL in op.label:
        return True
    code = opcode(op.label) or op.name
    return code.startswith(COLLECTIVES)


def read(ctx):
    if ctx.trace is None:
        return None
    ops = [o for o in ctx.trace.ops if is_merge_op(o)]
    n_batches = sum(1 for b in ctx.batches if b.traced)
    n_chips = len({o.chip for o in ctx.trace.ops})
    if not ops or not n_batches:
        return None
    return sum(o.dur for o in ops) / 1e6 / (n_batches * n_chips)
