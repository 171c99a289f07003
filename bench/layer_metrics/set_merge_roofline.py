"""Master merge kernel: the least time the traced batches' merge bytes need
at the chip's HBM peak, over the device time of ``merge_topk_rows``, in %.

The bytes are the least any correct merge of a set of ``ns`` slaves moves:
at each of the tournament's log2(ns) rounds, on each of the ns chips, every
answered query's two lists of ``k`` candidates read and its ``k`` best
written, as int32 docIDs.  ``ns`` is the number of slaves a query's driver
lengths were recorded for.  A set of one slave merges nothing and reads
nothing here."""
from bench import roofline, trace

KERNEL = "merge_topk_rows"


def merge_bytes(ns: int, ks) -> int:
    """One batch's least merge bytes: ``ks`` are its answered queries' k."""
    rounds = ns.bit_length() - 1
    return rounds * ns * sum(3 * k for k in ks) * roofline.WORD


def read(ctx):
    if ctx.trace is None:
        return None
    n_bytes = 0
    for b in ctx.batches:
        if b.traced and b.answers:
            n_bytes += merge_bytes(len(b.drivers[0]), [q.k for q, _, _ in b.answers])
    ns = trace.kernel_ns(ctx.trace, KERNEL)
    return roofline.share_pct(n_bytes, ns / 1e9, ctx.device_kind)
