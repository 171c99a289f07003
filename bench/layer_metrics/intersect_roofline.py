"""Intersection kernels: the least time the traced batches' bytes need at
the chip's HBM peak, over the device time of the kernels named
``intersect_batched*``, in %.  Bytes from ``bench/roofline.py``: each
slave's driver read, summed over the set, and the answer once."""
from bench import roofline, trace

KERNEL = "intersect_batched"


def read(ctx):
    if ctx.trace is None:
        return None
    ns = trace.kernel_ns(ctx.trace, KERNEL)
    n_bytes = 0
    for b in ctx.batches:
        if not b.traced:
            continue
        for (q, docids, _), slaves in zip(b.answers, b.drivers):
            n_bytes += roofline.answer_bytes(len(docids)) + sum(
                roofline.intersect_read_bytes(
                    base_len=base, delta_len=delta, n_terms=len(q.terms),
                    limited=q.site is not None, merged=ctx.merged, k=q.k,
                    window=ctx.window)
                for base, delta in slaves)
    return roofline.share_pct(n_bytes, ns / 1e9, ctx.device_kind)
