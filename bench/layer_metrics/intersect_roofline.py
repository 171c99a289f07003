"""Intersection kernels: the least time the traced batches' bytes need at
the chip's HBM peak, over the device time of the kernels named
``intersect_batched*``, in %.  Bytes from ``bench/roofline.py``."""
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
        for (q, docids, _), (base, delta) in zip(b.answers, b.drivers):
            n_bytes += roofline.intersect_bytes(
                base_len=base, delta_len=delta, n_terms=len(q.terms),
                limited=q.site is not None, merged=ctx.merged, k=q.k,
                n_answer=len(docids), window=ctx.window)
    return roofline.share_pct(n_bytes, ns / 1e9, ctx.device_kind)
