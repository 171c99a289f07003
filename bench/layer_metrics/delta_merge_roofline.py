"""Delta-merge kernel (merge-on-read): the least time the traced batches'
base windows and delta lists need at the chip's HBM peak, over the device
time of the kernels named ``merge_delta_windows*``, in %."""
from bench import roofline, trace

KERNEL = "merge_delta_windows"


def read(ctx):
    if ctx.trace is None or not ctx.merged:
        return None
    ns = trace.kernel_ns(ctx.trace, KERNEL)
    n_bytes = 0
    for b in ctx.batches:
        if not b.traced:
            continue
        for (q, _, _), (base, delta) in zip(b.answers, b.drivers):
            n_bytes += roofline.delta_merge_bytes(
                base_len=base, delta_len=delta, limited=q.site is not None,
                window=ctx.window)
    return roofline.share_pct(n_bytes, ns / 1e9, ctx.device_kind)
