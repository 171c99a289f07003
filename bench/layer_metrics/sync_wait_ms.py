"""Engine host path: mean ``master_merge`` phase per batch, in ms (the
batch-boundary sync, ``np.asarray`` of the answers, under which the device
batch completes; ``SearchService._execute``)."""
from bench import spans


def read(ctx):
    return spans.batch_mean_ms(ctx.spans, "master_merge")
