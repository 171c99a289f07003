"""Engine host path: mean ``finalize`` phase per batch, in ms (host-side
extraction of each query's docIDs and hit count; ``SearchService._execute``)."""
from bench import spans


def read(ctx):
    return spans.batch_mean_ms(ctx.spans, "finalize")
