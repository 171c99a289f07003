"""Engine host path: mean ``launch`` phase per batch, in ms (the call of the
jitted ``distributed_query_topk`` until it returns; ``SearchService``)."""
from bench import spans


def read(ctx):
    return spans.batch_mean_ms(ctx.spans, "launch")
