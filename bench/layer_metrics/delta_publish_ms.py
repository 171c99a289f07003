"""Ingest publish: mean ``delta_publish`` phase of the traced batches that
publish a new delta version, in ms (the numpy snapshot of
``DeltaWriter.host_delta``, then enqueueing its placement in
``SearchService._delta_snapshot``)."""
from bench import spans


def read(ctx):
    return spans.batch_mean_ms(ctx.spans, "delta_publish")
