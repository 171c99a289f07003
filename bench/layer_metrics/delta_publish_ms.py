"""Ingest publish: mean ``delta_publish`` phase of the traced batches that
publish a new delta version, in ms (``DeltaWriter.host_publish``, the host
gather of the term slabs and documents written since the placed snapshot;
then ``DeltaWriter.device_delta``, the patch's copy to the device and its
scatter into a copy of that snapshot, or a full placement on the writer's
first publish; then the placement onto the mesh, in
``SearchService._delta_snapshot``)."""
from bench import spans


def read(ctx):
    return spans.batch_mean_ms(ctx.spans, "delta_publish")
