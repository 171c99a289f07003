"""Engine host path: mean ``slave_dispatch`` phase per batch, in ms (host
batch build, planning and async dispatch of the query program;
``SearchService._execute``).  Every query of a batch carries the batch's
phase, so one span per batch is read."""


def read(ctx):
    per_batch = {}
    for s in ctx.spans:
        if s.batch_id is not None and "slave_dispatch" in s.phases:
            per_batch[s.batch_id] = s.phases["slave_dispatch"]
    return 1e3 * sum(per_batch.values()) / len(per_batch) if per_batch else None
