"""Master scheduler: mean ``admission_wait`` of the window's queries, in ms
(submit to the batch former popping the query's bucket; ``QuerySpan``)."""


def read(ctx):
    waits = [s.phases["admission_wait"] for s in ctx.spans
             if "admission_wait" in s.phases]
    return 1e3 * sum(waits) / len(waits) if waits else None
