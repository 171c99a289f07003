"""Per-batch phase means over the traced queries' ``QuerySpan``s."""


def batch_mean_ms(spans, phase: str) -> float | None:
    """Mean of ``phase`` over the traced batches that carry it, in ms.
    Every query of a batch carries the batch's phase, so one value per
    batch id is read; ``None`` when no batch carries the phase."""
    per_batch = {s.batch_id: s.phases[phase] for s in spans
                 if s.batch_id is not None and phase in s.phases}
    return 1e3 * sum(per_batch.values()) / len(per_batch) if per_batch else None
