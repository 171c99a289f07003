"""A run whose timed path is broken underneath reports ``correct`` false:
an answer altered where it is produced, half of each batch's answers left
out, and an ingest whose delta never leaves its first state."""
import dataclasses
import time

import pytest

from bench import harness


def altered_answer(svc):
    inner = svc.scheduler.executor

    def executor(queries, t_max, k, set_id):
        out = inner(queries, t_max, k, set_id)
        hit = out[0]
        out[0] = dataclasses.replace(hit, docids=hit.docids[:-1] + [hit.docids[-1] + 1]
                                     if hit.docids else [0])
        return out

    svc.scheduler.executor = executor


def half_batch(svc):
    inner = svc.scheduler.executor

    def executor(queries, t_max, k, set_id):
        half = len(queries) // 2
        out = inner(queries[:half] + [queries[0]] * (len(queries) - half), t_max, k, set_id)
        return out[:half] + [dataclasses.replace(out[0], docids=[], n_hits=0)
                             for _ in out[half:]]

    svc.scheduler.executor = executor


def frozen_delta(svc):
    first = svc.writer.device_delta()
    svc.writer.device_delta = lambda: first


@pytest.mark.parametrize("name,fault", [
    ("slave-mix", altered_answer),
    ("slave-sat", half_batch),
    ("fresh-mix", frozen_delta),
])
def test_broken_path_is_not_correct(tiny_cell, name, fault):
    traffic = {"outstanding": 16} if name == "slave-sat" else {"rate_qps": 40}
    cell = tiny_cell(name, **traffic)
    out = harness.run(cell, seed=2**31 + 11, seconds=1.0, trace=False,
                      t_process=time.perf_counter(), service_hook=fault)
    assert not out["correct"]
    assert out["checks"]["answers_differing"]["value"] > 0
