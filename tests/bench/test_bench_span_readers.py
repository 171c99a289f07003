"""The per-layer metrics read from the program's spans: one value per
batch (every query of a batch carries the batch's phases), their mean in
ms, and nothing where no traced batch has the phase.  And the same spans
as profiler events, in a trace recorded on one TPU v5e."""
import lzma

import pytest

from bench import harness, spec
from repro.obs.trace import QuerySpan

#: 0.9 s of a ``fresh-mix`` window with a mutation and its publish.
XPLANE = spec.BENCH / "testdata" / "fresh-mix-odys.xplane.pb.xz"

READERS = {"delta_publish_ms.fresh": "delta_publish", "launch_ms.sat": "launch",
           "sync_wait_ms.sat": "master_merge", "extract_ms.sat": "finalize"}


def _ctx(spans):
    return harness.Context("TPU v5 lite", 4096, True, spans, [], None)


def _batch(batch_id, n_queries, **phases):
    out = []
    for q in range(n_queries):
        s = QuerySpan(qid=10 * batch_id + q, submit_time=0.0, batch_id=batch_id,
                      batch_queries=n_queries)
        for p, dt in phases.items():
            s.add(p, dt)
        out.append(s)
    return out


@pytest.mark.parametrize("metric,phase", sorted(READERS.items()))
def test_one_value_per_batch_mean_in_ms(metric, phase):
    # batch 0 has 8 queries, batch 1 one: a mean over queries would be
    # pulled toward batch 0's 2 ms, the mean over batches is 3 ms
    spans = _batch(0, 8, **{phase: 0.002}) + _batch(1, 1, **{phase: 0.004})
    assert spec.reader(metric)(_ctx(spans)) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_none_without_spans(metric):
    assert spec.reader(metric)(_ctx([])) is None


@pytest.mark.parametrize("metric,phase", sorted(READERS.items()))
def test_cache_hits_and_other_phases_are_not_read(metric, phase):
    hit = QuerySpan(qid=99, submit_time=0.0, from_cache=True)
    hit.add("cache_lookup", 0.5)
    other = _batch(3, 2, slave_dispatch=0.7)
    read = spec.reader(metric)
    assert read(_ctx([hit] + other)) is None
    with_phase = [hit] + other + _batch(4, 2, **{phase: 0.001})
    assert read(_ctx(with_phase)) == pytest.approx(1.0)


def test_publish_averages_only_the_publishing_batches():
    spans = (_batch(0, 4, slave_dispatch=0.3, delta_publish=0.25)
             + _batch(1, 4, slave_dispatch=0.001)
             + _batch(2, 4, slave_dispatch=0.001)
             + _batch(3, 4, slave_dispatch=0.4, delta_publish=0.35))
    assert spec.reader("delta_publish_ms.fresh")(_ctx(spans)) == pytest.approx(300.0)
    # the existing dispatch metric still averages every batch
    assert spec.reader("host_dispatch_ms.fresh")(_ctx(spans)) == pytest.approx(175.5)


def test_readers_are_declared_per_cell():
    for metric in READERS:
        cells = {m["name"]: m for m in spec.load_cell(
            "fresh-mix" if metric.endswith(".fresh") else "slave-sat").per_layer}
        assert cells[metric]["source"] == "program_span"
        assert cells[metric]["unit"] == "ms"


def test_recorded_odys_events_nest_inside_their_steps():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(lzma.decompress(XPLANE.read_bytes()))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for plane in pd.planes for line in plane.lines for e in line.events
              if e.name.startswith(("odys.", "bench.step"))]
    names = {e[0] for e in events}
    assert {"odys.launch", "odys.master_merge", "odys.finalize", "odys.delta_publish",
            "odys.delta_rebuild", "odys.delta_place", "odys.mutation_apply"} <= names
    bench_steps = [e for e in events if e[0] == "bench.step"]
    steps = {e[3]["batch"]: e for e in events if e[0] == "odys.step"}
    assert len(steps) == len(bench_steps) > 10
    for name, start, end, stats in events:
        if name in ("bench.step", "odys.mutation_apply"):
            continue
        step = steps[stats["batch"]]
        assert step[1] <= start <= end <= step[2], name
        assert any(b[1] <= start <= end <= b[2] for b in bench_steps), name
