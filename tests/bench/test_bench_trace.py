"""The trace reduction, on a short trace recorded on one TPU v5e (a
``fresh-mix`` run, 0.6 s of its window; ``bench/testdata``)."""
import pytest

from bench import harness, roofline, spec
from bench import trace as bench_trace

XPLANE = spec.BENCH / "testdata" / "fresh-mix.xplane.pb.xz"


@pytest.fixture(scope="module")
def recorded():
    return bench_trace.load(str(XPLANE))


def test_device_ops_and_host_spans_are_found(recorded):
    assert {o.chip for o in recorded.ops} == {0}
    assert recorded.window_ns > 0
    assert len(recorded.ops) > 100
    names = {s[0] for s in recorded.spans}
    assert {"bench.step", "bench.submit"} <= names
    # op names are HLO instruction names, their labels the whole instruction
    assert all(" " not in o.name for o in recorded.ops)
    assert bench_trace.kernel_ns(recorded, "intersect_batched") > 0
    assert bench_trace.kernel_ns(recorded, "merge_delta_windows") > 0


def test_busy_and_idle_cover_the_window(recorded):
    busy = bench_trace.busy_ns(recorded)
    assert 0 < busy < recorded.window_ns
    idle = sum(bench_trace.idle_gaps(recorded).values())
    assert idle + busy == pytest.approx(recorded.window_ns, rel=1e-6)


def test_breakdown_is_ten_named_entries_at_most(recorded):
    b = bench_trace.breakdown(recorded)
    assert set(b) == {"device_ops", "idle_gaps"}
    for rows in b.values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
    tops = [s for _, s in b["device_ops"]]
    assert tops == sorted(tops, reverse=True)


def test_idle_share_and_kernel_shares_stay_in_range(recorded):
    ctx = harness.Context("TPU v5 lite", 4096, True, [], [], recorded)
    idle = spec.reader("device_idle_share.sat")(ctx)
    assert 0 < idle < 100
    # the least bytes a kernel could move in its recorded time stay under peak
    ns = bench_trace.kernel_ns(recorded, "merge_delta_windows")
    most = roofline.hbm_bytes_per_s("TPU v5 lite") * ns / 1e9
    assert roofline.share_pct(most, ns / 1e9, "TPU v5 lite") == pytest.approx(100.0)
