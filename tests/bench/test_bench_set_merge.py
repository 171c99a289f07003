"""The set's merge metrics: ``set_merge_ms`` sums the device time of the
master merge's ops (the merge kernel, the collective exchanges, the
``n_hits`` all-reduce) per traced batch and chip, and names no other op;
``set_merge_roofline`` puts the least bytes of the tournament's merges over
the merge kernel's time.  On a synthetic trace."""
import pytest

from bench import harness, roofline, spec
from bench import trace as bench_trace
from bench.harness import Batch
from bench.traffic import Query

KIND = "TPU v5 lite"


def _op(text, chip, start, dur):
    return bench_trace.Op(bench_trace.op_name(text), chip, start, dur, text)


def _trace(chips=4):
    ops = []
    for c in range(chips):
        ops += [
            _op("%merge_topk_rows.3 = s32[8,8,128]{2,1,0} custom-call(s32[8,8,128] %a), "
                "custom_call_target=\"tpu_custom_call\"", c, 0, 3000),
            _op("%merge_topk_rows.2 = s32[8,8,128]{2,1,0} custom-call(s32[8,8,128] %b), "
                "custom_call_target=\"tpu_custom_call\"", c, 5000, 3000),
            _op("%ppermute-start.1 = (s32[8,10]{1,0}, s32[8,10]{1,0}) "
                "collective-permute-start(s32[8,10]{1,0} %c)", c, 9000, 400),
            _op("%ppermute-done.1 = s32[8,10]{1,0} collective-permute-done("
                "(s32[8,10]{1,0}, s32[8,10]{1,0}) %ppermute-start.1)", c, 9500, 100),
            _op("%psum.7 = s32[8]{0} all-reduce(s32[8]{0} %d), channel_id=1", c, 9700, 500),
            # reads the all-reduce's output: not a merge op
            _op("%fusion.5 = s32[8]{0} fusion(s32[8]{0} %psum.7), kind=kLoop", c, 10300, 700),
            _op("%intersect_batched_driver_streamed.1 = s32[8,4096]{1,0} custom-call()",
                c, 11000, 9000),
        ]
    return bench_trace.Trace(1e6, ops, [])


def _batch(traced, ks, ns=4):
    answers = [(Query((1,), None, k, "single"), tuple(range(k)), k) for k in ks]
    b = Batch(0, answers, 0.0, 1.0, traced)
    b.drivers = [tuple((5000, 0) for _ in range(ns)) for _ in ks]
    return b


def _ctx(trace, batches):
    return harness.Context(KIND, 4096, False, [], batches, trace)


def test_merge_ops_per_traced_batch_and_chip():
    batches = [_batch(True, [10, 50]), _batch(True, [10]), _batch(False, [1000])]
    # per chip: two kernel rounds 6000 ns, the exchange 500, the all-reduce 500
    got = spec.reader("set_merge_ms.shard")(_ctx(_trace(), batches))
    assert got == pytest.approx(7000 / 1e6 / 2)


def test_merge_ops_are_named_by_kernel_or_opcode():
    read = spec.reader("set_merge_ms.shard")
    named = [o.name for o in _trace(1).ops
             if read(_ctx(bench_trace.Trace(1e6, [o], []), [_batch(True, [10])]))]
    assert named == ["merge_topk_rows.3", "merge_topk_rows.2", "ppermute-start.1",
                     "ppermute-done.1", "psum.7"]


def test_merge_roofline_counts_the_tournaments_least_bytes():
    batches = [_batch(True, [10, 50]), _batch(True, [1000], ns=4), _batch(False, [10])]
    # log2(4) rounds x 4 chips x (2k read + k written) x 4 B, traced batches only
    n_bytes = 2 * 4 * 3 * (10 + 50 + 1000) * 4
    ns_kernel = 4 * 6000
    want = 100 * n_bytes / roofline.hbm_bytes_per_s(KIND) / (ns_kernel / 1e9)
    got = spec.reader("set_merge_roofline.shard")(_ctx(_trace(), batches))
    assert got == pytest.approx(want)
    assert 0 < got <= 100


def test_nothing_to_read_reads_none():
    ms = spec.reader("set_merge_ms.shard")
    share = spec.reader("set_merge_roofline.shard")
    one_slave = [_batch(True, [10], ns=1)]
    assert ms(_ctx(None, one_slave)) is None and share(_ctx(None, one_slave)) is None
    # one slave merges nothing: no bytes, and a trace without merge ops
    no_merge = bench_trace.Trace(1e6, [o for o in _trace().ops
                                       if "intersect" in o.name], [])
    assert ms(_ctx(no_merge, one_slave)) is None
    assert share(_ctx(_trace(), one_slave)) is None
    assert ms(_ctx(_trace(), [_batch(False, [10])])) is None


def test_declared_for_the_set_cell_only():
    cell = spec.load_cell("sharded-mix")
    assert cell.chips == 4 and cell.config["service"]["ns"] == 4
    assert {m["name"] for m in cell.end_to_end} == {"query_p50_ms", "query_p95_ms",
                                                   "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "host_dispatch_ms.shard", "launch_ms.shard", "intersect_roofline.shard",
        "set_merge_ms.shard", "set_merge_roofline.shard"}
    for name in ("slave-mix", "slave-sat", "fresh-mix"):
        assert not any(m["name"].startswith("set_merge")
                       for m in spec.load_cell(name).per_layer)

