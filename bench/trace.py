"""Reduce a JAX profiler trace to what the per-layer metrics read: device
operations with their intervals, the benchmark's host spans, and the traced
window; and from those the device's busy time, each operation's total, and
the idle gaps by what the host was doing in them.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  Host spans are the events named ``bench.*``
(the harness's ``jax.profiler.TraceAnnotation``s).  All times are
nanoseconds from the trace's start, as ``jax.profiler.ProfileData`` gives
them.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import lzma
from collections import defaultdict
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
#: Event stats that carry a device op's longer names (the kernel's name
#: of a Pallas custom call sits in one of them).
NAME_STATS = ("long_name", "hlo_op", "tf_op", "name", "kernel_details")


def op_name(text: str) -> str:
    """A device op's HLO instruction name (``intersect_batched_driver_streamed.1``)
    from the event's name, which holds the whole instruction."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


@dataclasses.dataclass
class Op:
    name: str
    chip: int
    start: float
    dur: float
    label: str          # name plus the stats above, for matching kernel names


@dataclasses.dataclass
class Trace:
    window_ns: float
    ops: list[Op]
    spans: list[tuple[str, float, float]]   # (name, start, dur)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or one compressed with xz)."""
    from jax.profiler import ProfileData

    if path.endswith(".xz"):
        pd = ProfileData.from_serialized_xspace(lzma.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(path)
    ops: list[Op] = []
    spans = []
    window = None
    last = 0.0
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = float(st["profile_stop_time"] - st["profile_start_time"])
            continue
        if plane.name.startswith(DEVICE_PREFIX):
            try:
                chip = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            except ValueError:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    st = dict(e.stats)
                    label = " ".join([e.name] + [str(st[k]) for k in NAME_STATS if k in st])
                    ops.append(Op(op_name(e.name), chip, e.start_ns, e.duration_ns,
                                  label))
                    last = max(last, e.start_ns + e.duration_ns)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns, e.duration_ns))
                    last = max(last, e.start_ns + e.duration_ns)
    return Trace(window if window else last, ops, spans)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(trace: Trace, chip: int) -> list[tuple[float, float]]:
    return _union([(o.start, o.start + o.dur) for o in trace.ops if o.chip == chip])


def busy_ns(trace: Trace) -> float:
    """Time in which an operation ran, averaged over the chips traced."""
    chips = sorted({o.chip for o in trace.ops})
    if not chips:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(trace, c)) for c in chips) / len(chips)


def kernel_ns(trace: Trace, kernel: str) -> float:
    """Summed device time of the ops whose names carry ``kernel``."""
    return sum(o.dur for o in trace.ops if kernel in o.label)


def op_totals(trace: Trace) -> dict[str, float]:
    tot: dict[str, float] = defaultdict(float)
    for o in trace.ops:
        tot[o.name] += o.dur
    return dict(tot)


def idle_gaps(trace: Trace) -> dict[str, float]:
    """Idle device time (chip 0, or the first chip traced) by the
    innermost host span open at each gap's midpoint; ``no span`` where
    none was."""
    chips = sorted({o.chip for o in trace.ops})
    busy = busy_intervals(trace, chips[0]) if chips else []
    edges = [0.0] + [x for iv in busy for x in iv] + [trace.window_ns]
    spans = sorted(trace.spans, key=lambda s: s[1])
    starts = [sp[1] for sp in spans]
    out: dict[str, float] = defaultdict(float)
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        mid = (s + e) / 2
        # the harness's spans nest at most a few deep: look a few back
        j = bisect.bisect_right(starts, mid)
        inner = [sp for sp in spans[max(0, j - 8):j] if mid < sp[1] + sp[2]]
        name = min(inner, key=lambda sp: sp[2])[0] if inner else "no span"
        out[name] += e - s
    return dict(out)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ops that took the most device time and the idle time by host
    span, in seconds, at most ``top`` of each."""
    def top_list(d):
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": top_list(op_totals(trace)),
            "idle_gaps": top_list(idle_gaps(trace))}
