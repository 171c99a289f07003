"""Benchmark harness: one module per paper table/figure.

Emits ``name,metric,value,derived`` CSV lines.  Run as:
    PYTHONPATH=src python -m benchmarks.run [--suite fig13] [--backend pallas]

``--backend jnp|pallas`` selects the execution engine for every suite that
actually runs the JAX query engine (engine, updates, serving; the
dedicated ``backends`` sweep always measures both).  The fig/table suites
drive the analytic performance model and DES prototype, which have no
execution engine — the flag is accepted and ignored there.  ``--smoke``
shrinks the suites that support it (serving, updates) to CI-sized runs;
``--suite updates --smoke --backend pallas`` additionally prints the
freshness-tax before/after comparison (legacy staged path vs the
streaming posting pipeline).

``--json-dir DIR`` additionally writes one ``BENCH_<suite>.json`` per
suite run, containing every CSV record the suite printed (value + note
per metric; latency suites emit ``<metric>`` mean and ``<metric>_p95``
lines; the serving suite adds ``phase_<name>`` per-phase span means and
``lam*_residual_online`` Formula (18) gauges from the live observability
layer).  CI uploads these as artifacts and feeds ``BENCH_updates.json``
to ``scripts/check_bench.py``, the streamed-vs-staged regression gate —
which ignores metric keys it does not recognize, so emitters may grow.

``--codec packed`` (updates suite) runs the query sweep through the
block-codec read path — packed words decoded in-kernel — and, under
``--backend pallas``, interleaves packed vs raw reps per fill level; a
second ``check_bench.py --require-packed`` invocation gates those
``packed_over_raw_fill*`` ratios and the compression floor.
"""
import argparse
import contextlib
import inspect
import io
import json
import os
import sys
import time
import traceback
from pathlib import Path


def _early_devices_flag() -> None:
    """Apply ``--devices N`` before anything imports jax.

    The host-platform device count is an XLA init-time flag: it must be in
    ``XLA_FLAGS`` before the first jax import, and the suite imports below
    pull jax in transitively — so this scans raw ``sys.argv`` rather than
    waiting for argparse.  An explicit count already present in the
    environment wins.
    """
    n = None
    for i, a in enumerate(sys.argv):
        if a == "--devices" and i + 1 < len(sys.argv):
            n = sys.argv[i + 1]
        elif a.startswith("--devices="):
            n = a.split("=", 1)[1]
    if n is None:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(n)}".strip()
    )


_early_devices_flag()

from benchmarks import (  # noqa: E402  (jax env flags must be set first)
    bench_backends,
    bench_engine,
    bench_fig11,
    bench_fig12,
    bench_fig13,
    bench_kernels,
    bench_serving,
    bench_table3,
    bench_updates,
)
from repro.launch.compile_cache import use_checkout_cache  # noqa: E402

SUITES = {
    "table3": bench_table3.main,    # Table 3 parameters + derived ST/weights
    "fig11": bench_fig11.main,      # model vs DES-prototype, estimation error
    "fig12": bench_fig12.main,      # slave max vs segment size
    "fig13": bench_fig13.main,      # 300-node projection + 43,472-node headline
    "engine": bench_engine.main,    # measured JAX engine + §2 strategies
    "kernels": bench_kernels.main,  # Pallas kernel microbenches
    "backends": bench_backends.main,  # jnp vs Pallas engine backend sweep
    "updates": bench_updates.main,  # online-update ingest + freshness
    "serving": bench_serving.main,  # calibrated lambda sweep, measured vs model
}


class _Tee(io.TextIOBase):
    """Mirror writes to the real stdout while keeping a copy for parsing."""

    def __init__(self, out):
        self.out = out
        self.buf = io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


def _parse_records(text: str, suite: str) -> dict:
    """Pull ``suite,metric,value,note`` CSV lines out of a suite's output."""
    metrics = {}
    for line in text.splitlines():
        parts = line.strip().split(",")
        if len(parts) < 3 or parts[0] != suite:
            continue
        try:
            value = float(parts[2])
        except ValueError:
            continue
        metrics[parts[1]] = {
            "value": value,
            "note": ",".join(parts[3:]) if len(parts) > 3 else "",
        }
    return metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--suite", "--only", dest="suite", default=None,
        choices=sorted(SUITES),
    )
    ap.add_argument(
        "--backend", default=None, choices=["jnp", "pallas"],
        help="execution engine for the suites that run the JAX engine",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized runs for the suites that support it",
    )
    ap.add_argument(
        "--codec", default=None, choices=["raw", "packed"],
        help="posting codec for the suites that support it (updates): "
             "packed queries the block-codec in-kernel decode path and, "
             "under --backend pallas, emits the packed_over_raw_fill* "
             "gate ratios",
    )
    ap.add_argument(
        "--devices", type=int, default=None, metavar="N",
        help="host-platform device count (sets XLA_FLAGS "
             "--xla_force_host_platform_device_count before jax init; "
             "needed for the serving suite's multi-set slice sweep)",
    )
    ap.add_argument(
        "--sets", default=None, metavar="N[,N...]",
        help="set counts for the serving suite's disjoint-slice scale-out "
             "sweep (default 1,2,4; counts exceeding the device pool are "
             "skipped with a sets<N>_skipped record)",
    )
    ap.add_argument(
        "--json-dir", default=None, metavar="DIR",
        help="also write one BENCH_<suite>.json per suite (CI artifacts; "
             "consumed by scripts/check_bench.py)",
    )
    args = ap.parse_args()
    use_checkout_cache()
    sets = (
        [int(s) for s in args.sets.split(",") if s.strip()]
        if args.sets else None
    )
    names = [args.suite] if args.suite else list(SUITES)
    json_dir = Path(args.json_dir) if args.json_dir else None
    if json_dir:
        json_dir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for name in names:
        fn = SUITES[name]
        params = inspect.signature(fn).parameters
        kw = {}
        if args.backend is not None and "backend" in params:
            kw["backend"] = args.backend
        if args.smoke and "smoke" in params:
            kw["smoke"] = True
        if args.codec is not None and "codec" in params:
            kw["codec"] = args.codec
        if sets is not None and "sets" in params:
            kw["sets"] = sets
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        tee = _Tee(sys.stdout)
        try:
            with contextlib.redirect_stdout(tee):
                fn(**kw)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED", flush=True)
            traceback.print_exc()
            continue
        if json_dir:
            payload = {
                "suite": name,
                "backend": kw.get("backend"),
                "codec": kw.get("codec"),
                "smoke": bool(kw.get("smoke", False)),
                "elapsed_s": round(time.time() - t0, 3),
                "metrics": _parse_records(tee.buf.getvalue(), name),
            }
            path = json_dir / f"BENCH_{name}.json"
            path.write_text(json.dumps(payload, indent=2) + "\n")
            print(f"# wrote {path}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
