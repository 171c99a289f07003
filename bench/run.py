#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload slave-mix --seed 7 --seconds 30 --trace 0

The cell (a workload of ``BENCHMARK.json``) names its configuration and its
traffic mix; ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiled run.  Informational lines and the
numbers that decide ``correct`` go to standard error.  Without an
accelerator, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import spec

    cell = spec.load_cell(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        print(f"bench: no accelerator (jax sees {devs[0].platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, jax sees "
              f"{len(devs)}; nothing run", file=sys.stderr)
        return 2

    from bench import harness

    harness.use_compile_cache()
    out = harness.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_process=T_PROCESS)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
