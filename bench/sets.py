#!/usr/bin/env python3
"""Measure a cell's spread: run it as the benchmark's command, one process a
run, over sets of seeds, and report each metric's spread per set.

    python3 bench/sets.py --workload slave-mix --seeds 11,12,13,14,15,16 \\
        --sets 2 --seconds 51 --out runs/slave-mix

Each set runs every seed once, in order; the sets use the same seeds.  A
metric's spread is the distance between its first and third quartiles
(``statistics.quantiles(values, n=4)``) over its median.  Every run's last
line is kept in ``<out>.jsonl``; the last line printed is a JSON summary.
This process never imports JAX: each run holds the chip alone.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True, help="path prefix for the run log")
    args = ap.parse_args()

    command = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    seeds = [int(s) for s in args.seeds.split(",")]
    log = Path(args.out + ".jsonl")
    log.parent.mkdir(parents=True, exist_ok=True)
    sets: list[list[dict]] = []
    with log.open("a") as f:
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                t0 = time.perf_counter()
                p = subprocess.run(
                    command + ["--workload", args.workload, "--seed", str(seed),
                               "--seconds", str(args.seconds), "--trace", str(args.trace)],
                    cwd=ROOT, capture_output=True, text=True)
                wall = time.perf_counter() - t0
                last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
                row = {"set": k, "seed": seed, "rc": p.returncode, "wall_s": wall}
                try:
                    row["result"] = json.loads(last)
                except json.JSONDecodeError:
                    row["stderr_tail"] = p.stderr[-3000:]
                row["info"] = [ln for ln in p.stderr.splitlines()
                               if ln.startswith(("setup split", "open loop", "closed loop",
                                                 "generator", "collector", "mutations", "reference",
                                                 "check"))]
                f.write(json.dumps(row) + "\n")
                f.flush()
                print(json.dumps({k: v for k, v in row.items() if k != "stderr_tail"})
                      if "result" in row else json.dumps(row), file=sys.stderr, flush=True)
                rows.append(row)
            sets.append(rows)

    summary = {"workload": args.workload, "seconds": args.seconds, "seeds": seeds,
               "sets": []}
    for rows in sets:
        ok = [r["result"] for r in rows if "result" in r]
        metrics = sorted({m for r in ok for m in r["metrics"]})
        per = {}
        for m in metrics:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            per[m] = {"values": vals, "median": statistics.median(vals),
                      "spread": spread(vals) if len(vals) >= 2 else None}
        summary["sets"].append({"runs": len(rows), "correct": sum(r["correct"] for r in ok),
                                "metrics": per})
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
