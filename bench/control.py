#!/usr/bin/env python3
"""Run a cell's control: the reference with one stated guarantee broken.

    python3 bench/control.py --workloads slave-mix,slave-sat --seeds 5,6,7 --seconds 6

The configurations state that ``n_hits`` (and with it the answer) is exact
under the window bound of 4096 postings.  The control is the plain
reference computed over half that window: the step that would tempt a
later change (reading fewer postings a keyword).  For each seed this sets
the cell up, runs its traffic for ``--seconds`` as a run does, and counts
the served answers that differ from the reference at the cell's window
(the benchmark's ``answers_differing``) and from the control.  The control
has to differ where the program does not.  Cells of one configuration
share a seed's set-up.  The last line is a JSON object with the counts
per cell and seed.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control(cell, seed: int, seconds: float, built=None) -> dict:
    """Both counts for one seed (any backend: tests run it on the CPU),
    and for an ingest cell the served answers that the acknowledged
    mutations changed (what a delta frozen at its first state would serve
    wrong).  ``built``: a set-up of the cell's configuration to reuse."""
    from bench import harness
    from bench.traffic import Traffic

    window = cell.config["service"]["window"]
    if built is None:
        built = harness.build(cell, seed=seed, trace=False)
    shape = built.host.shape
    built = dataclasses.replace(built, traffic=Traffic(
        cell.traffic, vocab_size=shape.vocab_size, n_sites=shape.n_sites,
        n_docs=shape.n_docs))
    res, _ = harness.measure(cell, built, seed=seed, seconds=seconds,
                             trace=False, t_process=T_PROCESS)
    host = built.host
    gc.unfreeze()
    row = {"workload": cell.name, "seed": seed,
           "answers": sum(len(b.answers) for b in res["batches"]),
           "differ_from_reference": harness.verify(cell, res, host, window=window),
           "differ_from_control": harness.verify(cell, res, host, window=window // 2)}
    if cell.config.get("ingest") is not None:
        row["mutations"] = len(res["mutations"])
        row["differ_without_mutations"] = harness.verify(
            cell, res, host, window=window, mutations=False)
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True,
                    help="comma-separated cells of one configuration: one set-up a seed")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args()

    import jax

    from bench import harness, spec

    if jax.devices()[0].platform == "cpu":
        print("control: no accelerator; nothing run", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    cells = [spec.load_cell(w) for w in args.workloads.split(",")]
    if len({json.dumps(c.config, sort_keys=True) for c in cells}) != 1:
        raise SystemExit("--workloads must share one configuration")
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        built = harness.build(cells[0], seed=seed, trace=False)
        for cell in cells:
            rows.append(control(cell, seed, args.seconds, built))
            harness.log(json.dumps(rows[-1]))
        del built
        gc.collect()
    print(json.dumps({"workloads": [c.name for c in cells], "control_window":
                      cells[0].config["service"]["window"] // 2, "rows": rows,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
