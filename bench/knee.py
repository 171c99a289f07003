#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate at which the
backlog does not grow and a tail stays within a limit.

    python3 bench/knee.py --workload slave-mix --seed 3 --rates 50,100,200 \\
        --seconds 6 --limit-ms 50
    python3 bench/knee.py --workload fresh-mix --axis mutations --seed 3 \\
        --rates 0.5,1,2 --seconds 30 --limit-ms 1000 --max-drain-s 1

One process sets the cell up once, then offers each rate in turn for
``--seconds``, in ascending order: on ``--axis queries`` the query rate
(judged by ``query_p95_ms``), on ``--axis mutations`` the ingest stream's
rate (judged by ``fresh_lag_p95_ms``), the rest of the cell's traffic as it
stands.  Per rate it prints the latency percentiles and how long the loop
ran past the window to answer what was due in it (a growing backlog shows
as a drain that grows with the window).  A rate passes when the drain is
under ``--max-drain-s``, everything due was answered and seen, and the
tail is under ``--limit-ms``; the knee is the highest rate below the first
that fails, where the sweep stops.  The last line is a JSON object with
every rate's numbers and the knee.
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
    ap.add_argument("--axis", choices=("queries", "mutations"), default="queries")
    ap.add_argument("--rates", required=True, help="comma-separated, per second")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--limit-ms", type=float, required=True)
    ap.add_argument("--max-drain-s", type=float, default=0.5)
    args = ap.parse_args()

    import jax

    from bench import harness, spec
    from bench.traffic import Traffic

    class _Continued(Traffic):
        """The cell's traffic on a service that earlier rates have mutated:
        a mutation that would touch a page they deleted is left out."""

        def __init__(self, spec_, dead, **dims):
            super().__init__(spec_, **dims)
            self.dead = dead

        def mutations(self, rng, n):
            return [m for m in super().mutations(rng, n) if m.docid not in self.dead]

    if jax.devices()[0].platform == "cpu":
        print("knee: no accelerator; nothing run", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    tail = "query_p95_ms" if args.axis == "queries" else "fresh_lag_p95_ms"
    built = harness.build(cell, seed=args.seed, trace=False)
    harness.log(f"set-up: {built.setup}")
    rows, knee, dead = [], None, set()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        shape = built.host.shape
        spec_ = (dict(cell.traffic, rate_qps=rate) if args.axis == "queries" else
                 dict(cell.traffic, mutations=dict(cell.traffic["mutations"], rate_per_s=rate)))
        traffic = _Continued(spec_, dead, vocab_size=shape.vocab_size,
                             n_sites=shape.n_sites, n_docs=shape.n_docs)
        res = harness._open_loop(built.svc, traffic, seed=args.seed + 1000 * (i + 1),
                                 seconds=args.seconds, trace=False,
                                 t_process=T_PROCESS)
        dead |= {m.docid for m in res["mutations"] if m.op == "delete"}
        b = res["batches"]
        drain = (b[-1].t_end - b[0].t_start) - args.seconds if b else 0.0
        row = {"rate": rate, **{k: v for k, v in res["e2e"].items() if k != "setup_s"},
               "drain_s": drain, "unanswered": res["unanswered"], "unseen": res["unseen"],
               "batches": len(b), "info": res["info"]}
        ok = (drain < args.max_drain_s and res["unanswered"] == 0 and res["unseen"] == 0
              and row.get(tail, 0.0) <= args.limit_ms)
        row["passes"] = ok
        harness.log(json.dumps(row))
        rows.append(row)
        if not ok:
            break          # past the knee: higher rates only grow the backlog
        knee = rate
    print(json.dumps({"workload": args.workload, "axis": args.axis, "tail": tail,
                      "limit_ms": args.limit_ms, "knee": knee, "rates": rows,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
