"""Run one cell several times, one process after another, and report the
spread of each metric.

    python3 bench/tools/sets.py --workload <cell> --seeds 1,2,3,4,5,6 \
        [--sets 2] [--seconds 30] [--trace 0] [--out runs.jsonl]

Each run is ``bench/run.py`` in a child process; this parent never
touches JAX, so the child has the chip to itself.  With ``--sets 2`` the
seeds run twice, the second set after the first, as a bound is measured.
Each result line is appended to ``--out`` with its set, seed, exit code
and wall seconds; the summary gives, for each set and metric, the median
and the spread: the distance between the first and third quartile of
``statistics.quantiles(values, n=4)``, as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

RUN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "run.py")


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    rows = []
    for k in range(args.sets):
        for seed in seeds:
            t = time.perf_counter()
            p = subprocess.run(
                [sys.executable, RUN, "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(args.seconds),
                 "--trace", str(args.trace)],
                capture_output=True, text=True)
            wall = time.perf_counter() - t
            lines = p.stdout.strip().splitlines()
            try:
                line = json.loads(lines[-1]) if lines else None
            except json.JSONDecodeError:
                line = None
            row = {"set": k, "seed": seed, "rc": p.returncode,
                   "wall_s": wall, "line": line,
                   "stderr_tail": p.stderr[-1500:]}
            rows.append(row)
            print(json.dumps({k2: row[k2] for k2 in ("set", "seed", "rc",
                                                     "wall_s")}
                             | {"correct": line and line["correct"],
                                "metrics": line and {
                                    m: v["value"] for m, v in
                                    line["metrics"].items()},
                                "calls_ms": line and line.get("calls_ms"),
                                "checks": line and line.get("checks")}),
                  flush=True)
            if line is None:
                print(p.stderr[-3000:], flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    for k in range(args.sets):
        lines = [r["line"] for r in rows if r["set"] == k and r["line"]]
        names = sorted({m for l in lines for m in l["metrics"]})
        for m in names:
            vals = [l["metrics"][m]["value"] for l in lines
                    if m in l["metrics"]]
            print(json.dumps({"set": k, "metric": m, "n": len(vals),
                              "median": statistics.median(vals),
                              "spread": spread(vals)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
