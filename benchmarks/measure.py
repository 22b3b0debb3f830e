"""Runs one cell several times, one process a run, and prints the spreads.

    python benchmarks/measure.py --workload <cell> --seeds 1,2,3 [--seconds S]
        [--trace 0|1] [--control 0|1] [--sets 2] [--out chiprun_out/<file>]
        [anything else is passed on to run.py]

What the builder's contract asks of a new cell: sets of runs with the same
seeds in each set, and for each metric the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median. This parent never imports JAX, so each child has the chip alone.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--control", default="0")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out")
    args, passed_on = ap.parse_known_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    command = manifest["command"] + [
        "--workload", args.workload, "--seconds", str(seconds),
        "--trace", args.trace, "--control", args.control,
    ] + passed_on
    records, bad = [], 0
    for which in range(args.sets):
        for seed in args.seeds.split(","):
            t0 = time.perf_counter()
            proc = subprocess.run(command + ["--seed", seed], cwd=ROOT,
                                  capture_output=True, text=True)
            lines = proc.stdout.strip().splitlines()
            record = {"set": which, "seed": int(seed), "rc": proc.returncode,
                      "wall_s": time.perf_counter() - t0, "lines": lines[:-1]}
            try:
                record["result"] = json.loads(lines[-1])
            except (IndexError, ValueError):
                record["stderr"] = proc.stderr[-4000:]
            ok = proc.returncode == 0 and record.get("result", {}).get("correct")
            bad += not ok
            records.append(record)
            print(json.dumps({k: record.get(k) for k in
                              ("set", "seed", "rc", "wall_s", "result", "stderr")}),
                  flush=True)
            for line in lines[:-1]:
                print("   ", line[:600], flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)) or ".", exist_ok=True)
        with open(os.path.join(ROOT, args.out), "w") as f:
            json.dump(records, f, indent=1)
    names = sorted({n for r in records for n in r.get("result", {}).get("metrics", {})})
    for name in names:
        for which in range(args.sets):
            values = [r["result"]["metrics"][name]["value"] for r in records
                      if r["set"] == which and name in r.get("result", {}).get("metrics", {})]
            print(json.dumps({"metric": name, "set": which, "n": len(values),
                              "median": statistics.median(values) if values else None,
                              "min": min(values, default=None),
                              "max": max(values, default=None),
                              "iqr_over_median": spread(values)}), flush=True)
    print(json.dumps({"runs": len(records), "not_correct_or_failed": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
