#!/usr/bin/env python3
"""The runs a bound is set from: for one cell, `--sets` sets of `--runs`
runs at the benchmark's own `run_seconds`, run k of every set with the
same seed, each run a process of its own (this one never touches JAX),
then `--traced` traced runs, each with a seed no set has had. Prints
every value, and for each metric the two medians and each set's two
spreads: the interquartile distance over the median
(`statistics.quantiles(n=4)`), which a bound is about five times, and
the driver's (`core/stats.driver_spread`: the range less the one run
farthest from the median), whose mean over the two sets a new or
re-measured cell must keep inside HALF of the metric's bound; says
whether it does. Writes chiprun_out/measure_<cell>.json.

    python3 benchmarks/tools/measure_cell.py --workload gpt2-345m.train-1k --traced 1
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from core import stats  # noqa: E402  (plain Python: no JAX)
SEEDS = (2147483659, 3000000019, 4000000007, 1000003, 2718281828, 3141592653)
TRACED_SEEDS = (1234567891, 987654321, 2222222223, 3999999979)  # none of SEEDS
NOT_METRICS = ("seed", "correct", "failed", "attempted", "memory_peak_bytes")


def run(workload, seed, seconds, trace, log):
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", workload, "--seed", str(seed), "--trace",
           str(trace)] + (["--seconds", str(seconds)] if seconds else [])
    t0 = time.time()
    out = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    with open(log, "a") as f:
        f.write(f"\n===== {' '.join(cmd[2:])} rc={out.returncode} "
                f"{time.time() - t0:.0f}s\n")
        f.write("\n".join(ln for ln in out.stdout.splitlines()
                          if ln.startswith(("[bench]", "{"))))
        if out.returncode:
            f.write("\n" + out.stderr[-1500:])
    if out.returncode:
        print(f"seed {seed}: exit {out.returncode}\n"
              + out.stdout[-1500:] + out.stderr[-1500:], flush=True)
        return None
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs after the sets, each with a seed "
                    "that no set has had")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                    help="where the table and the log go (a run from an "
                    "unpacked archive names the repo's own chiprun_out)")
    args = ap.parse_args()
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"measure_{args.workload}.log")
    open(log, "w").close()
    sets = []
    for s in range(args.sets):
        rows = []
        for seed in SEEDS[:args.runs]:
            line = run(args.workload, seed, args.seconds, 0, log)
            if line is None or not line["correct"]:
                if not rows and not sets:
                    raise SystemExit("the first run failed or was not "
                                     "correct: stopping before more chip "
                                     "time goes")
                if line is None:
                    continue
            row = {k: v["value"] for k, v in line["metrics"].items()}
            row.update(seed=seed, correct=line["correct"],
                       failed=line["failed"], attempted=line["attempted"],
                       memory_peak_bytes=line["device"]["memory_peak_bytes"])
            rows.append(row)
            print(f"set {s} " + json.dumps(row), flush=True)
        sets.append(rows)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    summary = {}
    for m in [k for k in sets[0][0] if k not in NOT_METRICS]:
        per_set = []
        for rows in sets:
            vals = [r[m] for r in rows]
            if m == "setup_s" and rows is sets[0]:
                vals = vals[1:]       # a call's first run may compile
            per_set.append({"median": statistics.median(vals),
                            "spread": stats.spread(vals),
                            "driver_spread": stats.driver_spread(vals),
                            "min": min(vals), "max": max(vals)})
        summary[m] = per_set
        print(m, json.dumps(per_set), flush=True)
        if m != "setup_s":          # judged by its median alone
            mean = statistics.mean(p["driver_spread"] for p in per_set)
            print(f"{m}: the sets' driver spreads average {mean:.5f}, "
                  f"{mean / bounds[m]:.2f} of the bound {bounds[m]} "
                  f"(the gate is 0.50): "
                  + ("inside" if mean <= 0.5 * bounds[m] else "TOO NOISY"),
                  flush=True)
    traced = []
    for seed in TRACED_SEEDS[:args.traced]:
        traced.append(run(args.workload, seed, args.seconds, 1, log))
        print(f"traced seed {seed} " + json.dumps(traced[-1]), flush=True)
    with open(os.path.join(out_dir, f"measure_{args.workload}.json"),
              "w") as f:
        json.dump({"workload": args.workload, "sets": sets,
                   "summary": summary, "traced": traced}, f, indent=1)


if __name__ == "__main__":
    main()
