"""Run one cell several times, one process per run, and report the spread.

    python3 benchmarks/chip/measure.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--sets 2] [--trace 0|1] [--out runs.jsonl]

Each run is ``benchmarks/chip/run.py`` in a process of its own, as a check
makes it; this process never imports JAX, so each run has the chips to
itself.  Every seed runs once per set, in the same order in every set.
Each run's result line, exit code, start (epoch seconds), wall time and
the end of its standard error, which lists each solve's seconds, are
appended to ``--out``; the summary gives, per set and metric, the median
and the spread (the distance between the first and third quartile from
``statistics.quantiles(values, n=4)``, over the median).
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def run_once(workload, seed, seconds, trace):
    cmd = [sys.executable, str(ROOT / "benchmarks/chip/run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    started = time.time()
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"workload": workload, "seed": seed, "trace": trace,
            "rc": p.returncode, "started": started, "wall_s": wall, "result": result,
            "stderr_tail": p.stderr[-3000:]}


def summarise(runs):
    by_set = {}
    for r in runs:
        if r["result"]:
            for k, m in r["result"]["metrics"].items():
                by_set.setdefault(r["set"], {}).setdefault(k, []).append(
                    m["value"])
    for s, metrics in sorted(by_set.items()):
        for k, vals in sorted(metrics.items()):
            print(f"set {s} {k}: n={len(vals)} median="
                  f"{statistics.median(vals)!r} spread={spread(vals)!r} "
                  f"values={vals}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = []
    for s in range(args.sets):
        for seed in seeds:
            r = run_once(args.workload, seed, args.seconds, args.trace)
            r["set"] = s
            runs.append(r)
            res = r["result"] or {}
            print(json.dumps({"set": s, "seed": seed, "rc": r["rc"],
                              "wall_s": r["wall_s"],
                              "correct": res.get("correct"),
                              "attempted": res.get("attempted"),
                              "metrics": res.get("metrics"),
                              "checks": res.get("checks")}), flush=True)
            if r["rc"] != 0:
                print(r["stderr_tail"], flush=True)
            if args.out:
                args.out.parent.mkdir(parents=True, exist_ok=True)
                with args.out.open("a") as f:
                    f.write(json.dumps(r) + "\n")
    summarise(runs)
    return 0 if all(r["rc"] == 0 and r["result"] and r["result"]["correct"]
                    for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
