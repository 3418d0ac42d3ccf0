"""The control of the comparison that decides ``correct``, at a cell's own
size, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        [--iters 300]

For each seed it makes the cell's inputs, solves the first source with
``reference.control_solve`` (the reference in the solver's place, every
stored field rounded through bfloat16, the precision below the float32 the
configuration states for the outer solve) and judges the answer by the
harness's own check (``harness.check``), printing the residual it reads
beside the limit and the verdict.  The control runs on one chip: the
reference is not sharded.  The comparison is sound only where the check
finds every control answer not correct.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--iters", type=int, default=300)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from benchmarks.chip import fields, harness, reference

    devices = harness.require_chips(1)
    cell = harness.load_cell(ROOT, args.workload)
    kappa = float(cell.traffic["kappa"])
    limit = float(cell.config["solver"]["tol"])
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        U, sources = fields.make_inputs(seed, cell.config["lattice"],
                                        cell.traffic)
        t = time.perf_counter()
        x = reference.control_solve(U, sources[0], kappa, args.iters)
        solve = harness.Solve(0, time.perf_counter() - t, args.iters, 0, x)
        r, failed = harness.check([solve], U, sources, kappa, limit,
                                  devices[0])
        readings.append((r, failed == 0))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "iters": args.iters, "control_residual": r,
                          "limit": limit, "correct": failed == 0,
                          "seconds": solve.seconds,
                          "device": devices[0].device_kind}), flush=True)
        del U, sources, x, solve
    print(json.dumps({"workload": args.workload,
                      "control_min": min(r for r, _ in readings),
                      "limit": limit,
                      "all_fail": not any(ok for _, ok in readings)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
