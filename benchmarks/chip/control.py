"""The control of the comparison that decides ``correct``, at a cell's own
size, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

It holds the cell to its system's rules (``validate``) before it looks
for a chip.  For each seed it makes the cell's inputs, answers the first
request with the control of the cell's system (its plain reference in the
solver's place, one precision below what the configuration states:
``control`` in ``benchmarks/chip/systems/<system>.py``) and judges the
answer by the system's own ``check``, printing each number it reads
beside its limit and the verdict.  The control runs on one chip.  The
comparison is sound only where the check finds every control answer not
correct.
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
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from benchmarks.chip import harness

    cell = harness.load_cell(ROOT, args.workload)
    system = harness.load_system(ROOT, cell.config["system"])
    system.validate(cell.config, cell.traffic, cell.chips)
    devices = harness.require_chips(1)
    config, traffic = cell.config, cell.traffic
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        shared, requests = system.make_inputs(seed, config, traffic,
                                              devices)
        t = time.perf_counter()
        x = system.control(shared, requests[0], config, traffic)
        solve = harness.Solve(0, time.perf_counter() - t, {}, x)
        failed, checks = system.check([solve], shared, requests, config,
                                      traffic, devices[0])
        readings.append((checks, failed == 0))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "checks": checks, "correct": failed == 0,
                          "seconds": solve.seconds,
                          "device": devices[0].device_kind}), flush=True)
        del shared, requests, x, solve
    print(json.dumps({"workload": args.workload,
                      "control_min": {k: min(c[k]["value"]
                                             for c, _ in readings)
                                      for k in readings[0][0]},
                      "all_fail": not any(ok for _, ok in readings)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
