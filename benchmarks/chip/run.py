"""The chip benchmark: seconds per solve of the configuration's system, on
a TPU only.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  One process sets up the cell named in
``BENCHMARK.json`` (the system its configuration names, inputs from
``--seed`` on the device, every program warmed up or loaded from the
persistent compile cache), runs a closed loop of solves until
``--seconds`` have passed, checks every solve of the window against the
system's plain reference, and prints one JSON line last on standard
output.  ``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window and reports its per-layer metrics.  The numbers compared
for ``correct`` come last on standard error, each beside its limit.  It
exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, or a chip that ``peaks.json`` does not know.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu's own logs would go to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from benchmarks.chip.harness import BenchError, run

    try:
        out = run(ROOT, args.workload, args.seed, args.seconds,
                  bool(args.trace), T_START)
    except BenchError as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
