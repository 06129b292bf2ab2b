"""One run of one cell:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result's JSON object. Without the
chips the cell asks for it exits non-zero and prints no result;
``--rehearse`` instead runs the cell's control flow at a tiny size on the
CPU and prints a line with ``"correct": false`` and no device metric.
"""
from __future__ import annotations

import time

CLOCK_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import harness
    cell, device, args.peak, runner = harness.open_cell(args.workload,
                                                        args.rehearse)
    if args.seconds is None:
        args.seconds = cell.benchmark["run_seconds"]
    harness.say(f"seed {args.seed}, {args.seconds} s, trace {args.trace}")
    line = runner.run(cell, args, CLOCK_START, device)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
