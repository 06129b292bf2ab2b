"""Readings that limits are set from, made by hand on the chip:

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 [--what program,control,faults]
    (a fault alone by its name: half_batch, no_mp_exchange)

For each seed, in one process: the program's first steps against the plain
reference (the lower reading), the control (the reference in the precision
below the configuration's, in the program's place) and the planted faults
(the reference with half of the batch left out), each read by the same
comparison as a run's. Prints one JSON line per reading, and writes them to
``chiprun_out/calibrate.<workload>.jsonl``. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,faults")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell, _, args.peak, runner = harness.open_cell(args.workload,
                                                   args.rehearse)
    with open(harness.readings_file("calibrate", cell.name, args.rehearse),
              "a") as f:
        for seed in [int(s) for s in args.seeds.split(",")]:
            for row in runner.calibrate(cell, args, seed,
                                        set(args.what.split(","))):
                line = json.dumps(dict(row, seed=seed, workload=cell.name))
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
