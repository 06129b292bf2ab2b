"""How far the program's selection lies from the reference's, made by hand
on the chip for a cell whose family selects (``selection_overlap``):

    python3 chipbench/overlap.py --workload <name> --seeds 1,2,3 [--tokens 16384]

For each seed: weights and a prompt of ``--tokens`` ids from the seed, and at
128 positions spread over the prompt the share of the first layer's selected
positions that the program (bf16) and the reference (float32) both select.
Prints one JSON line per seed, and writes them to
``chiprun_out/overlap.<workload>.jsonl``. Nothing is compared with a limit;
not part of a benchmark run.
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
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import numpy as np
    from chipbench import harness, weights
    cell, _, _, _ = harness.open_cell(args.workload, args.rehearse)
    fam, m = cell.family, cell.model
    n = min(args.tokens, 256) if args.rehearse else args.tokens
    rows = np.linspace(0, n - 1, 128).astype(np.int32)
    with open(harness.readings_file("overlap", cell.name, args.rehearse),
              "a") as f:
        for seed in [int(s) for s in args.seeds.split(",")]:
            w = weights.make_weights(fam.leaves(m), seed)
            ids = np.random.Generator(np.random.PCG64([seed, 2])).integers(
                0, m["vocab_size"], n)
            share = np.asarray(fam.selection_overlap(w, m, ids, rows))
            past = share[rows >= m["index_topk"]]
            line = json.dumps({
                "workload": cell.name, "seed": seed, "tokens": n,
                "rows": len(rows), "rows_past_topk": int(len(past)),
                "overlap_mean": float(share.mean()),
                "overlap_min": float(share.min()),
                "overlap_past_topk_mean": float(past.mean()) if len(past)
                else None,
                "overlap_past_topk_min": float(past.min()) if len(past)
                else None})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
            del w
    return 0


if __name__ == "__main__":
    sys.exit(main())
