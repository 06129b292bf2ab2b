"""Where a serving cell's ``token_gap_std`` comes from, made by hand on the
chip: the gap of each request against the context it was served at.

    python3 chipbench/gaps.py --workload <name> --seeds 1,2 \
        --prompts 1024,1792,3072,6144,12288,24576 [--outputs 192] [--control 1]

For each seed, in one process: weights from the seed, one request of each
prompt length (ids from the seed, all due at once, so chunks carry decode
rows as in a window), served to the end by the cell's own engine; then each
request through the run's own arithmetic (``serve.token_gaps``): its widest
and mean gap, how many served tokens are not the reference's first, and
with ``--control 1`` the control's widest gap on the same request. A prompt shorter than what a
model's attention is limited to (GLM-5.2: ``index_topk`` 2048, where the
selection is everything and the sparse path is the dense one) is the
witness for what the rest of the model's rounding gives alone; a fault that
grows with the context shows as a mean that grows, rounding at a hard
choice as rare spikes over a flat mean. Prints one JSON line per request,
and writes them to ``chiprun_out/gaps.<workload>.jsonl``. Nothing is
compared with a limit; not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_seed(cell, args, seed: int, lengths, warm: bool):
    """One seed's rows. A generator, as ``serve.calibrate`` is: its engine
    and its 9 GB of weights go when it is exhausted, before the next seed's
    are made."""
    import numpy as np
    from chipbench import harness, serve
    from chipbench import traffic as gen
    fam, m, t = cell.family, cell.model, cell.traffic
    padded, last_max = serve.check_shape(t)
    d = serve.Driver(fam, m, t, seed)
    if warm:
        d.warm()            # later seeds find the programs in the process
    ids = np.random.Generator(np.random.PCG64([seed, 2]))
    planned = [gen.Planned(i, 0.0, ids.integers(
        0, m["vocab_size"], n).tolist(), args.outputs)
        for i, n in enumerate(lengths)]
    serve.drive(d, planned, t, 0.5)
    w = d.weights
    d.free()
    for p in planned:
        out = d.tokens.get(p.rid, [])
        if len(out) < p.max_new_tokens:
            harness.say(f"seed {seed}: request {p.rid} unfinished")
            continue
        # ``serve.token_gaps``'s arithmetic, the reference's logits kept for
        # the control's reading of the same request
        toks = list(p.prompt) + list(out[:-1])
        ref = fam.logits_after(w, m, toks, len(out), padded, last_max)
        gaps = lambda served: (
            ref.max(-1) - ref[np.arange(len(out)), np.asarray(served)]
        ) / ref.std(-1)
        g = gaps(out)
        row = {"workload": cell.name, "seed": seed,
               "prompt_tokens": len(p.prompt), "served": len(out),
               "gap_max": float(g.max()), "gap_at": int(g.argmax()),
               "gap_mean": float(g.mean()), "not_first": int((g > 0).sum())}
        if args.control:
            c = gaps(fam.logits_after(w, m, toks, len(out), padded, last_max,
                                      mode=t["control_mode"]).argmax(-1))
            row["control_gap_max"] = float(c.max())
            row["control_gap_mean"] = float(c.mean())
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prompts", required=True)
    ap.add_argument("--outputs", type=int, default=192)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell, _, _, _ = harness.open_cell(args.workload, args.rehearse)
    lengths = [int(p) for p in args.prompts.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    with open(harness.readings_file("gaps", cell.name, args.rehearse),
              "a") as f:
        for seed in seeds:
            for row in one_seed(cell, args, seed, lengths, seed == seeds[0]):
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
