"""Controls that take one mechanism out of the reference, made by hand on the
chip: would the run's own comparison see a program without it?

    python3 chipbench/variant_controls.py --workload <name> --seeds 1,2 \
        --variants nowindow,nogate [--prompts 4096,9000] [--outputs 256]

For each seed, in one process: weights from the seed, one request of each
prompt length (ids from the seed, all due at once), served to the end by
the cell's own engine. Then one reading of the program's served tokens and,
for each variant, one of the tokens that the float32 reference with that
mechanism taken out (``f32:<variant>``; the family's ``logits_after``
plants it) puts first, each through the run's own arithmetic
(``serve.token_gaps``) and its own comparison with the mix's own limits
(``serve.compare``). Every control has to read not ``correct``: if one
passes, the comparison does not see the mechanism. Prints one JSON line per
reading and writes them to ``chiprun_out/variant_controls.<workload>.jsonl``.
Not part of a benchmark run. ``state_controls.py`` is this tool for a model
with recurrent state, with its two variants fixed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_seed(cell, args, seed: int, lengths, variants, warm: bool):
    """One seed's rows; a generator, as ``state_controls.one_seed`` is."""
    import numpy as np
    from chipbench import harness, serve
    from chipbench import traffic as gen
    fam, m, t = cell.family, cell.model, cell.traffic
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
    done = [p for p in planned
            if len(d.tokens.get(p.rid, [])) >= p.max_new_tokens]
    for p in planned:
        if p not in done:
            harness.say(f"seed {seed}: request {p.rid} unfinished")
    readings = {"program": None,
                **{f"control:{v}": f"f32:{v}" for v in variants}}
    for reading, served_by in readings.items():
        worst, n_tok = 0.0, 0
        for p in done:
            g = serve.token_gaps(fam, w, m, t, p.prompt, d.tokens[p.rid],
                                 served_by=served_by)
            worst, n_tok = max(worst, float(g.max())), n_tok + len(g)
        c = serve.compare(worst if done else float("inf"),
                          {"unfinished": [p.rid for p in planned
                                          if p not in done]}, t["limits"])
        harness.say(f"seed {seed}, {reading}: {len(done)} requests, "
                    f"{n_tok} tokens")
        c.print()
        yield dict(workload=cell.name, seed=seed, reading=reading,
                   prompts=[len(p.prompt) for p in done], tokens=n_tok,
                   correct=c.correct, **c.as_dict())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", required=True)
    ap.add_argument("--prompts", default="4096,9000")
    ap.add_argument("--outputs", type=int, default=256)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell, _, _, _ = harness.open_cell(args.workload, args.rehearse)
    lengths = [int(p) for p in args.prompts.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = args.variants.split(",")
    with open(harness.readings_file("variant_controls", cell.name,
                                    args.rehearse), "a") as f:
        for seed in seeds:
            for row in one_seed(cell, args, seed, lengths, variants,
                                seed == seeds[0]):
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
