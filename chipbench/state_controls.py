"""The two controls of a model with recurrent state, made by hand on the chip:
would the run's own comparison see a program that lost the state between
engine chunks, or one without the recurrence?

    python3 chipbench/state_controls.py --workload <name> --seeds 1,2 \
        --prompts 640,1100,1800 [--outputs 256]

For each seed, in one process: weights from the seed, one request of each
prompt length (every one past 512 tokens, so that its state crosses an
engine chunk; ids from the seed, all due at once), served to the end by the
cell's own engine. Then three readings through the run's own arithmetic
(``serve.token_gaps``) and its own comparison with the mix's own limits
(``serve.compare``): the program's served tokens, and in their place the
tokens that the float32 reference puts first with the state zeroed at every
multiple of 512 positions (``f32:reset``) and with ``S C`` left out of ``y``
(``f32:norecur``; the family's ``logits_after`` plants both). The two
controls have to read not ``correct``: if either passes, the weights' starts
hide the mechanism from the comparison (``families/falcon_h1.py``, THE
STARTS). Prints one JSON line per reading, and writes them to
``chiprun_out/state_controls.<workload>.jsonl``. Not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

READINGS = {"program": None, "control:reset": "f32:reset",
            "control:norecur": "f32:norecur"}


def one_seed(cell, args, seed: int, lengths, warm: bool):
    """One seed's rows; a generator, as ``gaps.one_seed`` is."""
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
    for reading, served_by in READINGS.items():
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
    ap.add_argument("--prompts", default="640,1100,1800")
    ap.add_argument("--outputs", type=int, default=256)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell, _, _, _ = harness.open_cell(args.workload, args.rehearse)
    lengths = [int(p) for p in args.prompts.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    with open(harness.readings_file("state_controls", cell.name,
                                    args.rehearse), "a") as f:
        for seed in seeds:
            for row in one_seed(cell, args, seed, lengths, seed == seeds[0]):
                line = json.dumps(row)
                print(line, flush=True)
                f.write(line + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
