"""The rate sweep of an open-loop cell, made once by hand on the chip:

    python3 chipbench/sweep.py --workload <name> --rates 3,4,5,6,7 --seconds 30 --seed 7

One process, one engine; for each rate a window of ``--seconds`` at that
rate (the mix's own lengths) and its drain. Prints one JSON line per rate:
what came out, the tails, and how much was still queued when the window
closed. The knee is the highest rate whose backlog does not grow; the cell
then runs at four fifths of it (the number goes into the traffic file).
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness, serve, stats
    from chipbench import traffic as gen
    cell, _, _, _ = harness.open_cell(args.workload, args.rehearse)
    d = serve.Driver(cell.family, cell.model, cell.traffic, args.seed)
    d.warm()
    rid = 0
    with open(harness.readings_file("sweep", cell.name, args.rehearse),
              "a") as f:
        for rate in [float(r) for r in args.rates.split(",")]:
            t = dict(cell.traffic, rate_per_s=rate)
            planned = gen.plan(t, args.seed, cell.model["vocab_size"],
                               args.seconds, first_rid=rid)
            rid += len(planned)
            d.reset()
            run = serve.drive(d, planned, t, args.seconds)
            nums = serve.window_numbers(d, run, args.seconds)
            ttft = list(nums["ttft"].values())
            at_close = sum(1 for r, ts in d.times.items()
                           if ts and ts[-1] > args.seconds)
            row = {
                "rate_per_s": rate, "seconds": args.seconds,
                "requests": nums["attempted"],
                "rejected": len(run["rejected"]),
                "unfinished": len(nums["unfinished"]),
                "in_flight_at_close": at_close,
                "drain_s": max((ts[-1] for ts in d.times.values() if ts),
                               default=0.0) - args.seconds,
                "tokens_per_s": nums["tokens_per_s"],
                "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
                "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
                "ttft_under_1s_share": sum(x < 1.0 for x in ttft) / len(ttft),
                "itl_p50_ms": 1e3 * stats.percentile(nums["gaps"], 50),
                "itl_p95_ms": 1e3 * stats.percentile(nums["gaps"], 95),
                "iter_p50_ms": 1e3 * stats.percentile(
                    [r["t1"] - r["t0"] for r in d.iters], 50),
                "mean_decode_rows": sum(len(r["decode_ctx"]) for r in d.iters)
                / max(1, len(d.iters)),
                "gen_late_p95_ms": 1e3 * stats.percentile(run["late"], 95),
            }
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
