"""The one generator of serving traffic. A mix is a data file of
parameters; this reads it.

Open loop: Poisson arrivals at the mix's fixed ``rate_per_s``; prompt and
output lengths lognormal (``median``, ``sigma``), clipped to ``min`` and
``max``, in cycles of ``sizes_cycle`` requests; prompts share nothing.

Every seed gets the same work: arrival times and lengths come from the
mix's own ``shape_seed`` and never from the run's seed, which draws the
token ids (and the weights). A window holds some hundred requests, and
there the order in which long prompts meet decides the tail: with the
lengths permuted by the seed, ``ttft_p95_ms`` moved by 20 % from seed to
seed (PERF.md section 6). So a fresh seed does not change the schedule a
cell is judged on; the cells' ``why`` says so.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due: float                  # seconds from the window's start
    prompt: List[int]
    max_new_tokens: int


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def sizes(t: dict, n: int):
    """n (prompt, output) lengths: the mix's fixed cycle, repeated."""
    cycle = t["sizes_cycle"]
    shape = np.random.Generator(np.random.PCG64(t["shape_seed"]))
    base = list(zip(_lengths(shape, t["prompt_tokens"], cycle),
                    _lengths(shape, t["output_tokens"], cycle)))
    return [base[i % cycle] for i in range(n)]


def arrivals(t: dict, horizon: float) -> List[float]:
    """Due times in [0, horizon): the same for every seed."""
    shape = np.random.Generator(np.random.PCG64([t["shape_seed"], 7]))
    out, now = [], 0.0
    while True:
        now += shape.exponential(1.0 / t["rate_per_s"])
        if now >= horizon:
            return out
        out.append(now)


def plan(t: dict, seed: int, vocab: int, horizon: float,
         first_rid: int = 0) -> List[Planned]:
    """The requests of one run: one for each arrival before the horizon."""
    due = arrivals(t, horizon)
    ids = np.random.Generator(np.random.PCG64([seed, 2]))
    return [Planned(first_rid + i, d, ids.integers(0, vocab, int(p)).tolist(),
                    int(o))
            for i, (d, (p, o)) in enumerate(zip(due, sizes(t, len(due))))]
