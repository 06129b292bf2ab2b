"""Cells of kind ``serve_open``: the window drives ``submit()`` and
``step()`` of the program's own serving engine, as the configuration's
family builds it, in the loop ``engine.run()`` runs (wall mode), so that the
harness can stamp every token itself, after the iteration's sync. It adds no
scheduling of its own."""
from __future__ import annotations

import gc
import math
import re
import time
from typing import Dict, List

import numpy as np

from chipbench import check, harness, stats, weights
from chipbench import traffic as gen
from chipbench.harness import annotate, now, say

WARM_RID = 1 << 30          # warm-up requests count from here
DRAIN_S = 60.0


class Driver:
    """The engine with the harness's clock around it."""

    def __init__(self, fam, model, t, seed):
        self.fam, self.model, self.t = fam, model, t
        self.weights = weights.make_weights(fam.leaves(model), seed)
        harness.mark("weights")
        self.engine = fam.serve_engine(self.weights, model, t["engine"])
        self.t0 = now()
        self.tokens: Dict[int, List[int]] = {}      # rid -> served tokens
        self.times: Dict[int, List[float]] = {}     # rid -> their stamps
        self.seen: Dict[int, list] = {}     # rid -> [generated, cached] seen
        self.waiting_since: Dict[int, float] = {}   # accepted, no chunk yet
        self.queue_wait: Dict[int, float] = {}  # accepted to first chunk's start
        self.iters: List[dict] = []
        self.slept = False      # the engine was idle since the last step
        self.gc_pauses: List[tuple] = []    # (clock, seconds, generation)

    def on_gc(self, phase: str, info: dict) -> None:
        """Python's collector runs on the engine's thread: each pause is
        kept, so that a stall can be laid at its door or not."""
        if phase == "start":
            self._gc_t = self.clock()
        else:
            self.gc_pauses.append((self._gc_t, self.clock() - self._gc_t,
                                   info["generation"]))

    def clock(self) -> float:
        return now() - self.t0

    def submit(self, p: gen.Planned) -> bool:
        from paddle_tpu.inference import Request
        self.engine._clock = self.clock()
        adm = self.engine.submit(Request(p.prompt, p.max_new_tokens,
                                         request_id=p.rid))
        if adm.accepted:
            self.waiting_since[p.rid] = self.clock()
        return adm.accepted

    def step(self):
        """One engine iteration; stamps the tokens it produced with the
        clock after its sync, and records what the iteration did."""
        eng = self.engine
        t_a = self.clock()
        eng._clock = t_a
        with annotate("chipbench.engine_step"):
            done = eng.step()
        t_b = self.clock()
        rec = {"t0": t_a, "t1": t_b, "decode_ctx": [], "prefill": None,
               "after_idle": self.slept}
        self.slept = False
        for seq in list(eng.active) + list(done):
            rid = seq.req.request_id
            gen0, cached0 = self.seen.get(rid, (0, 0))
            out = seq.generated
            gained = len(out) - gen0
            first = 1 if (gen0 == 0 and gained >= 1) else 0
            decoded = gained - first
            prefilled = (seq.n_cached - cached0) - decoded
            if prefilled > 0:
                rec["prefill"] = (cached0, prefilled, first)
                if rid in self.waiting_since:   # its first chunk ran now
                    self.queue_wait[rid] = t_a - self.waiting_since.pop(rid)
            if decoded:
                rec["decode_ctx"].append(seq.n_cached)
            if gained:
                self.tokens.setdefault(rid, []).extend(out[gen0:])
                self.times.setdefault(rid, []).extend([t_b] * gained)
            self.seen[rid] = (len(out), seq.n_cached)
        self.iters.append(rec)
        return done

    def warm(self):
        """Every program the window can use, and no other: the prefill
        chunk, and each decode bucket as short requests fill the batch one
        a step (one more than half of ``max_batch`` reaches the largest
        bucket; each costs a prefill chunk). Raises unless all are then
        compiled."""
        e = self.t["engine"]
        rng = np.random.Generator(np.random.PCG64(0))
        n = e["max_batch"] // 2 + 1
        for i in range(n):
            self.submit(gen.Planned(
                WARM_RID + i, 0.0,
                rng.integers(0, self.model["vocab_size"], 8).tolist(), n + 4))
        while not self.engine.idle():
            self.step()
        want = {("prefill", e["prefill_chunk"])} | {
            ("decode", b) for b in self.engine.serve.decode_buckets}
        have = set(self.engine._compiled)
        if want - have:
            raise RuntimeError(f"warm-up left {sorted(want - have)} "
                               f"uncompiled")
        self.compiled = have
        self.reset()

    def reset(self):
        """Forget what earlier requests left in the harness's record."""
        for d in (self.tokens, self.times, self.seen, self.queue_wait):
            d.clear()
        self.iters.clear()

    def compiled_in_window(self):
        return sorted(set(self.engine._compiled) - self.compiled)

    def free(self):
        self.engine = None
        gc.collect()


def registry_numbers(engine) -> dict:
    """The program's own registry as plain numbers under its own names; a
    summary gives ``<name>_count`` and ``<name>_sum``, as its exposition
    does."""
    out = {}
    for name, v in engine.registry.snapshot().items():
        if isinstance(v, (int, float)):
            out[name] = v
        elif hasattr(v, "count") and hasattr(v, "sum"):
            out[name + "_count"], out[name + "_sum"] = v.count, v.sum
    return out


def registry_change(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if k in before}


def stop_trace(d: Driver, tracer) -> None:
    tracer.c1 = d.clock()
    tracer.stop()
    tracer.registry1 = registry_numbers(d.engine)


def drive(d: Driver, planned, t, seconds, tracer=None):
    """The window and its drain. Returns per-request facts."""
    pending = list(planned)
    sent: Dict[int, float] = {}       # rid -> due time
    late: List[float] = []
    rejected = set()
    by_rid = {p.rid: p for p in planned}
    d.t0 = now()
    trace_at = seconds / 3 if tracer is not None else None
    tracing = False
    d.gc_pauses.clear()
    gc.callbacks.append(d.on_gc)
    while True:
        clock = d.clock()
        closed = clock >= seconds
        if tracer is not None:
            if not tracing and trace_at is not None and clock >= trace_at:
                tracer.asked = clock
                tracer.registry0 = registry_numbers(d.engine)
                tracer.start()
                tracer.c0, tracing, trace_at = d.clock(), True, None
            elif tracing and clock >= tracer.c0 + t["trace_seconds"]:
                stop_trace(d, tracer)
                tracing = False
        with annotate("chipbench.generator"):
            while not closed and pending and pending[0].due <= clock:
                p = pending.pop(0)
                sent[p.rid] = p.due
                late.append(clock - p.due)
                if not d.submit(p):
                    rejected.add(p.rid)
        if d.engine.idle():
            if closed or not pending:
                break
            d.slept = True
            with annotate("chipbench.sleep"):
                time.sleep(max(0.0, min(pending[0].due - d.clock(), 0.002)))
            continue
        if clock >= seconds + DRAIN_S:
            break
        d.step()
    gc.callbacks.remove(d.on_gc)
    if tracing:
        stop_trace(d, tracer)
    if tracer is not None and tracer.t1 is None:
        raise SystemExit("chipbench: the window ended before its traced "
                         "part began: the mix ran out of requests. No result.")
    return {"sent": sent, "late": late, "rejected": rejected,
            "by_rid": by_rid}


def window_numbers(d: Driver, run: dict, seconds: float) -> dict:
    """The end-to-end arithmetic, on the harness's own stamps."""
    sent, by_rid = run["sent"], run["by_rid"]
    ttft, unfinished = {}, []
    for rid, due in sent.items():
        times = d.times.get(rid, [])
        whole = len(times) >= by_rid[rid].max_new_tokens
        if rid in run["rejected"] or not times:
            ttft[rid] = math.inf
        else:
            ttft[rid] = times[0] - due
        if not whole:
            unfinished.append(rid)
    all_times = [x for ts in d.times.values() for x in ts]
    return {
        "attempted": len(sent), "unfinished": unfinished,
        "tokens_per_s": stats.tokens_in_window(all_times, 0.0, seconds)
        / seconds,
        "ttft": ttft,
        "gaps": stats.gaps_in_window(d.times.values(), 0.0, seconds),
    }


def longest(d: Driver, n: int = 3) -> str:
    """The longest iterations, the longest waits between two of them with
    work at hand and the collector's longest pauses: a stall of the host
    or the device shows here, with when it fell."""
    steps = sorted(d.iters, key=lambda r: r["t0"] - r["t1"])[:n]
    gaps = sorted(((a, b) for a, b in zip(d.iters, d.iters[1:])
                   if not b["after_idle"]),
                  key=lambda ab: ab[0]["t1"] - ab[1]["t0"])[:n]
    return ("longest iterations " + ", ".join(
        f"{1e3 * (r['t1'] - r['t0']):.0f} ms at {r['t0']:.1f} s "
        f"({'chunk + ' if r['prefill'] else ''}{len(r['decode_ctx'])} rows)"
        for r in steps) + "; longest waits between iterations "
        + ", ".join(f"{1e3 * (b['t0'] - a['t1']):.0f} ms at {a['t1']:.1f} s"
                    for a, b in gaps)
        + f"; {len(d.gc_pauses)} collections, the longest "
        + ", ".join(f"{1e3 * s:.0f} ms at {at:.1f} s (generation {g})"
                    for at, s, g in sorted(d.gc_pauses,
                                           key=lambda p: -p[1])[:n]))


def sample_for_check(d: Driver, run: dict, nums: dict, seed: int, k: int):
    """Finished requests to compare, drawn from the seed, the longest among
    them."""
    done = [r for r in run["sent"] if r not in nums["unfinished"]
            and r not in run["rejected"]]
    if not done:
        return []
    size = lambda r: len(run["by_rid"][r].prompt) + len(d.tokens[r])
    biggest = max(done, key=size)
    rng = np.random.Generator(np.random.PCG64([seed, 3]))
    rest = [r for r in done if r != biggest]
    picks = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False))
    return [biggest] + [int(r) for r in picks]


def pad_len(n: int, step: int = 1024) -> int:
    return -(-n // step) * step


def check_shape(t: dict):
    """(padded, last_max): the one shape the reference compiles for a mix,
    from its longest prompt and longest answer."""
    out_max = t["output_tokens"]["max"]
    return pad_len(t["prompt_tokens"]["max"] + out_max), out_max


def token_gaps(fam, w, model, t, prompt, out, served_by=None):
    """For each served position: how far the served token's float32
    reference logit lies below the reference's best, in standard deviations
    of that position's logits. With ``served_by`` = a lower precision, the
    token read is the one that precision puts first (the control)."""
    toks = list(prompt) + list(out[:-1])
    padded, last_max = check_shape(t)
    ref = fam.logits_after(w, model, toks, len(out), padded, last_max)
    if served_by is not None:
        low = fam.logits_after(w, model, toks, len(out), padded, last_max,
                               mode=served_by)
        out = low.argmax(-1)
    rows = np.arange(len(out))
    return (ref.max(-1) - ref[rows, np.asarray(out)]) / ref.std(-1)


def compare(widest_gap: float, nums: dict, limits: dict) -> check.Compared:
    """The numbers that decide ``correct`` for a served window."""
    c = check.Compared()
    c.add("token_gap_std", widest_gap, limits["token_gap_std"])
    c.add("never_answered", len(nums["unfinished"]), 0)
    return c


def run(cell, args, clock_start: float, device: dict) -> str:
    fam, model, t = cell.family, cell.model, cell.traffic
    seconds = float(args.seconds)
    d = Driver(fam, model, t, args.seed)
    harness.mark("engine")
    d.warm()
    harness.mark("warm-up")
    planned = gen.plan(t, args.seed, model["vocab_size"], seconds)
    harness.mark("requests")
    setup_s = now() - clock_start
    tracer = harness.Tracer(cell.name) if args.trace else None
    run_ = drive(d, planned, t, seconds, tracer)
    peak_bytes = harness.memory_peak_bytes()
    compiled = d.compiled_in_window()
    if compiled:
        raise SystemExit(f"chipbench: programs compiled inside the window: "
                         f"{compiled}. No result.")
    nums = window_numbers(d, run_, seconds)
    say(f"{nums['attempted']} requests, {len(nums['unfinished'])} unfinished, "
        f"{len(run_['rejected'])} rejected, {nums['tokens_per_s']:.1f} "
        f"tokens/s, {len(d.iters)} iterations; set-up {setup_s:.2f} s: "
        f"{harness.phases(clock_start)}")
    say(longest(d))
    w = d.weights
    d.free()

    t_ref = now()
    picks = sample_for_check(d, run_, nums, args.seed, t["check_requests"])
    worst, n_tok = 0.0, 0
    for rid in picks:
        g = token_gaps(fam, w, model, t, run_["by_rid"][rid].prompt,
                       d.tokens[rid])
        worst, n_tok = max(worst, float(g.max())), n_tok + len(g)
    compared = compare(worst if picks else math.inf, nums, t["limits"])
    say(f"reference over {len(picks)} requests, {n_tok} served tokens, took "
        f"{now() - t_ref:.1f} s")

    device = dict(device, memory_peak_bytes=peak_bytes)
    breakdown = None
    if args.trace:
        metrics, busy, breakdown = harness.traced(
            cell, args, tracer,
            trace_counters(d, run_, nums, tracer, args.peak))
        device.update(busy)
    else:
        metrics = {m["name"]: {"value": end_to_end(m["name"], nums, setup_s),
                               "unit": m["unit"]} for m in cell.end_to_end}
    compared.print()
    return harness.result_line(
        compared=compared, attempted=nums["attempted"],
        failed=len(nums["unfinished"]), metrics=metrics, device=device,
        rehearse=args.rehearse, breakdown_=breakdown)


TAIL = re.compile(r"(ttft|itl)_p(\d+)_ms")


def end_to_end(name: str, nums: dict, setup_s: float) -> float:
    """An end-to-end metric by its name: ``setup_s``, ``serve_tokens_per_s``,
    and any percentile of the first-token times (``ttft_p<q>_ms``, over
    every request due in the window, one that never answered counting as
    the worst) or of the gaps between tokens (``itl_p<q>_ms``, all gaps of
    the window pooled)."""
    if name == "setup_s":
        return setup_s
    if name == "serve_tokens_per_s":
        return nums["tokens_per_s"]
    m = TAIL.fullmatch(name)
    if not m:
        raise SystemExit(f"chipbench: a serving cell has no end-to-end "
                         f"metric {name!r}")
    values = list(nums["ttft"].values()) if m.group(1) == "ttft" \
        else nums["gaps"]
    return 1e3 * stats.percentile(values, int(m.group(2))) if values \
        else math.inf


def trace_counters(d, run_, nums, tracer, peak) -> dict:
    """What the readers need: from the harness's own record, lists over the
    whole window; over the traced part of it, the FLOPs its iterations
    required and the family's counters for its kernels; and the change of
    the program's own registry over it, under the registry's names."""
    fam, model, t = d.fam, d.model, d.t
    c0, c1 = tracer.c0, tracer.c1
    its = [r for r in d.iters if c0 <= r["t0"] and r["t1"] <= c1]
    # the profiler's start and stop hold the engine's thread for seconds, so
    # the queues are read from the requests due before it was asked for
    early = {rid for rid in run_["sent"]
             if run_["by_rid"][rid].due < tracer.asked - 0.5}
    need = 0.0
    for r in its:
        if r["prefill"]:
            start, n, first = r["prefill"]
            need += fam.forward_flops(
                model, n, n * start + n * (n + 1) // 2, first)
        if r["decode_ctx"]:
            ctx = r["decode_ctx"]
            need += fam.forward_flops(model, len(ctx), sum(ctx), len(ctx))
    return {
        **fam.serve_kernels(model, t["engine"], its, peak),
        **registry_change(tracer.registry0, tracer.registry1),
        "gen_late_s": [s for rid, s in zip(run_["sent"], run_["late"])
                       if rid in early],
        "queue_wait_s": [s for rid, s in d.queue_wait.items()
                         if rid in early],
        "tokens_per_s": [nums["tokens_per_s"]],
        "iter_s": [r["t1"] - r["t0"] for r in its],
        "occupancy": [len(r["decode_ctx"]) / t["engine"]["max_batch"]
                      for r in its],
        "required_flops": need,
    }


def calibrate(cell, args, seed: int, what: set):
    """Readings for the limit: a short window at the cell's own load, then
    the program's widest gap and the control's on the same requests, each
    through the run's own comparison with the mix's own limits: the control
    has to come out as not correct."""
    fam, model, t = cell.family, cell.model, cell.traffic
    d = Driver(fam, model, t, seed)
    if not getattr(calibrate, "warmed", False):
        d.warm()            # later seeds find the programs in the process
        calibrate.warmed = True
    planned = gen.plan(t, seed, model["vocab_size"], args.seconds)
    run_ = drive(d, planned, t, args.seconds)
    nums = window_numbers(d, run_, args.seconds)
    w = d.weights
    d.free()
    picks = sample_for_check(d, run_, nums, seed, t["check_requests"])
    served_by = {"program": None, "control": t["control_mode"]}
    for reading in ("program", "control"):
        if reading not in what:
            continue
        worst, n_tok = 0.0, 0
        for rid in picks:
            g = token_gaps(fam, w, model, t, run_["by_rid"][rid].prompt,
                           d.tokens[rid], served_by=served_by[reading])
            worst, n_tok = max(worst, float(g.max())), n_tok + len(g)
        c = compare(worst if picks else math.inf, nums, t["limits"])
        name = reading + (":" + t["control_mode"] if served_by[reading]
                          else "")
        say(f"seed {seed}, {name}: {len(picks)} requests, {n_tok} tokens")
        c.print()
        yield dict(reading=name, requests=len(picks), tokens=n_tok,
                   correct=c.correct, **c.as_dict())
