"""Cells of kind ``train``: the window drives the ``step_fn`` of the
program's own train step, as the configuration's family builds it, fed
seeded token ids drawn anew for every step."""
from __future__ import annotations

import gc

import numpy as np

from chipbench import check, harness, weights
from chipbench.harness import annotate, now, say


class Feed:
    """Token ids and next-token labels for one step after another, from the
    seed; the last position has no label."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.rng = np.random.Generator(np.random.PCG64(seed))
        self.batch, self.seq, self.vocab = batch, seq, vocab

    def next(self):
        ids = self.rng.integers(0, self.vocab, (self.batch, self.seq),
                                dtype=np.int32)
        labels = np.concatenate(
            [ids[:, 1:], np.full((self.batch, 1), -100, np.int32)], axis=1)
        return ids, labels


def flat_names(tree) -> dict:
    """A tree to {leaf's path: value}: ``layers/q_proj``, ``experts/0/up``.
    However deep, and whether its leaves stack on a layer axis or not."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path, simple=True, separator="/"): v
            for path, v in flat}


class Program:
    """The compiled step with its state: one object, driven from the seed
    through its first steps in set-up and handed to the window."""

    def __init__(self, fam, model, t, seed):
        import jax
        import jax.numpy as jnp
        self.fam, self.model, self.t = fam, model, t
        self.step_fn, params, self.opt = fam.train_step(model, t)
        shardings = jax.tree_util.tree_map(lambda a: a.sharding, params)
        del params          # the benchmark's weights take their place
        leaves = fam.leaves(model)
        self.params = weights.make_weights(leaves, seed, shardings=shardings)
        self.feed = Feed(weights.fold_seed(seed), t["batch"], t["seq"],
                         model["vocab_size"])
        sq = lambda a: jnp.sum(jnp.square(a.astype(jnp.float32)))
        self._norms_sq = jax.jit(
            lambda tree: jax.tree_util.tree_map(sq, tree))
        self._change_sq = jax.jit(lambda p, key: jax.tree_util.tree_map(
            lambda a, b: sq(a.astype(jnp.float32) - b.astype(jnp.float32)),
            p, weights._make(key, leaves, jnp.bfloat16)))
        self._key = jax.random.PRNGKey(weights.fold_seed(seed))
        self.loss = None
        self.steps = 0

    def step(self):
        """The window's own call and feed."""
        with annotate("chipbench.feed"):
            ids, labels = self.feed.next()
        with annotate("chipbench.dispatch"):
            self.params, self.opt, self.loss = self.step_fn(
                self.params, self.opt, ids, labels)
        self.steps += 1
        return self.loss

    def first_steps(self, n=3) -> dict:
        """The steps the reference follows: their losses, the first
        gradient's norm by leaf as the optimizer got it (from the first
        moment after one step), the parameters' change by leaf after all."""
        b1 = self.t["adamw"]["beta1"]
        losses = [float(self.step())]
        grad = {k: float(v) ** 0.5 / (1 - b1) for k, v in flat_names(
            self._norms_sq(self.fam.first_moment(self.opt))).items()}
        losses += [float(self.step()) for _ in range(n - 1)]
        change = {k: float(v) ** 0.5 for k, v in flat_names(
            self._change_sq(self.params, self._key)).items()}
        return {"loss": losses, "grad": grad, "change": change}

    def drive(self, until: float) -> int:
        """Steps until the clock passes ``until``, the host one step ahead
        of the device; returns how many ended."""
        n, prev = 0, None
        while True:
            loss = self.step()
            n += 1
            if prev is not None:
                with annotate("chipbench.wait"):
                    prev.block_until_ready()
            prev = loss
            if now() >= until:
                break
        with annotate("chipbench.wait"):
            prev.block_until_ready()
        return n

    def programs(self) -> int:
        """How many programs the step has compiled so far."""
        return self.step_fn.jitted._cache_size()

    def free(self):
        self.params = self.opt = self.loss = None
        gc.collect()


def reference_readings(fam, model, t, seed, mode="f32", fault=None) -> dict:
    """What ``Program.first_steps`` reads, from the family's plain
    reference."""
    leaves = fam.leaves(model)
    w = weights.make_weights(leaves, seed)
    hp = dict(t["adamw"], lr=t["lr"])
    ref = fam.Trainer(w, model, hp, mode=mode, fault=fault)
    del w
    feed = Feed(weights.fold_seed(seed), t["batch"], t["seq"],
                model["vocab_size"])
    losses, grad = [], None
    for _ in range(3):
        loss, sq = ref.step(*feed.next())
        losses.append(loss)
        if grad is None:
            grad = {k: v ** 0.5 for k, v in sq.items()}
    change = {k: v ** 0.5 for k, v in ref.change_sq(
        weights.make_weights(leaves, seed)).items()}
    return {"loss": losses, "grad": grad, "change": change}


def compare(prog: dict, ref: dict, limits: dict) -> check.Compared:
    c = check.Compared()
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        name, gap = f"loss{i + 1}_rel", check.rel(a, b)
        if limits[name] is None:      # no upper reading: read, not compared
            say(f"{name} = {gap:.6g} (not compared)")
        else:
            c.add(name, gap, limits[name])
    gap, leaf = check.worst_leaf_gap(prog["grad"], ref["grad"])
    c.add("grad_norm_gap", gap, limits["grad_norm_gap"])
    moved = check.moved_leaves(ref["grad"])
    gap2, leaf2 = check.worst_leaf_gap(prog["change"], ref["change"], moved)
    c.add("change_norm_gap", gap2, limits["change_norm_gap"])
    say(f"worst gradient leaf {leaf}, worst change leaf {leaf2}; "
        f"{len(moved)} of {len(ref['grad'])} leaves count for the change")
    return c


def kernel_counters(fam, model, t, peak, traced_steps: int) -> dict:
    """What the readers need of a traced part of ``traced_steps`` steps: the
    family's counters for its kernels, and the FLOPs its steps required."""
    return {**fam.train_kernels(model, t, peak),
            "required_flops": traced_steps * t["batch"] * t["seq"]
            * fam.train_flops_per_token(model, t["seq"])}


def run(cell, args, clock_start: float, device: dict) -> str:
    fam, model, t = cell.family, cell.model, cell.traffic
    seconds = float(args.seconds)
    prog = Program(fam, model, t, args.seed)
    harness.mark("step built, weights")
    readings = prog.first_steps()
    say(f"first steps: losses {readings['loss']}")
    prog.loss.block_until_ready()
    harness.mark("first steps")
    compiled = prog.programs()
    setup_s = now() - clock_start

    tokens_per_step = t["batch"] * t["seq"]
    tracer, traced_steps = None, 0
    t0 = now()
    if args.trace:
        steps = prog.drive(t0 + seconds / 3)
        tracer = harness.Tracer(cell.name)
        tracer.start()
        traced_steps = prog.drive(now() + t["trace_seconds"])
        tracer.stop()
        steps += traced_steps + prog.drive(max(t0 + seconds, now()))
    else:
        steps = prog.drive(t0 + seconds)
    window = now() - t0
    if prog.programs() != compiled:
        raise SystemExit("chipbench: the step compiled again inside the "
                         "window. No result.")
    peak_bytes = harness.memory_peak_bytes()
    last_loss = float(prog.loss)
    prog.free()

    t_ref = now()
    ref = reference_readings(fam, model, t, args.seed)
    say(f"the reference's three steps took {now() - t_ref:.1f} s")
    compared = compare(readings, ref, t["limits"])
    device = dict(device, memory_peak_bytes=peak_bytes)
    rate = steps * tokens_per_step / window
    say(f"{steps} steps in {window:.3f} s: {rate:.1f} tokens/s; set-up "
        f"{setup_s:.2f} s: {harness.phases(clock_start)}")
    breakdown = None
    if args.trace:
        metrics, busy, breakdown = harness.traced(
            cell, args, tracer,
            kernel_counters(fam, model, t, args.peak, traced_steps))
        device.update(busy)
    else:
        metrics = {
            "train_tokens_per_s": {"value": rate, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    compared.print()
    return harness.result_line(
        compared=compared, attempted=steps,
        failed=0 if np.isfinite(last_loss) else steps,
        metrics=metrics, device=device, rehearse=args.rehearse,
        breakdown_=breakdown)


def calibrate(cell, args, seed: int, what: set):
    """Readings for the limits (``chipbench/calibrate.py``), each through
    the run's own comparison with the mix's own limits: the control and the
    planted fault have to come out as not correct."""
    fam, model, t = cell.family, cell.model, cell.traffic
    readings = None
    if "program" in what:       # before the reference takes the chip
        prog = Program(fam, model, t, seed)
        readings = prog.first_steps()
        prog.free()
    ref = reference_readings(fam, model, t, seed)

    def row(name, readings):
        c = compare(readings, ref, t["limits"])
        say(f"seed {seed}, {name}:")
        c.print()
        return {"reading": name, "correct": c.correct, **c.as_dict()}

    if readings is not None:
        yield row("program", readings)
    if "control" in what:
        yield row("control:" + t["control_mode"], reference_readings(
            fam, model, t, seed, mode=t["control_mode"]))
    if "faults" in what:
        yield row("fault:half_batch", reference_readings(
            fam, model, t, seed, fault="half_batch"))
