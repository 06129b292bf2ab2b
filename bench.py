"""Benchmark: Llama causal-LM training throughput on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric is model FLOPs utilization (MFU) for a bf16 Llama training step
(fwd+bwd+AdamW) at seq 2048 — the BASELINE.json north-star metric shape
(target >= 0.45 on v5p-128; vs_baseline = mfu / 0.45).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np


def peak_flops(device) -> float:
    """Per-chip dense bf16 peak from the package's one table; a device it
    does not know (a CPU among them) is an error, never a default."""
    from paddle_tpu.observability.metrics import require_peak_flops
    return require_peak_flops(device)


def run_config(config, batch, seq, dev, policy="save_mlp"):
    """Train-step MFU for one model config. Returns (mfu, tok_s, dt, loss).

    policy: remat policy. 'save_mlp' (keep flash outputs AND the gate/up
    matmul outputs — half the forward matmul FLOPs — across the remat
    boundary) wins wherever the residuals fit: flagship 0.621 vs 0.612
    (save_attn), 13B-geometry 0.642 vs 0.602, hd64 0.466. The 7B
    geometry (L=4, B=8) cannot hold the extra [B, S, I] residuals and
    keeps 'save_attn'; 'dots'/no-remat exceed memory at all these
    shapes."""
    import jax
    from paddle_tpu.models.llama import (ParallelConfig, build_train_step,
                                         train_flops_per_token)
    on_tpu = dev.platform != "cpu"
    parallel = ParallelConfig(remat=True, remat_policy=policy,
                              use_flash=on_tpu)
    step, params, opt = build_train_step(config, parallel, lr=1e-4)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    # warmup (compile) + 2 steps
    for _ in range(3):
        params, opt, loss = step(params, opt, ids, labels)
    jax.block_until_ready(loss)

    n_steps = 10 if on_tpu else 2
    trials = 3 if on_tpu else 1
    dt = 1e9
    for _ in range(trials):  # best-of-trials: host jitter is one-sided
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt, loss = step(params, opt, ids, labels)
        jax.block_until_ready(loss)
        dt = min(dt, (time.perf_counter() - t0) / n_steps)

    tok_s = batch * seq / dt
    mfu = tok_s * train_flops_per_token(config, seq) / peak_flops(dev)
    del params, opt
    return mfu, tok_s, dt, float(jax.device_get(loss))


def hbm_bw(device) -> float:
    """Per-chip datasheet HBM bytes/s from the package's one table; an
    unknown device is an error."""
    from paddle_tpu.observability.ledger import hbm_bw_per_device
    bw, source = hbm_bw_per_device(device)
    if bw is None:
        raise RuntimeError(f"no HBM bandwidth for this device ({source})")
    return bw


def trace_device_ms(run, span_prefix, reps=3):
    """Run `run()` reps times under the jax profiler and return the mean
    duration (ms) of device spans whose name starts with span_prefix, or
    None if no such span was recorded (e.g. non-TPU backends)."""
    import glob
    import gzip
    import tempfile

    import jax

    durs = []
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(reps):
                run()
        for fpath in glob.glob(td + "/**/*.trace.json.gz", recursive=True):
            with gzip.open(fpath, "rt") as fh:
                tr = json.load(fh)
            for e in tr.get("traceEvents", []):
                if e.get("ph") == "X" and \
                        e.get("name", "").startswith(span_prefix):
                    durs.append(e["dur"])
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3


def device_time_ms(fn, args, name="timedfn", reps=3):
    """Mean ON-DEVICE time of one jitted call, from profiler trace events.

    Wall-clock includes the host's dispatch of every call, which for
    kernels in the single-digit-ms range can be most of the reading. The
    profiler's device-side `jit_<name>` spans are the ground truth."""
    import jax

    fn.__name__ = name
    f = jax.jit(fn)
    o = f(*args)
    jax.device_get(jnp_ravel_first(o))

    def run():
        o = f(*args)
        jax.device_get(jnp_ravel_first(o))

    ms = trace_device_ms(run, f"jit_{name}(", reps=reps)
    if ms is None:  # profiler unavailable (non-TPU backends): fall back
        print(f"WARNING: no device trace events for {name}; falling back "
              "to wall-clock (includes host dispatch)",
              file=sys.stderr)
        t0 = time.perf_counter()
        for _ in range(reps):
            o = f(*args)
        jax.device_get(jnp_ravel_first(o))
        return (time.perf_counter() - t0) / reps * 1e3
    return ms


_MEASURED_BW = {}


def measured_hbm_bw(dev):
    """Achievable HBM read bandwidth (bytes/s), measured with a trivial
    streaming reduce over 1 GiB of bf16. The datasheet number (819 GB/s on
    v5e) is not attainable by real kernels, so floors computed against it
    can read x_of_floor < 1.0 — an impossibility. Floors below are
    reported against this measured ceiling instead."""
    kind = getattr(dev, "device_kind", "cpu")
    if kind in _MEASURED_BW:
        return _MEASURED_BW[kind]
    import jax
    import jax.numpy as jnp
    n = 1 << 29  # 512Mi bf16 elements = 1 GiB
    big = jax.jit(lambda k: (jax.random.uniform(k, (n,), jnp.float32) - 0.5)
                  .astype(jnp.bfloat16))(jax.random.PRNGKey(0))
    jax.device_get(big.ravel()[0])
    ms = device_time_ms(lambda x: jnp.sum(x.astype(jnp.float32)), (big,),
                        "hbmread")
    del big
    bw = (n * 2) / (ms / 1e3)
    _MEASURED_BW[kind] = bw
    return bw


def jnp_ravel_first(o):
    import jax.numpy as jnp
    leaf = o[0] if isinstance(o, (tuple, list)) else o
    return jnp.ravel(leaf)[0]


def run_decode(config, batch, dev, prompt_len=128, new_tokens=128,
               quantize=False):
    """Warm greedy-generation decode cost. Returns
    (ms_per_step, tok_s, floor_ms, measured_floor_ms).

    ms_per_step comes from the profiler's device span of the decode scan
    (jit_generate_scan) alone — the prefill executable is a separate span,
    so no wall-clock subtraction (which previously produced x_of_floor
    readings < 1.0, a physical impossibility). floor_ms is the weight-read
    bound against the DATASHEET bandwidth; measured_floor_ms against the
    achievable bandwidth from measured_hbm_bw — decode is HBM-bound, every
    step streams all params once (KV-cache traffic is comparatively small
    at this context length). quantize=True runs weight-only int8 (halved
    weight stream; floors computed against the int8 bytes)."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama import (count_params, generate_scan_bucket,
                                         greedy_generate, init_llama_params,
                                         quantize_llama_int8)
    params = init_llama_params(config, seed=0)
    if quantize:
        params = quantize_llama_int8(params)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, config.vocab_size,
                         (batch, prompt_len)).astype(np.int32)

    greedy_generate(params, prompt, config, new_tokens)  # compile
    n_steps = generate_scan_bucket(new_tokens)
    scan_ms = trace_device_ms(
        lambda: greedy_generate(params, prompt, config, new_tokens),
        "jit_generate_scan(", reps=3)
    if scan_ms is None:  # off-TPU: wall-clock with prefill subtraction
        if dev.platform != "cpu":
            print("WARNING: no jit_generate_scan device span; decode "
                  "timing falling back to dispatch-inflated wall-clock",
                  file=sys.stderr)

        def timed(n_new):
            greedy_generate(params, prompt, config, n_new)
            t0 = time.perf_counter()
            greedy_generate(params, prompt, config, n_new)
            return time.perf_counter() - t0
        # best-of-3 each term, clamped: single-shot jitter can make the
        # difference negative (ADVICE r3)
        full = min(timed(new_tokens) for _ in range(3))
        one = min(timed(1) for _ in range(3))
        scan_ms = max((full - one) * 1e3, 1e-3)
    mspt = scan_ms / n_steps

    bw = hbm_bw(dev)
    itemsize = 1 if quantize else jnp.dtype(config.dtype).itemsize
    streamed = count_params(config)
    if not config.tie_word_embeddings:
        # the INPUT embedding table is read via a b-row gather per step,
        # not streamed; only the separate lm_head streams. (Tied: the
        # table IS the head and streams once.)
        streamed -= config.vocab_size * config.hidden_size
    bytes_per_step = streamed * itemsize  # weights read per token
    # the KV cache is ALSO read once per step (the decode scan reads the
    # full static-shape cache extent every layer): at batch>1 this is the
    # dominant batch-dependent term, and a floor that ignores it calls
    # honest cache traffic "overhead". Cache stays bf16 under weight-only
    # int8 quantization.
    c = config
    cache_len = prompt_len + new_tokens
    kv_bytes = (2 * c.num_hidden_layers * batch * cache_len
                * c.num_key_value_heads * c.head_dim
                * jnp.dtype(c.dtype).itemsize)
    bytes_per_step += kv_bytes
    floor_ms = bytes_per_step / bw * 1e3
    mbw = measured_hbm_bw(dev) if dev.platform != "cpu" else bw
    measured_floor_ms = bytes_per_step / mbw * 1e3
    del params
    return mspt, batch / (mspt / 1e3), floor_ms, measured_floor_ms


def bench_moe(dev):
    """Config-ladder #5 timed on one chip: ERNIE-MoE (slot-schedule
    top-2 dispatch, r5) train step. Reports ACTIVE-parameter MFU — the
    capacity factor (1.25) pads expert buckets beyond the routed tokens,
    so computed utilization is cf x higher than active, and the f32
    AdamW moments stream for ALL expert params though only top-k are
    active per token. Single chip has no all-to-all (ep=1); the ep=2
    all-to-all share is recorded by the driver dryrun's timing line."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.ernie_moe import ErnieMoEConfig, build_train_step
    cfg = ErnieMoEConfig(vocab_size=8192, hidden_size=1024,
                         intermediate_size=4096, num_hidden_layers=8,
                         num_attention_heads=8, num_experts=8, moe_topk=2,
                         capacity_factor=1.25, moe_every=2,
                         max_position_embeddings=512, dtype=jnp.bfloat16)
    B, S = 8, 512
    step, p, o = build_train_step(cfg, ep_degree=1, lr=1e-4)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)
    import jax as _jax
    for _ in range(3):
        p, o, loss, _lm = step(p, o, ids, labels)
    _jax.device_get(loss)
    # DEVICE-span timing (the bench's standard for sub-100ms dispatches:
    # host dispatch per call is a visible share of a step this short; the
    # flagship 300-800 ms steps absorb it). Falls back to wall-clock
    # off-TPU.
    state = {"p": p, "o": o}

    def run():
        state["p"], state["o"], loss, _lm = step(state["p"], state["o"],
                                                 ids, labels)
        _jax.device_get(loss)

    ms = trace_device_ms(run, "jit_step(", reps=5)
    if ms is not None:
        dt = ms / 1e3
    else:
        n, trials, dt = 10, 3, 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(n):
                p, o, loss, _lm = step(p, o, ids, labels)
            _jax.device_get(loss)
            dt = min(dt, (time.perf_counter() - t0) / n)
    p, o = state.get("p", p), state.get("o", o)
    tok_s = B * S / dt
    c = cfg
    n_dense = sum(1 for i in range(c.num_hidden_layers)
                  if (i % c.moe_every) != (c.moe_every - 1))
    n_moe = c.num_hidden_layers - n_dense
    ffn = 2 * c.hidden_size * c.intermediate_size
    active = (c.vocab_size * c.hidden_size
              + c.num_hidden_layers * 4 * c.hidden_size ** 2
              + n_dense * ffn
              + n_moe * (c.moe_topk * ffn + c.hidden_size * c.num_experts))
    fpt = 6.0 * active + 12 * c.num_hidden_layers * c.hidden_size * S
    del p, o
    return {
        "active_mfu": round(tok_s * fpt / peak_flops(dev), 4),
        "tokens_per_sec_per_chip": round(tok_s, 1),
        "step_time_s": round(dt, 4),
        "experts": c.num_experts, "topk": c.moe_topk,
        "capacity_factor": c.capacity_factor,
        "dominant_cost": "expert-FFN matmuls on cf x1.25-padded capacity "
                         "buckets + f32 AdamW moment streaming for the "
                         "full (not active) expert params; dispatch/"
                         "combine are row gathers with gather-only vjps "
                         "(r5 slot schedule — the r4 one-hot einsums are "
                         "gone; no all-to-all at ep=1, see MULTICHIP ep2 "
                         "timing line for the virtual-mesh a2a share)",
    }


def bench_moe_dropless(dev):
    """The dropless counterpart of bench_moe on the SAME config: ragged
    grouped-GEMM expert compute (dispatch_mode='ragged', no capacity
    buckets, zero drops) with param-dtype optimizer moments
    (multi_precision=False) so the bf16 expert moments stream at half
    the bytes. Reports active-parameter MFU plus the pad-waste stats
    that replace the capacity factor: tile-alignment padding is bounded
    by one MXU row tile per expert, vs cf=1.25's unconditional 25%."""
    import jax as _jax
    import jax.numpy as jnp
    from paddle_tpu.models.ernie_moe import ErnieMoEConfig, build_train_step
    cfg = ErnieMoEConfig(vocab_size=8192, hidden_size=1024,
                         intermediate_size=4096, num_hidden_layers=8,
                         num_attention_heads=8, num_experts=8, moe_topk=2,
                         capacity_factor=1.25, moe_every=2,
                         max_position_embeddings=512, dtype=jnp.bfloat16)
    B, S = 8, 512
    step, p, o = build_train_step(cfg, ep_degree=1, lr=1e-4,
                                  dispatch_mode="ragged",
                                  multi_precision=False, with_stats=True)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)
    for _ in range(3):
        p, o, loss, aux = step(p, o, ids, labels)
    _jax.device_get(loss)
    state = {"p": p, "o": o}

    def run():
        state["p"], state["o"], loss, aux = step(state["p"], state["o"],
                                                 ids, labels)
        _jax.device_get(loss)

    ms = trace_device_ms(run, "jit_step(", reps=5)
    if ms is not None:
        dt = ms / 1e3
    else:
        n, trials, dt = 10, 3, 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(n):
                p, o, loss, aux = step(p, o, ids, labels)
            _jax.device_get(loss)
            dt = min(dt, (time.perf_counter() - t0) / n)
    p, o = state["p"], state["o"]
    p, o, loss, aux = step(p, o, ids, labels)
    st = _jax.device_get(aux)
    live = float(st["moe_live_rows"])
    padded = float(st["moe_padded_rows"])
    tok_s = B * S / dt
    c = cfg
    n_dense = sum(1 for i in range(c.num_hidden_layers)
                  if (i % c.moe_every) != (c.moe_every - 1))
    n_moe = c.num_hidden_layers - n_dense
    ffn = 2 * c.hidden_size * c.intermediate_size
    active = (c.vocab_size * c.hidden_size
              + c.num_hidden_layers * 4 * c.hidden_size ** 2
              + n_dense * ffn
              + n_moe * (c.moe_topk * ffn + c.hidden_size * c.num_experts))
    fpt = 6.0 * active + 12 * c.num_hidden_layers * c.hidden_size * S
    del p, o
    return {
        "active_mfu": round(tok_s * fpt / peak_flops(dev), 4),
        "tokens_per_sec_per_chip": round(tok_s, 1),
        "step_time_s": round(dt, 4),
        "experts": c.num_experts, "topk": c.moe_topk,
        "dispatch_mode": "ragged",
        "multi_precision": False,
        "moe_dropped_tokens": float(st["moe_dropped_tokens"]),
        "moe_routed_tokens": float(st["moe_routed_tokens"]),
        # pad-waste: dead rows the ragged schedule computes (tile
        # alignment only; <= one row tile per expert per MoE layer) as a
        # fraction of the expert-buffer rows — the number that replaces
        # the capacity path's unconditional cf-1 = 25% bucket padding
        "pad_rows_per_step": padded,
        "pad_waste_frac": round(padded / max(live + padded, 1.0), 4),
        "expert_rows_per_layer_mean": [
            round(float(x) / max(n_moe, 1), 1)
            for x in np.asarray(st["moe_expert_rows"])],
        "dominant_cost": "ragged grouped-GEMM expert FFNs over the "
                         "expert-sorted token buffer (gmm fwd + dX/dW on "
                         "one flat row-tile schedule); zero drops, pad "
                         "bounded by one 128-row tile per expert; bf16 "
                         "AdamW moments (multi_precision=False) halve "
                         "optimizer streaming vs the capacity rung",
    }


def bench_moe_skew(dev):
    """PR 10 rung: skew-proof expert parallelism on the FINE-GRAINED
    ERNIE-MoE preset (E=32, top-4, one shared expert — ernie_moe_fine).

    Three records in one rung:
    - active-parameter MFU of the production MoE step (ragged dispatch,
      active-only AdamW moments, param-dtype moment storage) — the
      headline moe_active_mfu tracks the best MoE configuration, which
      after this PR is this one;
    - ANALYTIC wire bytes of the ragged a2a vs the dense capacity a2a
      under uniform / zipf / point-mass routing, measured from the
      actual top-k routing of sampled gate logits at ep=4: the ragged
      transport ships only routed rows, the dense one always ships the
      full cf-padded capacity buffers;
    - overlap fraction (non-final a2a hops the schedule lets the expert
      FFN start under) from TRACE-TIME counters of an ep=2 island
      lowering with the overlap schedule on; null when <2 devices.
    """
    import jax as _jax
    import jax.numpy as jnp
    from paddle_tpu.models.ernie_moe import build_train_step, ernie_moe_fine
    from paddle_tpu.parallel.moe import moe_capacity
    cfg = ernie_moe_fine()
    B, S = 8, 512
    step, p, o = build_train_step(cfg, ep_degree=1, lr=1e-4,
                                  dispatch_mode="ragged_a2a",
                                  multi_precision=False, with_stats=True,
                                  active_only_moments=True)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)
    for _ in range(3):
        p, o, loss, aux = step(p, o, ids, labels)
    _jax.device_get(loss)
    state = {"p": p, "o": o}

    def run():
        state["p"], state["o"], loss, aux = step(state["p"], state["o"],
                                                 ids, labels)
        _jax.device_get(loss)

    ms = trace_device_ms(run, "jit_step(", reps=5)
    # the profiler reps donated the local p/o into state: rebind before
    # the wall-clock fallback touches them again
    p, o = state["p"], state["o"]
    if ms is not None:
        dt = ms / 1e3
    else:
        n, trials, dt = 10, 3, 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(n):
                p, o, loss, aux = step(p, o, ids, labels)
            _jax.device_get(loss)
            dt = min(dt, (time.perf_counter() - t0) / n)
    del state, p, o
    tok_s = B * S / dt
    c = cfg
    n_dense = sum(1 for i in range(c.num_hidden_layers)
                  if (i % c.moe_every) != (c.moe_every - 1))
    n_moe = c.num_hidden_layers - n_dense
    ffn = 2 * c.hidden_size * c.intermediate_size
    shared_ffn = 2 * c.hidden_size * (c.num_shared_experts
                                      * c.intermediate_size)
    active = (c.vocab_size * c.hidden_size
              + c.num_hidden_layers * 4 * c.hidden_size ** 2
              + n_dense * ffn
              + n_moe * (c.moe_topk * ffn + shared_ffn
                         + c.hidden_size * c.num_experts))
    fpt = 6.0 * active + 12 * c.num_hidden_layers * c.hidden_size * S

    # -- analytic wire-byte sweep at ep=4 ---------------------------------
    E, k, H = c.num_experts, c.moe_topk, c.hidden_size
    ep = 4
    e_local = E // ep
    T_shard = B * S // ep
    dtype_bytes = 2  # bf16 rows on the wire
    cap, _ref = moe_capacity(T_shard, k, E, c.capacity_factor)
    # the dense capacity a2a ships every REMOTE expert's full capacity
    # bucket regardless of routing — per rank, per MoE layer
    dense_bytes = (E - e_local) * cap * H * dtype_bytes
    sweep = {}
    for name in ("uniform", "zipf", "point_mass"):
        logits = rng.randn(ep * T_shard, E).astype(np.float32)
        if name == "zipf":
            logits -= 3.0 * np.log(np.arange(E) + 1.0)[None, :]
        elif name == "point_mass":
            logits[:, 0] += 20.0
            logits[:, 1] += 19.0
        topk = np.argsort(-logits, axis=-1)[:, :k]          # [T, k]
        src = np.repeat(np.arange(ep), T_shard)             # token -> rank
        dest = topk // e_local                              # [T, k]
        wire_rows = int((dest != src[:, None]).sum())
        wire_bytes = wire_rows * H * dtype_bytes / ep       # per rank
        sweep[name] = {
            "wire_rows": wire_rows,
            "ragged_wire_bytes_per_rank": int(wire_bytes),
            "dense_capacity_bytes_per_rank": int(dense_bytes),
            "wire_vs_dense_ratio": round(wire_bytes / dense_bytes, 4),
        }

    # -- overlap fraction from a trace of the ep=2 island -----------------
    overlap_frac = None
    devs = _jax.devices()
    if len(devs) >= 2:
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle_tpu import observability as obs
        from paddle_tpu.parallel.moe import moe_ragged_dispatch_a2a
        mesh = Mesh(np.array(devs[:2]), ("ep",))

        def island(xs, ls, w1s, w2s):
            out, aux = moe_ragged_dispatch_a2a(
                xs, ls, w1s, w2s, E, axis_name="ep", k=k, overlap=True)
            return out

        f = shard_map(island, mesh=mesh,
                      in_specs=(P("ep"), P("ep"), P("ep"), P("ep")),
                      out_specs=P("ep"), check_vma=False)
        obs.reset_counters()
        try:
            # counters are trace-time: lowering alone records the hop
            # schedule, no device step needed
            _jax.jit(f).lower(
                jnp.zeros((128, H), jnp.bfloat16),
                jnp.zeros((128, E), jnp.float32),
                jnp.zeros((E, H, c.intermediate_size), jnp.bfloat16),
                jnp.zeros((E, c.intermediate_size, H), jnp.bfloat16))
            cnt = obs.counters()
            tot = cnt.get("moe.a2a.hops_total", 0.0)
            overlap_frac = (round(cnt.get("moe.a2a.hops_overlapped", 0.0)
                                  / tot, 4) if tot else None)
        finally:
            obs.reset_counters()

    return {
        "active_mfu": round(tok_s * fpt / peak_flops(dev), 4),
        "tokens_per_sec_per_chip": round(tok_s, 1),
        "step_time_s": round(dt, 4),
        "experts": E, "topk": k,
        "num_shared_experts": c.num_shared_experts,
        "dispatch_mode": "ragged_a2a",
        "multi_precision": False,
        "active_only_moments": True,
        "sweep_ep": ep,
        "sweep": sweep,
        "overlap_fraction": overlap_frac,
        "dominant_cost": "fine-grained expert FFNs (E=32 top-4, I=512) "
                         "on the flat grouped-GEMM schedule plus one "
                         "shared-expert dense FFN; a2a wire cost scales "
                         "with ROUTED rows (see sweep) instead of the "
                         "dense path's cf-padded capacity buckets; AdamW "
                         "moments stream only for experts that routed "
                         "tokens this step (active-only masking)",
    }


def decode_pair_stack_ab(dev, config_hd64):
    """hd64_b8 floor-gap attempt (ISSUE satellite): A/B the standalone
    slab decode kernel with PADDLE_TPU_DECODE_HD64_STACK on/off. The
    pair-stacked variant packs two head_dim-64 heads per 128-lane tile:
    NH/2 fewer padded MXU FLOPs and an NH/2 thinner per-lane window, so
    the fitter keeps the full 512-lane T tile where the wide slab drops
    to fragmented 128-lane DMAs. Recorded either way; the baseline block
    choice stays the default unless the env flag asks for the stack."""
    import os

    import jax.numpy as jnp
    from jax import enable_x64
    from paddle_tpu.ops.decode_attention import decode_attention_slab
    c = config_hd64
    B, NH, HD = 8, c.num_attention_heads, c.head_dim
    KVD = NH * HD
    L, T, pos = 2, 4096, 4095
    it = jnp.dtype(c.dtype).itemsize
    rng = np.random.RandomState(9)
    q = np.zeros((B, NH, KVD), np.float32)
    for h in range(NH):   # head-block-diagonal, as the slab caller builds
        q[:, h, h * HD:(h + 1) * HD] = rng.randn(B, HD) * 0.1
    qs = jnp.asarray(q, c.dtype)
    kc = jnp.asarray(rng.randn(L, B, KVD, T), c.dtype)
    vc = jnp.asarray(rng.randn(L, B, KVD, T), c.dtype)
    res = {"batch": B, "num_heads": NH, "head_dim": HD, "cache_T": T}
    key = "PADDLE_TPU_DECODE_HD64_STACK"
    prev = os.environ.get(key)
    try:
        for name, flag in (("baseline_ms", "0"), ("pair_stack_ms", "1")):
            os.environ[key] = flag
            # x64 off for the whole jit trace+lower: the package enables
            # x64 globally, but under jit the pallas index maps lower
            # OUTSIDE the kernel's own mosaic_trace_ctx and 64-bit index
            # constants leak in (eager calls lower inside the ctx)
            with enable_x64(False):
                ms = device_time_ms(
                    lambda q, k, v: decode_attention_slab(q, k, v, 1, pos),
                    (qs, kc, vc), f"hd64slab{flag}")
            res[name] = round(ms, 3)
    finally:
        if prev is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prev
    res["pair_stack_speedup"] = round(
        res["baseline_ms"] / max(res["pair_stack_ms"], 1e-9), 3)
    # the floor for this kernel is streaming one layer's k+v cache once
    bw = hbm_bw(dev)
    res["cache_stream_floor_ms"] = round(2 * B * KVD * T * it / bw * 1e3, 3)
    return res


def decode_block_sweep(dev, config_hd64):
    """hd64 floor-gap satellite: sweep PADDLE_TPU_DECODE_BLOCK_T over the
    fused attend+update slab kernel at the hd64_b8 shape — the kernel
    family _fit_block_t serves (the r5 1.36x-of-floor reading). The
    override forces each tile size; the kernel-level x_of_floor is
    against streaming one layer's k+v cache once. The winner's tile is
    what the fitter default should produce with the 6-window accounting
    for the update path."""
    import os

    import jax.numpy as jnp
    from paddle_tpu.ops.decode_attention import decode_attend_update_slab
    c = config_hd64
    B, NH, HD = 8, c.num_attention_heads, c.head_dim
    KVD = NH * HD
    L, T, pos = 2, 4096, 4000
    it = jnp.dtype(c.dtype).itemsize
    rng = np.random.RandomState(10)
    q = np.zeros((B, NH, KVD), np.float32)
    for h in range(NH):
        q[:, h, h * HD:(h + 1) * HD] = rng.randn(B, HD) * 0.1
    qs = jnp.asarray(q, c.dtype)
    nk = jnp.asarray(rng.randn(B, KVD), c.dtype)
    nv = jnp.asarray(rng.randn(B, KVD), c.dtype)
    kc = jnp.asarray(rng.randn(L, B, KVD, T), c.dtype)
    vc = jnp.asarray(rng.randn(L, B, KVD, T), c.dtype)
    bw = hbm_bw(dev)
    floor_ms = 2 * B * KVD * T * it / bw * 1e3
    key = "PADDLE_TPU_DECODE_BLOCK_T"
    prev = os.environ.get(key)
    res = {"batch": B, "head_dim": HD, "cache_T": T,
           "cache_stream_floor_ms": round(floor_ms, 3)}
    try:
        for tag in ("fitted", "128", "256", "512"):
            if tag == "fitted":
                os.environ.pop(key, None)
            else:
                os.environ[key] = tag
            ms = device_time_ms(
                lambda q, nk, nv, k, v: decode_attend_update_slab(
                    q, nk, nv, k, v, 1, pos),
                (qs, nk, nv, kc, vc), f"updslab{tag}")
            res[f"block_{tag}"] = {
                "ms": round(ms, 3),
                "x_of_floor": round(ms / max(floor_ms, 1e-9), 3)}
    finally:
        if prev is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prev
    best = min((k for k in res if k.startswith("block_")),
               key=lambda k: res[k]["ms"])
    res["best"] = best
    return res


def bench_step_ledger(dev, config, batch, seq, step_time_s,
                      use_flash=True):
    """Measured-mode roofline ledger for the train step (measurement only
    — no behavior change): each component from
    observability.flagship_component_specs timed in isolation at the
    step's real shapes (device spans on TPU, wall-clock fallback
    elsewhere) and fed to RooflineLedger with its analytic FLOPs/bytes,
    so every line carries a compute-/memory-bound classification and an
    achieved-vs-roofline fraction. The explicit 'unattributed' remainder
    is what the components don't cover — remat recompute, elementwise
    glue, layout changes, scheduling gaps. Collectives are 0.0 on one
    chip by construction."""
    from paddle_tpu.observability.ledger import (RooflineLedger,
                                                 flagship_component_specs)
    led = RooflineLedger(name="flagship_step", device=dev)
    specs = flagship_component_specs(config, batch, seq,
                                     use_flash=use_flash)
    for i, spec in enumerate(specs):
        fn, args = spec["build"]()
        ms = device_time_ms(fn, args, f"ldg{i}")
        led.add(spec["name"], flops=spec["mult"] * spec["flops"],
                bytes_accessed=spec["mult"] * spec["bytes_accessed"],
                transcendentals=spec["mult"] * spec["transcendentals"],
                time_ms=spec["mult"] * ms, calls=spec["mult"])
    led.add("collectives", time_ms=0.0, calls=0)
    step_ms = step_time_s * 1e3
    rep = led.report(step_ms)
    comps = {}
    for ln in rep["lines"]:
        comps[ln["name"]] = {
            "ms": round(ln["attributed_ms"], 3),
            "frac": (round(ln["frac_of_step"], 4)
                     if ln["frac_of_step"] is not None else None),
            "bound": ln["bound"],
            "roofline_frac": (round(ln["achieved_frac"], 3)
                              if ln["achieved_frac"] is not None else None),
        }
    return {
        "step_ms": round(step_ms, 3),
        "peak_source": rep["peak_source"],
        "bw_source": rep["bw_source"],
        "attributed_ms": round(rep["attributed_ms"], 3),
        "unattributed_ms": round(rep["unattributed_ms"], 3),
        "unattributed_frac": round(rep["unattributed_frac"], 4),
        "components": comps,
        "note": ("components timed in isolation at step shapes; "
                 "'unattributed' is the residual (remat recompute, "
                 "elementwise glue, layout changes); collectives are "
                 "zero on a single chip"),
    }


def bench_ledger_roofline(dev, config, on_tpu):
    """PR 17 rung: roofline-ledger cost and parity. The same training run
    twice from identical seeds — bare, then with the always-on model-mode
    RooflineLedger fed exactly as TrainStep feeds it (kernel-cost window
    delta over the compile trace, on_step per step) — gated on (a)
    bitwise-identical loss sequences (the ledger only ever sees host
    floats and trace-time cost constants) and (b) attributed ledger
    overhead — time inside ledger calls via the overlap_bench timing
    proxy — under 2% of the monitored run's wall. The headline
    ``unattributed_frac`` comes from the measured-mode component ledger
    at the same shapes (model-mode roofline times are optimistic floors,
    so its remainder is an upper bound, not the attribution metric)."""
    import jax
    from benchmarks.overlap_bench import _TimedProxy
    from paddle_tpu.models.llama import ParallelConfig, build_train_step
    from paddle_tpu.observability.ledger import RooflineLedger
    from paddle_tpu.ops import _common as _opsc

    parallel = ParallelConfig(remat=True, use_flash=on_tpu)
    rng = np.random.RandomState(6)
    n_steps, batch, seq = (20, 4, 512) if on_tpu else (8, 2, 64)
    ids = rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    def run(ledger):
        step, params, opt = build_train_step(config, parallel, lr=1e-4)
        snap = _opsc.snapshot_kernel_costs()
        for _ in range(2):  # compile + settle outside the timed window
            params, opt, loss = step(params, opt, ids, labels)
        if ledger is not None:
            # the compile trace fired every pallas cost_estimate site:
            # the window delta IS this program's per-kernel cost
            ledger.ingest(_opsc.kernel_costs_since(snap))
        jax.device_get(loss)
        losses = []
        t0 = time.perf_counter()
        last = t0
        for _ in range(n_steps):
            params, opt, loss = step(params, opt, ids, labels)
            # per-step host sync in BOTH runs so the bare and ledgered
            # loops execute the identical schedule
            losses.append(float(jax.device_get(loss)))
            now = time.perf_counter()
            if ledger is not None:
                ledger.on_step(now - last)
            last = now
        return losses, time.perf_counter() - t0

    losses_off, wall_off = run(None)
    counter = [0.0]
    led = RooflineLedger(name="bench_train_step", device=dev)
    losses_on, wall_on = run(_TimedProxy(led, counter))
    overhead_pct = counter[0] / wall_on * 100.0
    model_rep = led.report()
    measured = bench_step_ledger(dev, config, batch, seq,
                                 wall_off / n_steps, use_flash=on_tpu)
    out = {
        "steps": n_steps,
        "ledger_losses_identical": losses_on == losses_off,
        "ledger_overhead_pct": round(overhead_pct, 3),
        "model_mode_lines": len([ln for ln in model_rep["lines"]
                                 if ln["name"] != "unattributed"]),
        "model_mode_unattributed_frac": (
            round(model_rep["unattributed_frac"], 4)
            if model_rep["unattributed_frac"] is not None else None),
        "unattributed_frac": measured["unattributed_frac"],
        "measured": measured,
    }
    assert out["ledger_losses_identical"], (losses_off, losses_on)
    assert overhead_pct < 2.0, \
        f"roofline ledger attributed overhead {overhead_pct:.2f}% >= 2%"
    assert out["model_mode_lines"] >= 1, \
        "model-mode ledger ingested no kernel cost lines"
    if not on_tpu:
        out["note"] = ("tiny config on CPU — functional rung; the "
                       "overhead gate is attributed (proxy-timed), and "
                       "measured-mode component times are wall-clock "
                       "fallbacks")
    return out


def varlen_ceiling_ablation(dev, dense_fwd_ms, dense_bwd_ms, S=16384):
    """Varlen-efficiency ceiling satellite: run ONE S-token sequence
    (cu=[0, S] — layout identical to dense) through the varlen
    flat-schedule kernels and compare against the dense flash numbers at
    the same shape. The one-seq eff IS the kernel's ceiling: the gap
    from dense flash is pure flat-schedule overhead (scalar-prefetched
    tile walk, per-tile boundary masks), and the remaining gap of the
    16-seq pack to THIS ceiling is the packing tax (ragged tails,
    per-seq softmax resets) — not schedule waste. S defaults to the
    on-TPU 16384; off-TPU callers pass a small S so interpret mode can
    afford the quadratic walk."""
    import jax as _jax
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_varlen import (flash_varlen_attention,
                                             varlen_schedule_stats)
    cu = jnp.asarray([0, S], jnp.int32)
    rng = np.random.RandomState(6)
    mk = lambda: jnp.asarray(rng.randn(S, 8, 128).astype(np.float32),
                             jnp.bfloat16)
    qv, kv, vv = mk(), mk(), mk()

    def fwd(q, k, v):
        return flash_varlen_attention(q, k, v, cu, cu, 1 / 11.3, True,
                                      self_attn=True, max_seqlen=S)

    def bwd(q, k, v):
        loss = lambda *a: (flash_varlen_attention(
            *a, cu, cu, 1 / 11.3, True, self_attn=True,
            max_seqlen=S).astype(jnp.float32) ** 2).sum()
        return _jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    ms_f = device_time_ms(fwd, (qv, kv, vv), "vlceilf")
    ms_b = device_time_ms(bwd, (qv, kv, vv), "vlceilb")
    fl = 2 * 2 * 8 * S * S * 128 / 2
    pk = peak_flops(dev)
    out = {
        "oneseq_fwd_ms": round(ms_f, 2), "oneseq_bwd_ms": round(ms_b, 2),
        "dense_flash_fwd_ms": round(dense_fwd_ms, 2),
        "dense_flash_bwd_ms": round(dense_bwd_ms, 2),
        "varlen_fwd_eff_ceiling": round(fl / (ms_f / 1e3) / pk, 3),
        "varlen_bwd_eff_ceiling": round(2.5 * fl / (ms_b / 1e3) / pk, 3),
        "schedule_overhead_fwd": round(max(ms_f / dense_fwd_ms - 1, 0), 3),
        "schedule_overhead_bwd": round(max(ms_b / dense_bwd_ms - 1, 0), 3),
        "schedule": varlen_schedule_stats(
            np.asarray(cu), np.asarray(cu), 8, 128, causal=True,
            self_attn=True, dtype=jnp.bfloat16, max_seqlen=S),
    }
    return out


def bench_fleet_observability(dev, config, on_tpu):
    """PR 15 rung: FleetMonitor cost and parity. The same training run
    twice from identical seeds — bare, then with every step feeding a
    FleetMonitor (interval reporting: site counter deltas, all-device
    memory, one fleet_health JSONL record each) — gated on (a) bitwise-
    identical loss sequences (the monitor only ever SEES host floats the
    loop already had, it cannot perturb the computation) and (b)
    attributed monitor overhead — time inside FleetMonitor calls via the
    overlap_bench timing proxy — under 2% of the monitored run's wall."""
    import jax
    from benchmarks.overlap_bench import _TimedProxy
    from paddle_tpu.models.llama import ParallelConfig, build_train_step
    from paddle_tpu.observability import fleet as fleet_mod
    from paddle_tpu.observability.fleet import FleetMonitor

    parallel = ParallelConfig(remat=True, use_flash=on_tpu)
    rng = np.random.RandomState(5)
    n_steps, batch, seq = (20, 4, 512) if on_tpu else (8, 2, 64)
    ids = rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    def run(monitor):
        step, params, opt = build_train_step(config, parallel, lr=1e-4)
        for _ in range(2):  # compile + settle outside the timed window
            params, opt, loss = step(params, opt, ids, labels)
        jax.device_get(loss)
        losses = []
        t0 = time.perf_counter()
        last = t0
        for _ in range(n_steps):
            params, opt, loss = step(params, opt, ids, labels)
            # per-step host sync in BOTH runs so the monitored and bare
            # loops execute the identical schedule (and the loss is a
            # host float by the time the monitor sees it)
            losses.append(float(jax.device_get(loss)))
            now = time.perf_counter()
            if monitor is not None:
                monitor.on_step(now - last, loss=losses[-1])
            last = now
        return losses, time.perf_counter() - t0

    losses_off, wall_off = run(None)
    path = os.path.join(
        tempfile.mkdtemp(prefix="paddle_tpu_fleet_bench_"),
        "fleet_health.jsonl")
    counter = [0.0]
    mon = FleetMonitor(rank=0, world=1, interval=4, out_path=path)
    losses_on, wall_on = run(_TimedProxy(mon, counter))
    n_reports, problems = fleet_mod.check_file(path)
    overhead_pct = counter[0] / wall_on * 100.0
    last_report = mon.reports[-1] if mon.reports else {}
    out = {
        "steps": n_steps,
        "reports": n_reports,
        "monitored_losses_identical": losses_on == losses_off,
        "fleet_overhead_pct": round(overhead_pct, 3),
        "fleet_overhead_ab_pct": round((wall_on / wall_off - 1.0) * 100.0,
                                       2),
        "health_check_ok": not problems,
        "step_time_ms_worst": (last_report.get("step_time_ms") or
                               {}).get("worst"),
        "hbm_peak_bytes": last_report.get("hbm_peak_bytes"),
        "anomalies": len(mon.anomalies),
    }
    assert out["monitored_losses_identical"], (losses_off, losses_on)
    assert overhead_pct < 2.0, \
        f"fleet monitor attributed overhead {overhead_pct:.2f}% >= 2%"
    assert not problems, problems
    if not on_tpu:
        out["note"] = ("tiny config on CPU — functional rung; the "
                       "overhead gate is attributed (proxy-timed), not "
                       "the noisy A/B wall delta")
    return out


def bench_serve_continuous(dev, config, on_tpu):
    """Tentpole rung: the continuous-batching serving engine under a
    Poisson arrival trace with mixed prompt lengths. Reports end-to-end
    tokens/s, per-token latency percentiles (TPOT p50/p99), TTFT, and
    the engine telemetry means (queue depth, decode-batch occupancy,
    block-pool utilization, prefill-vs-decode time share). Off-TPU the
    tiny config runs the full engine in pallas interpret mode — a
    functional rung with honest relative latencies; the flagship trace
    needs the TPU round."""
    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params
    from paddle_tpu.observability.metrics import StepMetrics

    rng = np.random.RandomState(11)
    if on_tpu:
        serve = ServeConfig(block_size=128, num_blocks=257, max_batch=8,
                            prefill_chunk=256, max_seq_len=2048)
        n_req, rate, max_new = 24, 40.0, 64
        plens = rng.choice([64, 128, 384, 768], size=n_req,
                           p=[0.35, 0.35, 0.2, 0.1])
    else:
        serve = ServeConfig(block_size=128, num_blocks=17, max_batch=4,
                            prefill_chunk=64, max_seq_len=256)
        n_req, rate, max_new = 6, 8.0, 8
        plens = rng.choice([8, 24, 96, 130], size=n_req)
    params = init_llama_params(config, seed=0)
    metrics = StepMetrics(name="serve", n_devices=1)
    # all PR-12 observability layers ON for the measured run: the reported
    # tokens/s carries the request-tracing + histogram + flight-recorder
    # cost (bounded <2% by overlap_bench.bench_overhead)
    eng = InferenceEngine(params, config, serve, telemetry=metrics,
                          trace_requests=True, flight_recorder=True)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
    prompts = [rng.randint(1, config.vocab_size, size=int(n)).tolist()
               for n in plens]
    reqs = [Request(p, max_new_tokens=max_new, arrival=float(t))
            for p, t in zip(prompts, arrivals)]
    stats = eng.run(reqs)
    recs = metrics.records

    # tracing-overhead check on the same prompts, deterministic replay so
    # the traced and untraced runs execute identical schedules and must
    # produce identical tokens (tracing is measurement-only). The headline
    # pct is ATTRIBUTED (time inside observability calls / run wall, via
    # the overlap_bench proxy clamp); the raw A/B wall delta rides along
    # for reference but carries several percent of host-scheduler noise.
    from benchmarks.overlap_bench import _TimedProxy

    def _det_run(on, attribute=False):
        e = InferenceEngine(params, config, serve, trace_requests=on,
                            flight_recorder=on)
        counter = [0.0]
        if attribute:
            e.tracer = _TimedProxy(e.tracer, counter)
            e.recorder = _TimedProxy(e.recorder, counter)
            e.slo = {k: _TimedProxy(h, counter) for k, h in e.slo.items()}
        rs = [Request(p, max_new_tokens=max_new, arrival=float(i))
              for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        e.run(rs, deterministic=True)
        return (time.perf_counter() - t0, counter[0],
                {s.req.request_id: list(s.generated) for s in e.finished})

    _det_run(False)  # warm the jit caches outside the timed pair
    t_off, _, toks_off = _det_run(False)
    t_on, _, toks_on = _det_run(True)
    wall_attr, obs_s, _ = _det_run(True, attribute=True)

    def mean_of(key):
        vals = [r[key] for r in recs if r.get(key) is not None]
        return round(float(np.mean(vals)), 4) if vals else None

    pre = sum(r.get("prefill_ms") or 0.0 for r in recs)
    dec = sum(r.get("decode_ms") or 0.0 for r in recs)
    out = {
        "requests": stats["requests"],
        "generated_tokens": stats["generated_tokens"],
        "tokens_per_sec": round(stats["tokens_per_sec"] or 0.0, 2),
        "ttft_p50_s": round(stats["ttft_p50_s"], 4),
        "ttft_p99_s": round(stats["ttft_p99_s"], 4),
        "tpot_p50_s": round(stats["tpot_p50_s"], 4),
        "tpot_p99_s": round(stats["tpot_p99_s"], 4),
        # streaming estimates from the fixed-memory LogHistograms, next to
        # the exact end-of-run percentiles above — must agree within one
        # log bucket (~16%) modulo the nearest-rank/interpolated split
        "ttft_stream_p50_s": round(stats["ttft_stream_p50_s"], 4),
        "ttft_stream_p99_s": round(stats["ttft_stream_p99_s"], 4),
        "tpot_stream_p50_s": round(stats["tpot_stream_p50_s"], 4),
        "tpot_stream_p99_s": round(stats["tpot_stream_p99_s"], 4),
        "unfinished": stats["unfinished"],
        "trace_spans": eng.tracer.span_count(),
        "tracing_overhead_pct": round(obs_s / wall_attr * 100.0, 2),
        "tracing_overhead_ab_pct": round((t_on / t_off - 1.0) * 100.0, 2),
        "traced_tokens_identical": toks_on == toks_off,
        "preemptions": stats["preemptions"],
        "iterations": stats["iterations"],
        "compiled_shapes": sorted(stats["compiles"]),
        "arrival_trace": {"process": "poisson", "rate_per_s": rate,
                          "prompt_lengths": sorted(set(int(x)
                                                       for x in plens))},
        "pool_blocks": stats["pool_blocks"],
        "block_size": serve.block_size,
        "max_batch": serve.max_batch,
        "queue_depth_mean": mean_of("queue_depth"),
        "batch_occupancy_mean": mean_of("batch_occupancy"),
        "pool_utilization_mean": mean_of("pool_utilization"),
        "prefill_time_share": round(pre / max(pre + dec, 1e-9), 4),
    }
    if not on_tpu:
        out["note"] = ("tiny config in pallas interpret mode on CPU — "
                       "functional rung; flagship trace lands with the "
                       "TPU bench round")
    return out


def bench_preempt_resume(dev, config, on_tpu):
    """PR-13 robustness rung: what preemption tolerance costs.

    * save_overlap_overhead_pct — wall time of n train steps with the
      CheckpointManager's interval-paced ASYNC saves riding along
      (device->host snapshot inline, file write overlapping subsequent
      steps) vs the same n steps bare; blocking_save_overhead_pct rides
      along to show what the overlap buys back;
    * resume_to_parity_ms — CheckpointManager.restore into a fresh
      state plus the first post-restore step, whose loss must match the
      uninterrupted run at that step bitwise (same compiled step);
    * swap_drain_ms — InferenceEngine.swap_weights drain latency at a
      mid-serve iteration boundary (identical weights, token streams
      checked bit-identical against an unswapped run).
    """
    import os
    import shutil
    import tempfile

    import jax
    from paddle_tpu.distributed.checkpoint.manager import CheckpointManager
    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import (ParallelConfig, build_train_step,
                                         init_llama_params)

    import jax.numpy as jnp

    parallel = ParallelConfig(remat=True, use_flash=on_tpu)
    step, params, opt = build_train_step(config, parallel, lr=1e-4)
    batch, seq = (4, 2048) if on_tpu else (2, 128)
    rng = np.random.RandomState(13)
    ids = rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)

    # the jitted step DONATES its param/opt buffers, so every run that
    # branches from shared state must branch from a fresh device copy
    def copy_tree(t):
        return jax.tree_util.tree_map(
            lambda a: jnp.copy(a) if isinstance(a, jax.Array) else a, t)

    p, o = copy_tree(params), copy_tree(opt)
    for _ in range(2):  # compile + warm outside every timed window
        p, o, loss = step(p, o, ids, labels)
    jax.device_get(loss)
    n = 6 if on_tpu else 4
    interval = n // 2  # two saves per measured run

    root = tempfile.mkdtemp(prefix="paddle_tpu_bench_ckpt_")
    try:
        # orbax cold-start (imports, type-handler registration, asyncio
        # setup) lands on the process's first save/restore — pay it here,
        # outside every timed window
        warm = CheckpointManager(os.path.join(root, "warm"), keep=1)
        warm.save({"params": p, "opt": o, "step": 0}, 0, block=True)
        warm.restore({"params": copy_tree(p), "opt": copy_tree(o),
                      "step": 0})

        pp, oo = copy_tree(p), copy_tree(o)
        t0 = time.perf_counter()
        for _ in range(n):
            pp, oo, loss = step(pp, oo, ids, labels)
        jax.device_get(loss)
        t_plain = time.perf_counter() - t0

        mgr = CheckpointManager(os.path.join(root, "async"), keep=2,
                                interval=interval)
        pp, oo = copy_tree(p), copy_tree(o)
        t0 = time.perf_counter()
        for i in range(1, n + 1):
            pp, oo, loss = step(pp, oo, ids, labels)
            mgr.on_step(i, lambda: {"params": pp, "opt": oo, "step": i})
        jax.device_get(loss)
        t_async = time.perf_counter() - t0
        errs = mgr.wait()  # drain the tail write OUTSIDE the window:
        assert not errs, errs  # overlapping it is the feature measured

        mgr_b = CheckpointManager(os.path.join(root, "block"), keep=2)
        pb, ob = copy_tree(p), copy_tree(o)
        t0 = time.perf_counter()
        for i in range(1, n + 1):
            pb, ob, loss = step(pb, ob, ids, labels)
            if i % interval == 0:
                mgr_b.save({"params": pb, "opt": ob, "step": i}, i,
                           block=True)
        jax.device_get(loss)
        t_block = time.perf_counter() - t0

        # resume-to-parity: the uninterrupted run's next-step loss is the
        # target; restore the newest checkpoint (written at step n, state
        # == pp/oo) into a fresh template and replay that step
        _, _, l_ref = step(pp, oo, ids, labels)
        l_ref = float(jax.device_get(l_ref))
        tmpl = {"params": copy_tree(params), "opt": copy_tree(opt),
                "step": 0}
        t0 = time.perf_counter()
        restored_step = mgr.restore(tmpl)
        _, _, l_res = step(tmpl["params"], tmpl["opt"], ids, labels)
        l_res = float(jax.device_get(l_res))
        resume_ms = (time.perf_counter() - t0) * 1e3

        # mid-serve weight-swap drain latency, identical-weights parity
        if on_tpu:
            serve = ServeConfig(block_size=128, num_blocks=65, max_batch=4,
                                prefill_chunk=256, max_seq_len=1024)
            plens, max_new = (64, 384), 16
        else:
            serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                                prefill_chunk=64, max_seq_len=256)
            plens, max_new = (8, 130), 6
        sparams = init_llama_params(config, seed=0)
        copy = lambda t: jax.tree_util.tree_map(lambda a: a, t)

        def mk_reqs():
            r = np.random.RandomState(3)
            return [Request(r.randint(1, config.vocab_size,
                                      size=int(nn)).tolist(),
                            max_new_tokens=max_new, arrival=float(i))
                    for i, nn in enumerate(plens)]

        ref_eng = InferenceEngine(copy(sparams), config, serve)
        ref_eng.run(mk_reqs(), deterministic=True)
        eng = InferenceEngine(copy(sparams), config, serve)
        eng.swap_weights(copy(sparams), at_iteration=3)
        st = eng.run(mk_reqs(), deterministic=True)
        toks = lambda e: {s.req.request_id: s.tokens for s in e.finished}

        out = {
            "train_steps_timed": n,
            "saves_per_run": n // interval,
            "step_time_plain_ms": round(t_plain / n * 1e3, 2),
            "save_overlap_overhead_pct":
                round((t_async / t_plain - 1) * 100, 2),
            "blocking_save_overhead_pct":
                round((t_block / t_plain - 1) * 100, 2),
            "resume_to_parity_ms": round(resume_ms, 1),
            "resume_step": restored_step,
            "resume_loss_bitwise": l_res == l_ref,
            "swap_drain_ms": round(eng.last_swap["swap_ms"], 2),
            "swap_tokens_identical": toks(eng) == toks(ref_eng),
            "swap_unfinished": st["unfinished"],
        }
        if not on_tpu:
            out["note"] = ("tiny config on CPU — overhead ratios are "
                           "functional-rung numbers; the flagship costs "
                           "land with the TPU bench round")
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_serve_overload(dev, config, on_tpu):
    """PR-14 robustness rung: the serving engine under a 2x-capacity
    burst with admission control, deadline shedding, and the crash
    journal all live.

    * determinism — the same arrival trace replayed twice must shed the
      SAME request set and produce bit-identical survivor streams
      (deterministic mode: deadlines/admission consult only the
      iteration clock);
    * accounting — every request ends finished/rejected/shed/failed
      with a cause (``no_silent_drops``), and the pool is leak-free
      after the burst;
    * goodput — a wall-clock run of the same burst reports generated
      tokens/s over admitted-and-finished requests, the shed rate, and
      finished-request TTFT p99;
    * cost — wall share attributed to the admission controller + the
      engine journal via the overlap_bench proxy clamp (the PR-12
      observability layers have their own <2% gate; this isolates what
      PR 14 added).
    """
    import os
    import shutil
    import tempfile

    from benchmarks.overlap_bench import _TimedProxy
    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params

    rng = np.random.RandomState(17)
    if on_tpu:
        serve = dict(block_size=128, num_blocks=33, max_batch=4,
                     prefill_chunk=256, max_seq_len=1024, max_queue=16,
                     overcommit=8.0)
        n_req, max_new = 24, 32
        plens = rng.choice([64, 128, 384], size=n_req)
        ttft_dl, total_dl = 30.0, 120.0     # iteration-clock deadlines
    else:
        serve = dict(block_size=128, num_blocks=3, max_batch=1,
                     prefill_chunk=32, max_seq_len=256, max_queue=8,
                     overcommit=8.0)
        n_req, max_new = 8, 24
        plens = [30] * n_req
        ttft_dl, total_dl = 28.0, 160.0
    params = init_llama_params(config, seed=0)
    prompts = [rng.randint(1, config.vocab_size, size=int(n)).tolist()
               for n in plens]

    def mk_reqs(arrivals, scale=1.0):
        return [Request(p, max_new_tokens=max_new, arrival=float(t),
                        ttft_deadline=ttft_dl * scale,
                        deadline=total_dl * scale)
                for p, t in zip(prompts, arrivals)]

    root = tempfile.mkdtemp(prefix="paddle_tpu_bench_overload_")
    try:
        def det_run(tag, attribute=False):
            eng = InferenceEngine(
                params, config, ServeConfig(**serve),
                journal=os.path.join(root, f"{tag}.jsonl"))
            counter = [0.0]
            if attribute:
                eng._journal = _TimedProxy(eng._journal, counter)
                eng.admission = _TimedProxy(eng.admission, counter)
            t0 = time.perf_counter()
            stats = eng.run(mk_reqs(range(n_req)), deterministic=True)
            wall = time.perf_counter() - t0
            return eng, stats, wall, counter[0]

        det_run("warm")  # compile + warm outside every timed window
        eng_a, st_a, _, _ = det_run("a")
        eng_b, st_b, _, _ = det_run("b")
        shed_of = lambda e: sorted((s.req.request_id, s.fail_cause)
                                   for s in e.shed)
        toks_of = lambda e: {s.req.request_id: s.tokens
                             for s in e.finished}
        outcomes = st_a["outcomes"]
        silent = [rid for rid, (state, cause) in outcomes.items()
                  if state not in ("finished", "rejected", "shed",
                                   "failed")
                  or (state != "finished" and not cause)]

        # attributed admission+journal cost on the same deterministic
        # trace (max of 2 — conservative, like the overlap_bench gate)
        attrs = []
        det_wall = None
        for i in range(2):
            _, _, w, obs = det_run(f"attr{i}", attribute=True)
            attrs.append(obs / max(w, 1e-9))
            det_wall = w if det_wall is None else min(det_wall, w)
        attr = max(attrs)

        # wall-clock goodput run: the burst arrives at 2x the rate the
        # engine drains it; the iteration-clock deadlines rescale to
        # seconds via the measured per-iteration wall
        pace = det_wall / (2.0 * n_req)
        it_wall = det_wall / max(st_a["iterations"], 1)
        eng_w = InferenceEngine(params, config, ServeConfig(**serve),
                                journal=os.path.join(root, "wall.jsonl"))
        t0 = time.perf_counter()
        st_w = eng_w.run(mk_reqs([i * pace for i in range(n_req)],
                                 scale=it_wall))
        wall = time.perf_counter() - t0

        out = {
            "requests": n_req,
            "shed_deterministic": shed_of(eng_a) == shed_of(eng_b),
            "streams_identical": toks_of(eng_a) == toks_of(eng_b),
            "no_silent_drops": not silent,
            "pool_leak_free": eng_a.pool.used_blocks == 0
                              and eng_w.pool.used_blocks == 0,
            "det_finished": st_a["requests"],
            "det_shed": st_a["shed"],
            "det_rejected": st_a["rejected"],
            "admission_journal_overhead_pct": round(attr * 100.0, 3),
            "goodput_tokens_per_sec":
                round(st_w["generated_tokens"] / wall, 2),
            "wall_finished": st_w["requests"],
            "wall_shed_rate": round(st_w["shed"] / n_req, 3),
            "wall_rejected": st_w["rejected"],
            "wall_ttft_p99_s": round(st_w["ttft_p99_s"], 4)
                if st_w["requests"] else None,
        }
        if not on_tpu:
            out["note"] = ("tiny config in pallas interpret mode on CPU "
                           "— functional rung; flagship burst lands with "
                           "the TPU bench round")
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_serve_prefix_cache(dev, config, on_tpu):
    """PR-16 tentpole rung: prefix-cached serving (COW shared KV blocks)
    under a Poisson trace where 80% of requests share one long system
    prompt. Reports the cache hit rate, TTFT p50/p99 cache-on vs
    cache-off on the SAME trace, tokens/s, and the two correctness
    gates the feature ships under: cached-vs-cold greedy tokens bitwise
    identical, and a leak-free pool (shared blocks counted once,
    parked cache blocks excluded)."""
    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params

    rng = np.random.RandomState(16)
    if on_tpu:
        serve_kw = dict(block_size=128, num_blocks=257, max_batch=8,
                        prefill_chunk=256, max_seq_len=2048)
        n_req, rate, max_new, sys_len = 24, 12.0, 32, 1024
        tail = (16, 96)
    else:
        serve_kw = dict(block_size=128, num_blocks=24, max_batch=2,
                        prefill_chunk=64, max_seq_len=512)
        n_req, rate, max_new, sys_len = 10, 4.0, 6, 384
        tail = (8, 24)
    params = init_llama_params(config, seed=0)
    system = rng.randint(1, config.vocab_size, size=sys_len).tolist()
    prompts = []
    for i in range(n_req):
        if rng.rand() < 0.8 or i == 0:   # 80% share the system prompt
            sfx = rng.randint(1, config.vocab_size,
                              size=rng.randint(*tail)).tolist()
            prompts.append(system + sfx)
        else:
            prompts.append(rng.randint(
                1, config.vocab_size,
                size=rng.randint(sys_len // 4, sys_len // 2)).tolist())
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))

    def wall_run(prefix_cache):
        eng = InferenceEngine(
            params, config, ServeConfig(prefix_cache=prefix_cache,
                                        **serve_kw))
        reqs = [Request(list(p), max_new_tokens=max_new, arrival=float(t))
                for p, t in zip(prompts, arrivals)]
        t0 = time.perf_counter()
        stats = eng.run(reqs)
        return eng, stats, time.perf_counter() - t0

    def det_tokens(prefix_cache):
        eng = InferenceEngine(
            params, config, ServeConfig(prefix_cache=prefix_cache,
                                        **serve_kw))
        reqs = [Request(list(p), max_new_tokens=max_new, arrival=float(i))
                for i, p in enumerate(prompts)]
        eng.run(reqs, deterministic=True)
        return eng, {s.req.request_id: list(s.generated)
                     for s in eng.finished}

    det_tokens(False)            # warm the jit caches outside timing
    eng_off, st_off, wall_off = wall_run(False)
    eng_on, st_on, wall_on = wall_run(True)
    pc = eng_on.stats()["prefix_cache"]
    # bitwise parity gate on a deterministic replay of the same prompts
    eng_dc, toks_cold = det_tokens(False)
    eng_dw, toks_warm = det_tokens(True)
    # hit requests' first token can land inside the arrival-poll
    # iteration (TTFT records as 0.0); floor at 1 ms so the speedup
    # stays a finite, conservative number
    p50_up = st_off["ttft_p50_s"] / max(st_on["ttft_p50_s"], 1e-3)
    out = {
        "requests": n_req,
        "shared_prefix_tokens": sys_len,
        "hit_rate": pc["hit_rate"],
        "hit_tokens": pc["hit_tokens"],
        "cached_blocks": pc["cached_blocks"],
        "cow_copies": pc["cow_copies"],
        "ttft_p50_s_off": round(st_off["ttft_p50_s"], 4),
        "ttft_p50_s_on": round(st_on["ttft_p50_s"], 4),
        "ttft_p99_s_off": round(st_off["ttft_p99_s"], 4),
        "ttft_p99_s_on": round(st_on["ttft_p99_s"], 4),
        "ttft_p50_speedup": round(p50_up, 2),
        "tokens_per_sec_off":
            round(st_off["generated_tokens"] / wall_off, 2),
        "tokens_per_sec_on":
            round(st_on["generated_tokens"] / wall_on, 2),
        "cached_tokens_identical": toks_warm == toks_cold,
        "pool_leak_free": all(e.pool.used_blocks == 0 for e in
                              (eng_off, eng_on, eng_dc, eng_dw)),
        "det_hits": eng_dw.stats()["prefix_cache"]["hits"],
    }
    if not on_tpu:
        out["note"] = ("tiny config in pallas interpret mode on CPU — "
                       "functional rung; flagship trace lands with the "
                       "TPU bench round")
    return out


def bench_serve_kv_int8(dev, config, on_tpu):
    """PR-16 rung: int8 paged KV capacity. At a FIXED pool byte budget,
    how many sequences are concurrently resident with int8 blocks
    (bytes + per-column fp32 scale sidecars) vs fp16 blocks — measured
    by actually serving that many one-block sequences with zero
    preemptions — plus decode wall per token for each dtype. Uses a
    head_dim=64 config: the ratio 2*hd/(hd+4) needs hd >= 36 to clear
    the 1.8x target (at hd=64 the analytic ceiling is 1.88x)."""
    import jax.numpy as jnp

    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params, llama_tiny

    if on_tpu:
        cfg = llama_tiny(vocab=2048, hidden=1024, layers=4, heads=16,
                         kv_heads=8, seq=256)
        budget_blocks, max_new, plen = 64, 8, 100
    else:
        cfg = llama_tiny(vocab=96, hidden=256, layers=1, heads=4,
                         kv_heads=2, seq=256)
        budget_blocks, max_new, plen = 8, 2, 100
    bs = 128
    kvd = cfg.num_key_value_heads * (
        cfg.hidden_size // cfg.num_attention_heads)
    nkv = cfg.num_key_value_heads
    # per-block bytes across k+v (per layer): fp16/fp32 model dtype vs
    # int8 bytes + one fp32 scale per (kv-head, column)
    fp_item = jnp.dtype(cfg.dtype).itemsize
    bytes_fp = 2 * kvd * bs * fp_item
    bytes_i8 = 2 * (kvd * bs * 1 + nkv * bs * 4)
    budget = budget_blocks * bytes_fp
    blocks_i8 = int(budget // bytes_i8)
    params = init_llama_params(cfg, seed=0)
    rng = np.random.RandomState(8)

    def peak_concurrency(kv_dtype, usable):
        serve = ServeConfig(block_size=bs, num_blocks=usable + 1,
                            max_batch=usable, prefill_chunk=128,
                            max_seq_len=128, kv_dtype=kv_dtype)
        eng = InferenceEngine(params, cfg, serve, record_events=True)
        reqs = [Request(rng.randint(1, cfg.vocab_size,
                                    size=plen).tolist(),
                        max_new_tokens=max_new, arrival=0.0)
                for _ in range(usable)]
        t0 = time.perf_counter()
        stats = eng.run(reqs)
        wall = time.perf_counter() - t0
        live = peak = 0
        for ev in eng.events:
            kind = ev[1]
            if kind == "admit":
                live += 1
                peak = max(peak, live)
            elif kind in ("finish", "evict", "shed", "failed"):
                live -= 1
        assert stats["preemptions"] == 0 and eng.pool.used_blocks == 0
        return peak, stats, wall

    peak_concurrency("auto", budget_blocks)      # warm jit caches
    peak_fp, st_fp, wall_fp = peak_concurrency("auto", budget_blocks)
    peak_i8, st_i8, wall_i8 = peak_concurrency("int8", blocks_i8)
    dec_fp = wall_fp / max(st_fp["generated_tokens"], 1)
    dec_i8 = wall_i8 / max(st_i8["generated_tokens"], 1)
    out = {
        "head_dim": cfg.hidden_size // cfg.num_attention_heads,
        "pool_budget_bytes_per_layer": int(budget),
        "block_bytes_fp": int(bytes_fp),
        "block_bytes_int8": int(bytes_i8),
        "blocks_fp": budget_blocks,
        "blocks_int8": blocks_i8,
        "max_concurrent_fp": peak_fp,
        "max_concurrent_int8": peak_i8,
        "concurrency_ratio": round(peak_i8 / max(peak_fp, 1), 2),
        # the 1.8x contract pinned against fp16 block bytes, independent
        # of the platform model dtype (fp32 on CPU inflates the measured
        # ratio above this)
        "model_kv_itemsize": int(fp_item),
        "fp16_equivalent_ratio": round(2 * kvd * bs * 2 / bytes_i8, 2),
        "decode_ms_per_tok_fp": round(dec_fp * 1e3, 3),
        "decode_ms_per_tok_int8": round(dec_i8 * 1e3, 3),
        "decode_ms_ratio": round(dec_i8 / max(dec_fp, 1e-9), 2),
    }
    if not on_tpu:
        out["note"] = ("tiny hd=64 config in pallas interpret mode on "
                       "CPU — capacity ratio is exact (byte arithmetic "
                       "+ real concurrent serving); decode timing is "
                       "interpret-mode, honest only relatively")
    return out


def bench_serve_speculative(dev, config, on_tpu):
    """PR-18 tentpole rung: speculative decoding (draft model + batched
    paged verification) vs the sequential engine on the SAME
    shared-prefix Poisson trace. Reports accept-rate, tokens/s and TPOT
    p50/p99 for both engines, and the gate the feature ships under:
    speculative streams token-bitwise-identical to sequential greedy
    decode (deterministic replay), leak-free pool.

    Throughput is measured in the deterministic ITERATION clock
    (tokens per scheduler iteration): on a real TPU decode is
    memory-bound, so a verify pass over K+1 positions costs roughly one
    sequential step and tokens/iteration is the honest speedup proxy;
    interpret-mode wall time scales with arithmetic instead and is
    reported alongside for reference only."""
    import jax

    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params

    rng = np.random.RandomState(18)
    if on_tpu:
        serve_kw = dict(block_size=128, num_blocks=257, max_batch=8,
                        prefill_chunk=256, max_seq_len=2048)
        n_req, rate, max_new, sys_len, K = 24, 12.0, 32, 512, 4
        tail = (16, 96)
    else:
        serve_kw = dict(block_size=128, num_blocks=24, max_batch=2,
                        prefill_chunk=64, max_seq_len=256)
        n_req, rate, max_new, sys_len, K = 8, 6.0, 8, 96, 3
        tail = (8, 24)
    params = init_llama_params(config, seed=0)
    # Condition the weights so the default layer-truncated draft tracks
    # the base model: damp every layer's residual writes so logits are
    # dominated by the embedding path both models share. The parity
    # gate below holds for ANY weights by construction (emitted tokens
    # are always the base argmax); the damping only makes the recorded
    # accept-rate/speedup representative of a draft trained to track
    # its base, rather than of two mutually-random networks.
    damp = 0.05
    layers = dict(params["layers"])
    for name in ("o_proj", "down_proj"):
        layers[name] = jax.tree_util.tree_map(lambda a: a * damp,
                                              layers[name])
    params = dict(params, layers=layers)
    system = rng.randint(1, config.vocab_size, size=sys_len).tolist()
    prompts = [system + rng.randint(1, config.vocab_size,
                                    size=rng.randint(*tail)).tolist()
               for _ in range(n_req)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))

    def det_run(speculative):
        eng = InferenceEngine(
            params, config, ServeConfig(speculative=speculative,
                                        draft_k=K, **serve_kw))
        reqs = [Request(list(p), max_new_tokens=max_new, arrival=float(i))
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        stats = eng.run(reqs, deterministic=True)
        wall = time.perf_counter() - t0
        toks = {s.req.request_id: list(s.generated) for s in eng.finished}
        return eng, stats, wall, toks

    def wall_run(speculative):
        eng = InferenceEngine(
            params, config, ServeConfig(speculative=speculative,
                                        draft_k=K, **serve_kw))
        reqs = [Request(list(p), max_new_tokens=max_new, arrival=float(t))
                for p, t in zip(prompts, arrivals)]
        t0 = time.perf_counter()
        eng.run(reqs)
        return eng, time.perf_counter() - t0

    det_run(True)                # warm the jit caches outside timing
    det_run(False)
    eng_off, st_off, dwall_off, toks_off = det_run(False)
    eng_on, st_on, dwall_on, toks_on = det_run(True)
    weng_off, wall_off = wall_run(False)
    weng_on, wall_on = wall_run(True)
    sp = eng_on.stats()["speculative"]
    # iteration-clock throughput: tokens per scheduler iteration
    tpi_off = st_off["generated_tokens"] / max(st_off["iterations"], 1)
    tpi_on = st_on["generated_tokens"] / max(st_on["iterations"], 1)
    out = {
        "requests": n_req,
        "draft_k": K,
        "draft_layers": sp["draft_layers"],
        "base_layers": config.num_hidden_layers,
        "accept_rate": round(sp["accept_rate"], 3),
        "proposed": sp["proposed"],
        "accepted": sp["accepted"],
        "tokens_per_iteration_off": round(tpi_off, 3),
        "tokens_per_iteration_on": round(tpi_on, 3),
        "speedup": round(tpi_on / max(tpi_off, 1e-9), 2),
        "tpot_p50_iters_off": round(st_off["tpot_p50_s"], 4),
        "tpot_p50_iters_on": round(st_on["tpot_p50_s"], 4),
        "tpot_p99_iters_off": round(st_off["tpot_p99_s"], 4),
        "tpot_p99_iters_on": round(st_on["tpot_p99_s"], 4),
        "iterations_off": st_off["iterations"],
        "iterations_on": st_on["iterations"],
        "wall_tokens_per_sec_off":
            round(weng_off.stats()["generated_tokens"] / wall_off, 2),
        "wall_tokens_per_sec_on":
            round(weng_on.stats()["generated_tokens"] / wall_on, 2),
        "streams_identical": toks_on == toks_off,
        "pool_leak_free": all(e.pool.used_blocks == 0 for e in
                              (eng_off, eng_on, weng_off, weng_on)),
        "compiled_shapes": sorted(st_on["compiles"]),
        "arrival_trace": {"process": "poisson", "rate_per_s": rate,
                          "shared_prefix_tokens": sys_len},
    }
    if not on_tpu:
        out["note"] = ("tiny config in pallas interpret mode on CPU — "
                       "speedup is the iteration-clock proxy (interpret "
                       "wall time scales with arithmetic, not memory "
                       "traffic); TPU round lands final numbers")
    return out


def bench_serve_tp(dev, config, on_tpu):
    """PR-19 tentpole rung: tensor-parallel serving. The same Poisson
    trace served at mp=1 and at every feasible mp in {2, 4} — weights
    sliced per param_pspecs, KV pools sharded by kv-head — with
    speculation + int8 KV + prefix caching all on. Reports per-degree
    tokens/s, TTFT/TPOT p50/p99 and pool-bytes-per-rank, and the gates
    the feature ships under: every sharded stream token-bitwise-
    identical to mp=1 (greedy argmax absorbs the ULP drift of the
    row-parallel reductions; PARITY.md), leak-free pools at every
    degree.

    Off-TPU the virtual CPU mesh time-slices one host, so wall-clock
    "speedup" measures sharding overhead, not parallel speedup — the
    honest per-rank win there is pool_bytes_per_rank halving per
    doubling of mp; the TPU round lands real scaling numbers."""
    import jax

    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params, llama_tiny

    rng = np.random.RandomState(19)
    if on_tpu:
        cfg = config  # flagship: nh=nkv=16, vocab/inter % 4 == 0
        serve_kw = dict(block_size=128, num_blocks=257, max_batch=8,
                        prefill_chunk=256, max_seq_len=2048)
        n_req, rate, max_new, sys_len, tail = 24, 12.0, 32, 512, (16, 96)
    else:
        # kv_heads=4 so mp=4 can shard the pools one kv head per rank
        cfg = llama_tiny(vocab=96, hidden=64, layers=2, heads=4,
                         kv_heads=4, seq=256)
        serve_kw = dict(block_size=128, num_blocks=24, max_batch=2,
                        prefill_chunk=64, max_seq_len=256)
        n_req, rate, max_new, sys_len, tail = 8, 6.0, 8, 96, (8, 24)
    spec_kw = dict(speculative=True, draft_k=3, prefix_cache=True,
                   kv_dtype="int8")
    ndev = len(jax.devices())
    degrees = [m for m in (1, 2, 4)
               if m <= ndev and cfg.num_key_value_heads % m == 0]
    if degrees == [1]:
        return {"note": f"needs >= 2 local devices for the mp rung, have "
                        f"{ndev} — run under XLA_FLAGS="
                        f"--xla_force_host_platform_device_count=8",
                "devices": ndev}
    params = init_llama_params(cfg, seed=0)
    system = rng.randint(1, cfg.vocab_size, size=sys_len).tolist()
    prompts = [system + rng.randint(1, cfg.vocab_size,
                                    size=rng.randint(*tail)).tolist()
               for _ in range(n_req)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))

    def det_run(mp):
        eng = InferenceEngine(params, cfg,
                              ServeConfig(mp=mp, **spec_kw, **serve_kw))
        reqs = [Request(list(p), max_new_tokens=max_new, arrival=float(i))
                for i, p in enumerate(prompts)]
        stats = eng.run(reqs, deterministic=True)
        toks = {s.req.request_id: list(s.generated) for s in eng.finished}
        return eng, stats, toks

    def wall_run(mp):
        eng = InferenceEngine(params, cfg,
                              ServeConfig(mp=mp, **spec_kw, **serve_kw))
        reqs = [Request(list(p), max_new_tokens=max_new, arrival=float(t))
                for p, t in zip(prompts, arrivals)]
        t0 = time.perf_counter()
        stats = eng.run(reqs)
        return eng, stats, time.perf_counter() - t0

    per_degree, ref_toks, leak_free, parity = {}, None, True, True
    for mp in degrees:
        det_run(mp)  # warm the per-degree jit caches outside timing
        eng_d, st_d, toks = det_run(mp)
        eng_w, st_w, wall = wall_run(mp)
        if mp == degrees[0]:
            ref_toks = toks
        parity = parity and (toks == ref_toks)
        leak_free = leak_free and all(e.pool.used_blocks == 0
                                      for e in (eng_d, eng_w))
        per_degree[f"mp{mp}"] = {
            "tokens_per_iteration": round(
                st_d["generated_tokens"] / max(st_d["iterations"], 1), 3),
            "wall_tokens_per_sec": round(
                st_w["generated_tokens"] / wall, 2),
            "ttft_p50_s": round(st_w["ttft_p50_s"], 4),
            "ttft_p99_s": round(st_w["ttft_p99_s"], 4),
            "tpot_p50_s": round(st_w["tpot_p50_s"], 4),
            "tpot_p99_s": round(st_w["tpot_p99_s"], 4),
            "pool_bytes_per_rank": eng_d.stats()["pool_bytes_per_rank"],
            "compiled_shapes": sorted(st_d["compiles"]),
        }
    base = per_degree[f"mp{degrees[0]}"]
    top = per_degree[f"mp{degrees[-1]}"]
    out = {
        "requests": n_req,
        "degrees": degrees,
        "kv_heads": cfg.num_key_value_heads,
        **per_degree,
        "wall_speedup_top": round(top["wall_tokens_per_sec"]
                                  / max(base["wall_tokens_per_sec"], 1e-9),
                                  2),
        "pool_bytes_ratio_top": round(base["pool_bytes_per_rank"]
                                      / max(top["pool_bytes_per_rank"], 1),
                                      2),
        "streams_identical": parity,
        "pool_leak_free": leak_free,
        "arrival_trace": {"process": "poisson", "rate_per_s": rate,
                          "shared_prefix_tokens": sys_len},
    }
    if not on_tpu:
        out["note"] = ("tiny config on the virtual CPU mesh — parity and "
                       "per-rank pool bytes are exact; wall-clock numbers "
                       "measure sharding overhead on one time-sliced "
                       "host, not parallel speedup; TPU round lands real "
                       "scaling")
    return out


def bench_serve_fleet(dev, config, on_tpu):
    """PR-20 tentpole rung: the multi-replica serving fleet. One
    shared-prefix Poisson trace served by N in {1, 2, 4} FleetRouter
    replicas (prefix caching on, per-replica journals), reporting
    per-N tokens/s and the router's affinity hit rate, plus the gates
    the feature ships under: every fleet's streams token-bitwise-
    identical to the lone engine's (greedy decode is a pure function
    of prompt + weights — replica count cannot change tokens), an A/B
    of affinity vs seeded-random dispatch on fleet-wide prefix-cache
    reuse, a chaos cell (kill one replica mid-burst: zero lost
    accepted requests, migrated streams bit-identical), and a rolling
    fleet-wide weight swap (every replica swaps at its idle boundary,
    zero drops).

    Off-TPU the replicas time-slice one host, so wall-clock "speedup"
    measures router + duplication overhead, not parallel speedup — the
    honest wins there are the affinity hit-rate delta and the chaos /
    rolling-swap gates; the TPU round lands real scaling numbers."""
    import shutil
    import tempfile

    from paddle_tpu.inference import (FleetRouter, InferenceEngine,
                                      Request, ServeConfig)
    from paddle_tpu.models.llama import init_llama_params, llama_tiny

    rng = np.random.RandomState(20)
    if on_tpu:
        cfg = config
        serve_kw = dict(block_size=128, num_blocks=257, max_batch=8,
                        prefill_chunk=256, max_seq_len=2048,
                        prefix_cache=True)
        n_req, rate, max_new, sys_len, tail = 24, 12.0, 32, 512, (16, 96)
    else:
        cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4,
                         kv_heads=2, seq=512)
        serve_kw = dict(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=256,
                        prefix_cache=True)
        n_req, rate, max_new, sys_len, tail = 10, 4.0, 6, 140, (6, 16)
    params = init_llama_params(cfg, seed=0)
    system = rng.randint(1, cfg.vocab_size, size=sys_len).tolist()
    prompts = [system + rng.randint(1, cfg.vocab_size,
                                    size=rng.randint(*tail)).tolist()
               for _ in range(n_req)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))

    def det_reqs():
        # iteration-clock arrivals, spaced so the shared prefix is
        # derived before later submits probe for it
        return [Request(list(p), max_new_tokens=max_new,
                        arrival=float(2 * i))
                for i, p in enumerate(prompts)]

    def det_run(n, policy="affinity", **runkw):
        d = tempfile.mkdtemp(prefix="fleet_bench_")
        try:
            fleet = FleetRouter(params, cfg, ServeConfig(**serve_kw),
                                n_replicas=n, journal_dir=d,
                                policy=policy)
            stats = fleet.run(det_reqs(), deterministic=True, **runkw)
            return fleet, stats, fleet.streams()
        finally:
            shutil.rmtree(d, ignore_errors=True)

    def wall_run(n):
        fleet = FleetRouter(params, cfg, ServeConfig(**serve_kw),
                            n_replicas=n)
        reqs = [Request(list(p), max_new_tokens=max_new,
                        arrival=float(t))
                for p, t in zip(prompts, arrivals)]
        t0 = time.perf_counter()
        stats = fleet.run(reqs)
        return fleet, stats, time.perf_counter() - t0

    # lone-engine reference: the bit-identity oracle for every fleet
    ref_eng = InferenceEngine(params, cfg, ServeConfig(**serve_kw))
    reqs = det_reqs()
    for i, r in enumerate(reqs):
        r.request_id = i
    ref_eng.run(reqs, deterministic=True)
    ref = {s.req.request_id: list(s.generated) for s in ref_eng.finished}

    per_n, parity, leak_free, zero_lost = {}, True, True, True
    for n in (1, 2, 4):
        det_run(n)  # warm the jit caches outside timing
        fleet_d, st_d, toks = det_run(n)
        fleet_w, st_w, wall = wall_run(n)
        parity = parity and (toks == ref)
        zero_lost = zero_lost and st_d["lost"] == 0 == st_w["lost"]
        leak_free = leak_free and all(
            fleet_d.engines[i].pool.used_blocks == 0
            for i in fleet_d._live())
        per_n[f"n{n}"] = {
            "tokens_per_iteration": round(
                st_d["generated_tokens"] / max(st_d["iterations"], 1),
                3),
            "wall_tokens_per_sec": round(
                st_w["generated_tokens"] / wall, 2),
            # worst live replica's streaming TTFT p99 (the fleet's
            # client-visible tail)
            "ttft_p99_s": round(max(
                fleet_w.engines[i].slo["ttft"].percentile(99) or 0.0
                for i in fleet_w._live()), 4),
            "affinity_hit_rate": (round(st_d["affinity_hit_rate"], 3)
                                  if st_d["affinity_hit_rate"]
                                  is not None else None),
            "spills": st_d["spills"],
            "routed_per_replica": st_d["routed_per_replica"],
        }

    # A/B: affinity vs seeded-random dispatch, fleet-wide cache reuse
    fleet_a, st_a, _ = det_run(4)
    fleet_r, st_r, toks_r = det_run(4, policy="random")
    aff_tokens = sum(e.cache.hit_tokens for e in fleet_a.engines)
    rnd_tokens = sum(e.cache.hit_tokens for e in fleet_r.engines)

    # chaos: kill replica 0 mid-burst, journal migration onto survivors
    fleet_c, st_c, toks_c = det_run(3, kill_at=(n_req, 0))

    # rolling fleet-wide weight swap under traffic (same weights, so
    # bit-identity doubles as the zero-drop check)
    fleet_s, st_s, toks_s = det_run(3, rolling_swap_at=3,
                                    swap_source=params)

    base = per_n["n1"]["wall_tokens_per_sec"]
    top = per_n["n4"]["wall_tokens_per_sec"]
    out = {
        "requests": n_req,
        "replica_counts": [1, 2, 4],
        **per_n,
        "wall_speedup_top": round(top / max(base, 1e-9), 2),
        "streams_identical": parity,
        "zero_lost": zero_lost,
        "pool_leak_free": leak_free,
        "affinity_ab": {
            "affinity_hit_tokens": aff_tokens,
            "random_hit_tokens": rnd_tokens,
            "affinity_wins": bool(aff_tokens >= rnd_tokens),
            "random_streams_identical": toks_r == ref,
        },
        "chaos_kill": {
            "migrations": st_c["migrations"],
            "lost": st_c["lost"],
            "streams_identical": toks_c == ref,
            "survivors_leak_free": all(
                fleet_c.engines[i].pool.used_blocks == 0
                for i in fleet_c._live()),
        },
        "rolling_swap": {
            "swapped": st_s["rolling_swaps"],
            "lost": st_s["lost"],
            "streams_identical": toks_s == ref,
            "drops": sum(e.last_swap["in_flight_running"]
                         + e.last_swap["in_flight_prefill"]
                         for e in fleet_s.engines
                         if e.last_swap is not None),
        },
        "arrival_trace": {"process": "poisson", "rate_per_s": rate,
                          "shared_prefix_tokens": sys_len},
    }
    if not on_tpu:
        out["note"] = ("tiny config with replicas time-slicing one "
                       "host — parity, zero-lost and hit-rate gates "
                       "are exact; wall-clock speedup measures router "
                       "overhead, not parallel scaling; TPU round "
                       "lands real numbers")
    return out


def _static_analysis_record():
    """Per-rule finding counts from paddle_tpu.analysis — the bench
    record carries the lint posture of the tree the numbers came from
    (a weak-scalar or host-sync regression shows up next to the MFU it
    distorted)."""
    try:
        from paddle_tpu.analysis import apply_baseline, run as run_analysis
        report = run_analysis()
        stale = apply_baseline(report)
    except Exception as exc:  # the record is telemetry, never a gate
        return {"error": f"{type(exc).__name__}: {exc}"}
    return {
        "rules": report.to_json()["rules"],
        "total_active": len(report.active),
        "total_suppressed": len(report.suppressed),
        "total_allowlisted": len(report.allowlisted),
        # PR-11 ratchet posture: findings the baseline absorbs (debt
        # still to burn down) and entries whose finding is gone (stale
        # — the ratchet demands their deletion)
        "total_baselined": len(report.baselined),
        "baseline_stale": len(stale),
    }


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        # every number below is written under a device metric's name
        # (MFU, x_of_floor, attn_eff): a CPU run has none of them
        raise SystemExit(
            "bench.py measures the chip and JAX found only the CPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            "nothing is written from a CPU run")
    on_tpu = True   # the `else` arms below are dead (ROADMAP S0 removes them)
    seq = 2048 if on_tpu else 128
    batch = 4 if on_tpu else 2
    if on_tpu:
        # flagship shape: head_dim=128 (Llama-2's), MXU-sized matmuls
        config = LlamaConfig(vocab_size=32000, hidden_size=2048,
                             intermediate_size=8192, num_hidden_layers=12,
                             num_attention_heads=16, num_key_value_heads=16,
                             max_position_embeddings=seq, dtype=jnp.bfloat16)
        # round-1 shape (head_dim=64), kept for cross-round comparability
        config_hd64 = LlamaConfig(vocab_size=32000, hidden_size=1024,
                                  intermediate_size=4096, num_hidden_layers=24,
                                  num_attention_heads=16,
                                  num_key_value_heads=16,
                                  max_position_embeddings=seq,
                                  dtype=jnp.bfloat16)
    else:
        from paddle_tpu.models.llama import llama_tiny
        config = llama_tiny(seq=seq)
        config_hd64 = None

    mfu, tok_s, dt, loss = run_config(config, batch, seq, dev)
    detail = {
        "tokens_per_sec_per_chip": round(tok_s, 1),
        "step_time_s": round(dt, 4),
        "device": str(getattr(dev, "device_kind", dev.platform)),
        "seq_len": seq, "batch": batch,
        "hidden": config.hidden_size, "layers": config.num_hidden_layers,
        "head_dim": config.head_dim,
        "loss": round(loss, 4),
    }
    if config_hd64 is not None:
        mfu64, tok_s64, dt64, _ = run_config(config_hd64, batch, seq, dev)
        detail["hd64_shape"] = {
            "mfu": round(float(mfu64), 4),
            "tokens_per_sec_per_chip": round(tok_s64, 1),
            "step_time_s": round(dt64, 4),
            "hidden": config_hd64.hidden_size,
            "layers": config_hd64.num_hidden_layers,
            "head_dim": config_hd64.head_dim,
        }

    if on_tpu:
        # North-star geometry (BASELINE.md): REAL Llama-2 7B / 13B layer
        # shapes. One v5e chip cannot hold the full models with AdamW
        # states (12 B/param), so these run as many true-geometry layers
        # as fit (measured: 7B fits L=4 at B=8, 13B L=2 at B=8; L+1 or
        # 2xB is RESOURCE_EXHAUSTED; the offload_attn remat policy fits
        # B=16 but host-offload traffic drops MFU to 0.49). vocab=8192
        # keeps the embedding from crowding out layers — per-layer MFU is
        # the quantity of interest. Per-chip MFU at these shapes is the
        # single-chip factor of the v5p-128 north-star target.
        for key, h, inter, heads, L7, b7, pol in (
                ("7b_shape", 4096, 11008, 32, 4, 8, "save_attn"),
                ("13b_layer", 5120, 13824, 40, 2, 8, "save_mlp")):
            cfg_ns = LlamaConfig(vocab_size=8192, hidden_size=h,
                                 intermediate_size=inter,
                                 num_hidden_layers=L7,
                                 num_attention_heads=heads,
                                 num_key_value_heads=heads,
                                 max_position_embeddings=seq,
                                 dtype=jnp.bfloat16)
            mfu_ns, tok_ns, dt_ns, _ = run_config(cfg_ns, b7, seq, dev,
                                                  policy=pol)
            detail[key] = {
                "mfu": round(float(mfu_ns), 4),
                "tokens_per_sec_per_chip": round(tok_ns, 1),
                "step_time_s": round(dt_ns, 4),
                "hidden": h, "intermediate": inter, "layers": L7,
                "batch": b7, "head_dim": 128,
            }

    # KV-cache greedy decode (whole continuation = one dispatch). ms/step is
    # bounded below by streaming all bf16 weights from HBM once per step
    # (weight_floor_ms); tok/s scales with batch at near-constant step time.
    decode = {}
    variants = [("flagship", config, False)] + (
        [("hd64", config_hd64, False)] if config_hd64 is not None else [])
    if on_tpu:
        # weight-only int8 (quantize_llama_int8): halves the weight stream
        # — decode lands BELOW the bf16 floor
        variants.append(("flagship_int8", config, True))
    for name, cfg, quant in variants:
        for b in (1, 8):
            mspt, tok_s_d, floor, mfloor = run_decode(cfg, b, dev,
                                                      quantize=quant)
            decode[f"{name}_b{b}"] = {
                "ms_per_step": round(mspt, 2),
                "tokens_per_sec": round(tok_s_d, 1),
                "weight_floor_ms": round(floor, 2),
                "measured_floor_ms": round(mfloor, 2),
                "x_of_floor": round(mspt / mfloor, 2),
            }
    if on_tpu:
        decode["measured_hbm_gbs"] = round(measured_hbm_bw(dev) / 1e9, 1)
        if config_hd64 is not None:
            decode["hd64_pair_stack_ab"] = decode_pair_stack_ab(
                dev, config_hd64)
            decode["hd64_block_sweep"] = decode_block_sweep(
                dev, config_hd64)
    detail["decode"] = decode

    # continuous-batching serving engine (paged KV cache) under a
    # Poisson arrival trace — runs on both backends
    detail["serve_continuous"] = bench_serve_continuous(dev, config, on_tpu)

    # preemption-tolerant training (PR 13): checkpoint-overlap cost,
    # resume-to-parity, live weight-swap drain — runs on both backends
    detail["preempt_resume"] = bench_preempt_resume(dev, config, on_tpu)

    # overload-hardened serving (PR 14): deterministic shedding, goodput
    # under a 2x burst, admission+journal cost — runs on both backends
    detail["serve_overload"] = bench_serve_overload(dev, config, on_tpu)

    # prefix-cached serving + int8 paged KV (PR 16): TTFT under shared
    # system prompts, capacity at fixed pool bytes — both backends
    detail["serve_prefix_cache"] = bench_serve_prefix_cache(
        dev, config, on_tpu)
    detail["serve_kv_int8"] = bench_serve_kv_int8(dev, config, on_tpu)

    # speculative decoding (PR 18): draft model + batched paged
    # verification vs the sequential engine on the same trace — both
    # backends; parity gate (streams bitwise-identical) always enforced
    detail["serve_speculative"] = bench_serve_speculative(
        dev, config, on_tpu)

    # tensor-parallel serving (PR 19): the engine inside the mp ring
    # plans, sharded KV pools, bitwise parity vs mp=1 — both backends
    # (off-TPU needs the virtual CPU mesh: XLA_FLAGS device count >= 2)
    detail["serve_tp"] = bench_serve_tp(dev, config, on_tpu)

    # multi-replica fleet serving (PR 20): prefix-affinity router over
    # N engines, chaos kill + journal migration, rolling weight swap
    detail["serve_fleet"] = bench_serve_fleet(dev, config, on_tpu)

    # fleet observability (PR 15): attributed FleetMonitor cost + loss
    # parity monitored vs bare — runs on both backends
    detail["fleet_observability"] = bench_fleet_observability(
        dev, config, on_tpu)

    # kernel-level performance attribution (PR 17): always-on roofline
    # ledger parity + attributed cost, measured-mode component
    # itemization — runs on both backends
    detail["ledger_roofline"] = bench_ledger_roofline(dev, config, on_tpu)

    if on_tpu:
        detail["step_ledger_flagship"] = bench_step_ledger(
            dev, config, batch, seq, dt)

    if on_tpu:
        # long-context: streaming-KV Pallas kernels (whole-KV residency
        # would exceed VMEM ~6k tokens earlier); causal, head_dim=128.
        # Timed via profiler DEVICE events: wall-clock carries the host's
        # dispatch of every call, which buried these kernels under ~10x
        # noise in the round-2 numbers (0.082 "eff" for a kernel actually
        # running at 0.60).
        import jax as _jax
        from paddle_tpu.ops import flash_attention as _fa
        long_seq = {}
        for s_long in (16384, 32768, 131072):
            # 131072 halves bh: 8 heads of q/k/v/do + f32 grads at 128k
            # rows would not leave room for the dq streaming partials
            bh, d_ = (8, 128) if s_long <= 32768 else (4, 128)
            rng2 = np.random.RandomState(1)
            q = jnp.asarray(rng2.randn(bh, s_long, d_).astype(np.float32),
                            dtype=jnp.bfloat16)
            k = jnp.asarray(rng2.randn(bh, s_long, d_).astype(np.float32),
                            dtype=jnp.bfloat16)
            v = jnp.asarray(rng2.randn(bh, s_long, d_).astype(np.float32),
                            dtype=jnp.bfloat16)

            def fwd(q, k, v):
                return _fa._flash_fwd(q, k, v, True, 1 / 11.3, 1024, 1024)[0]

            def bwd(q, k, v):
                # grad w.r.t. ALL of q/k/v: grad-of-q-only would DCE the
                # dK/dV streaming kernel out of the program entirely
                loss = lambda q, k, v: (_fa._flash_attention(
                    q, k, v, True, 1 / 11.3, 1024, 1024)
                    .astype(jnp.float32) ** 2).sum()
                return _jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

            ms_f = device_time_ms(fwd, (q, k, v), f"lsfwd{s_long}")
            ms_b = device_time_ms(bwd, (q, k, v), f"lsbwd{s_long}")
            fl = 2 * 2 * bh * s_long * s_long * d_ / 2  # causal half
            # static schedule record for the r7 fused flat backward: which
            # path ran, blocks, and the fetch-once contract (r05 split-
            # kernel baseline for comparison: bwd_eff=0.599 at S=32768)
            sched = _fa.dense_bwd_schedule_stats(
                bh, s_long, s_long, d_, jnp.bfloat16, True, 1024, 1024)
            long_seq[f"S{s_long}"] = {
                "ms": round(ms_f, 1),
                "attn_eff": round(fl / (ms_f / 1e3) / peak_flops(dev), 3),
                "bwd_ms": round(ms_b, 1),
                # bwd does ~2.5x the fwd FLOPs (5 matmuls vs 2)
                "bwd_eff": round(2.5 * fl / (ms_b / 1e3) / peak_flops(dev), 3),
                "bwd_schedule": {k: v for k, v in sched.items()
                                 if k not in ("bh", "seq_q", "seq_k",
                                              "head_dim", "mode")},
            }
        long_seq["bwd_baseline_r05"] = {
            "bwd_eff_s32768": 0.599,
            "note": "split dkv+dq kernel pair (each block fetched twice, "
                    "7 matmuls/pair) before the r7 fused flat rewrite",
        }
        detail["long_seq_flash_fwd"] = long_seq

        # context-parallel strategy compare at 32k, sep=4: per-chip COMPUTE
        # proxy on one chip. Ring = the worst (last, causal) rank's n_sep
        # block-flash calls + lse merges; Ulysses = one full-S flash over
        # H/n_sep heads. Comm cost differs (ring overlaps ppermute with
        # block compute; Ulysses pays two all_to_alls) and needs a real
        # multi-chip slice to measure.
        from paddle_tpu.ops.flash_attention import flash_block_fwd
        from paddle_tpu.parallel.ring_attention import _merge_partials
        s_cp, n_sep, h_cp, d_cp = 32768, 4, 8, 128
        s_loc = s_cp // n_sep
        rng3 = np.random.RandomState(2)
        kr = jnp.asarray(rng3.randn(h_cp, s_cp, d_cp).astype(np.float32),
                         dtype=jnp.bfloat16)
        vr = jnp.asarray(rng3.randn(h_cp, s_cp, d_cp).astype(np.float32),
                         dtype=jnp.bfloat16)
        qr = jnp.asarray(rng3.randn(h_cp, s_loc, d_cp).astype(np.float32),
                         dtype=jnp.bfloat16)
        sc_cp = 1 / 11.3

        def cpring(q, k, v):
            o, lse = flash_block_fwd(q, k[:, -s_loc:], v[:, -s_loc:],
                                     causal=True, scale=sc_cp)
            o = o.astype(jnp.float32)
            for i in range(n_sep - 1):
                blk = slice(i * s_loc, (i + 1) * s_loc)
                ob, lb = flash_block_fwd(q, k[:, blk], v[:, blk],
                                         causal=False, scale=sc_cp)
                o, lse = _merge_partials(o, lse, ob, lb)
            return o

        qu = jnp.asarray(
            rng3.randn(h_cp // n_sep, s_cp, d_cp).astype(np.float32),
            dtype=jnp.bfloat16)

        def cpuly(q, k, v):
            return _fa._flash_fwd(q, k, v, True, sc_cp, 1024, 1024)[0]

        ms_ring = device_time_ms(cpring, (qr, kr, vr), "cpring")
        ms_uly = device_time_ms(
            cpuly, (qu, kr[:h_cp // n_sep], vr[:h_cp // n_sep]), "cpuly")
        detail["cp_compare_s32k_sep4"] = {
            "ring_worst_rank_ms": round(ms_ring, 2),
            "ulysses_ms": round(ms_uly, 2),
            "note": "compute proxy on one chip; ring overlaps ppermute "
                    "with block compute, Ulysses adds 2 all_to_alls. Real "
                    "sep=4 collective rung: cp_compare_sep4 in the "
                    "multichip dryrun (MULTICHIP json tail)",
        }

        # packed varlen attention (kernel-backed flash on the packed
        # layout, scalar-prefetched live-tile scheduling): a 16-sequence
        # 16k-token causal pack, fwd + full bwd
        from paddle_tpu.ops.flash_varlen import flash_varlen_attention
        vl_lens = [2048, 512, 1024, 3072, 256, 896, 1536, 2048,
                   128, 512, 768, 1024, 640, 384, 512, 640]
        vl_total, vl_max = sum(vl_lens), max(vl_lens)
        cu_vl = jnp.asarray(np.concatenate(
            [[0], np.cumsum(vl_lens)]).astype(np.int32))
        rng4 = np.random.RandomState(3)
        qv = jnp.asarray(rng4.randn(vl_total, 8, 128).astype(np.float32),
                         dtype=jnp.bfloat16)
        kv = jnp.asarray(rng4.randn(vl_total, 8, 128).astype(np.float32),
                         dtype=jnp.bfloat16)
        vv = jnp.asarray(rng4.randn(vl_total, 8, 128).astype(np.float32),
                         dtype=jnp.bfloat16)

        def vlfwd(q, k, v):
            return flash_varlen_attention(q, k, v, cu_vl, cu_vl, 1 / 11.3,
                                          True, self_attn=True,
                                          max_seqlen=vl_max)

        def vlbwd(q, k, v):
            loss = lambda *a: (flash_varlen_attention(
                *a, cu_vl, cu_vl, 1 / 11.3, True, self_attn=True,
                max_seqlen=vl_max).astype(jnp.float32) ** 2).sum()
            return _jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        ms_vf = device_time_ms(vlfwd, (qv, kv, vv), "pvfwd")
        ms_vb = device_time_ms(vlbwd, (qv, kv, vv), "pvbwd")
        fl_vl = sum(2 * 2 * 8 * L * L * 128 / 2 for L in vl_lens)
        detail["moe"] = bench_moe(dev)
        detail["moe_dropless"] = bench_moe_dropless(dev)
        detail["moe_skew_sweep"] = bench_moe_skew(dev)
        from paddle_tpu.ops.flash_varlen import varlen_schedule_stats
        vl_sched = varlen_schedule_stats(
            np.asarray(cu_vl), np.asarray(cu_vl), 8, 128,
            causal=True, self_attn=True, dtype=jnp.bfloat16,
            max_seqlen=vl_max)
        detail["packed_varlen_16seq_16k"] = {
            "fwd_ms": round(ms_vf, 2), "bwd_ms": round(ms_vb, 2),
            # round-5 record before the fused flat-schedule backward
            # landed (rectangular (H, n_k, n_q) dKV + (H, n_q, n_k) dQ
            # grids, dead tiles predicated but still stepped).
            "bwd_ms_r5_rect_baseline": 5.68,
            "varlen_fwd_eff": round(fl_vl / (ms_vf / 1e3)
                                    / peak_flops(dev), 3),
            # bwd recomputes p and runs 5 matmuls vs the fwd's 2:
            # useful-FLOP convention is 2.5x the fwd count.
            "varlen_bwd_eff": round(2.5 * fl_vl / (ms_vb / 1e3)
                                    / peak_flops(dev), 3),
            "schedule": vl_sched,
            # one-seq == dense layout through the SAME kernels: the
            # measured ceiling the 16-seq pack should be judged against
            "ceiling_ablation": varlen_ceiling_ablation(
                dev, long_seq["S16384"]["ms"],
                long_seq["S16384"]["bwd_ms"]),
        }

    if not on_tpu:
        # varlen-efficiency ceiling (ROADMAP VERDICT item 5) at an
        # interpret-affordable S: the dense flash fwd/bwd reference at
        # the SAME shape runs through the same interpret path, so the
        # schedule-overhead ratios are like-for-like even though the
        # absolute ms (and thus the eff_* fields, priced against the
        # nominal CPU peak) carry no hardware meaning off-TPU.
        import jax as _jax
        from paddle_tpu.ops import flash_attention as _fa
        s_vc = 512
        rngvc = np.random.RandomState(6)
        mkd = lambda: jnp.asarray(
            rngvc.randn(8, s_vc, 128).astype(np.float32), jnp.bfloat16)
        qd, kd, vd = mkd(), mkd(), mkd()

        def vcdfwd(q, k, v):
            return _fa._flash_fwd(q, k, v, True, 1 / 11.3, 256, 256)[0]

        def vcdbwd(q, k, v):
            loss = lambda q, k, v: (_fa._flash_attention(
                q, k, v, True, 1 / 11.3, 256, 256)
                .astype(jnp.float32) ** 2).sum()
            return _jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        ms_vcf = device_time_ms(vcdfwd, (qd, kd, vd), "vcdf", reps=1)
        ms_vcb = device_time_ms(vcdbwd, (qd, kd, vd), "vcdb", reps=1)
        vc = varlen_ceiling_ablation(dev, ms_vcf, ms_vcb, S=s_vc)
        vc["note"] = ("interpret mode on CPU at S=512 — the "
                      "schedule_overhead_* ratios vs dense flash are the "
                      "meaningful fields; eff ceilings need the TPU "
                      "round at S=16384")
        detail["varlen_ceiling_ablation"] = vc

    detail["static_analysis"] = _static_analysis_record()

    # The driver records a BOUNDED TAIL of stdout: round 4's single giant
    # JSON line was truncated mid-object and the official record had
    # parsed:null. Emit the full detail FIRST (plus a sidecar file), then
    # a SHORT final summary line — one number per config-ladder rung — so
    # whatever capture window the driver uses, the last line parses.
    full = {
        "metric": "llama_train_mfu",
        "value": round(float(mfu), 4),
        "unit": "MFU",
        "vs_baseline": round(float(mfu) / 0.45, 4),
        "detail": detail,
    }
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_DETAIL.json"), "w") as fh:
            json.dump(full, fh, indent=1)
    except OSError:
        pass
    print(json.dumps(full))
    # ONE mapping from the detail dict to the flat rung record — shared
    # with the regression ratchet (python -m paddle_tpu.observability
    # .regress --check) so the bench and the baseline can never disagree
    # about what a rung is
    from paddle_tpu.observability.regress import rungs_from_bench_detail
    rungs = rungs_from_bench_detail(full)
    rungs.pop("llama_train_mfu", None)  # already the summary line's value
    print(json.dumps({
        "metric": "llama_train_mfu",
        "value": round(float(mfu), 4),
        "unit": "MFU",
        "vs_baseline": round(float(mfu) / 0.45, 4),
        "rungs": rungs,
        "detail_file": "BENCH_DETAIL.json",
    }))


if __name__ == "__main__":
    main()
