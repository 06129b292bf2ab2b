"""Comm–compute overlap bench: overlapped vs blocking on the virtual mesh.

Times the three overlap paths against their blocking twins on the 8-device
virtual CPU mesh (same harness as the multichip dryrun, whose output this
extends — see __graft_entry__.dryrun_multichip):

- TP: ring collective matmuls (parallel/collective_matmul.py) vs the fused
  psum/all-gather islands.
- DP: bucketed grad psum (distributed/sharding_utils.py) vs per-parameter
  psums (the unfused sync the reference's EagerReducer replaces).
- PP: the async-p2p 1F1B schedule (parallel/pipeline.py, overlap_p2p) vs the
  blocking schedule.

Caveat: the host-CPU collective emulation serializes every hop at a
rendezvous, so the latency hiding that motivates the ring/async variants
cannot materialize here — wall-clock on this mesh measures op-count overhead
only. Bucketed DP sync wins on op count and shows a real speedup; the TP
ring and PP async schedules show their overhead (the TPU win comes from
overlap the emulation can't express) and are asserted ≤ blocking only on a
real TPU backend. Run: `python benchmarks/overlap_bench.py`.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEV = 8
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={N_DEV}").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def _timeit(f, *args, reps=5, inner=3):
    jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            o = f(*args)
        jax.block_until_ready(o)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best * 1e3


def bench_tp(cpus, mp=4, t=256, k=1024, out=1024):
    from jax import shard_map
    from paddle_tpu.parallel import collective_matmul as cm

    mesh = Mesh(np.array(cpus[:mp]), ("mp",))
    rng = np.random.RandomState(0)

    def island(kern, in_specs):
        return jax.jit(shard_map(
            lambda a, b: kern(a, b, mp, "mp"), mesh=mesh, in_specs=in_specs,
            out_specs=P(), axis_names=frozenset(["mp"]), check_vma=False))

    x = jax.device_put(jnp.asarray(rng.randn(t, k), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P("mp", None)))
    row_specs = (P(None, "mp"), P("mp", None))
    row_ring = _timeit(island(cm.ring_allreduce_matmul, row_specs), x, w)
    row_blk = _timeit(island(cm.blocking_allreduce_matmul, row_specs), x, w)

    x2 = jnp.asarray(rng.randn(t, k), jnp.float32)
    w2 = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                        NamedSharding(mesh, P(None, "mp")))
    col_specs = (P(), P(None, "mp"))
    col_ring = _timeit(island(cm.ring_allgather_matmul, col_specs), x2, w2)
    col_blk = _timeit(island(cm.blocking_allgather_matmul, col_specs), x2, w2)
    return dict(row_ring=row_ring, row_blk=row_blk,
                col_ring=col_ring, col_blk=col_blk)


def bench_dp(cpus, dp=8, width=256, depth=8, batch=64, cap_mb=0.5):
    """End-to-end dp train step: blocking GSPMD sync (grads reduced at the
    step-end barrier the partitioner schedules) vs the explicit bucketed
    island (per-bucket variadic psums issued as backward produces them)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    mesh = Mesh(np.array(cpus[:dp]).reshape(dp, 1), ("dp", "mp"))
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, 16).astype(np.float32))

    def loss_fn(o, l):
        return paddle.mean((o - l) ** 2)

    res = {}
    for mode in (None, "bucketed"):
        paddle.set_device("cpu")
        paddle.seed(7)
        layers = []
        for _ in range(depth):
            layers += [nn.Linear(width, width), nn.GELU()]
        model = nn.Sequential(*layers, nn.Linear(width, 16))
        opt = AdamW(learning_rate=1e-2,
                    parameters=model.parameters(), weight_decay=0.01)
        step = TrainStep(model, loss_fn, opt, mesh=mesh, batch_spec=P("dp"),
                         grad_sync=mode, grad_bucket_mb=cap_mb)
        loss = step(x, labels=y)  # compile + warm
        res[mode or "blocking"] = _timeit(
            lambda: step(x, labels=y), reps=3, inner=5)
        res[(mode or "blocking") + "_loss"] = float(loss)
        if mode == "bucketed":
            res["n_buckets"] = len(step.grad_buckets)
    return res


def bench_tp_chunks(cpus, mps=(4, 8), chunks=(1, 2, 4)):
    """mp=4/8 chunk sweep of the ring all-reduce matmul (delegates to
    ring_bench.chunk_sweep): blocking vs unchunked ring vs chunked ring,
    with per-hop comm_span bytes snapshotted from the trace counters."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ring_bench.py")
    spec = importlib.util.spec_from_file_location("ring_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {mp: mod.chunk_sweep(cpus, mp=mp, chunks=chunks) for mp in mps}


def bench_stage3_prefetch(cpus, dp=2, sh=4, width=256, depth=6, batch=64,
                          bucket_mb=0.05):
    """End-to-end ZeRO-3 train step: GSPMD's as-consumed param all-gathers
    vs the bucketed one-ahead prefetch (sharding_utils.prefetch_param_
    gathers). Loss must be bit-identical — prefetch is pure data movement."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    mesh = Mesh(np.array(cpus[:dp * sh]).reshape(dp, sh), ("dp", "sharding"))
    rng = np.random.RandomState(5)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, 16).astype(np.float32))

    res = {}
    for pf in (False, True):
        paddle.set_device("cpu")
        paddle.seed(7)
        layers = []
        for _ in range(depth):
            layers += [nn.Linear(width, width), nn.GELU()]
        model = nn.Sequential(*layers, nn.Linear(width, 16))
        opt = AdamW(learning_rate=1e-2, parameters=model.parameters(),
                    weight_decay=0.01)
        model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
        obs.reset_counters()
        step = TrainStep(model,
                         loss_fn=lambda o, l: paddle.mean((o - l) ** 2),
                         optimizer=opt, mesh=mesh,
                         batch_spec=P(("dp", "sharding")),
                         param_prefetch=pf, param_bucket_mb=bucket_mb)
        loss = step(x, labels=y)  # compile + warm (trace fills counters)
        key = "prefetch" if pf else "blocking"
        res[key] = _timeit(lambda: step(x, labels=y), reps=3, inner=5)
        res[key + "_loss"] = float(loss)
        if pf:
            res["n_buckets"] = len(step.param_gather_buckets or [])
            res["bucket_counters"] = {
                k: v for k, v in obs.counters().items()
                if k.startswith("param_gather.")}
    return res


def bench_pp(cpus, S=2, M=8, H=256):
    from jax import shard_map
    from paddle_tpu.parallel.pipeline import (last_stage_value, microbatch,
                                              pipeline_apply,
                                              stack_stage_params)

    mesh = Mesh(np.array(cpus[:S]), ("pp",))
    rng = np.random.RandomState(2)
    stacked = stack_stage_params(
        [{"w": jnp.asarray(rng.randn(H, H), jnp.float32) * 0.1}
         for _ in range(S)])
    x_mb = microbatch(jnp.asarray(rng.randn(M * 4, H), jnp.float32), M)

    def build(ovl):
        pipe = pipeline_apply(lambda p, h: jnp.tanh(h @ p["w"]), S, M, "pp",
                              remat=False, overlap_p2p=ovl)

        def island(params, xm):
            return last_stage_value(jnp.sum(pipe(params, xm) ** 2), S, "pp")

        return jax.jit(shard_map(
            island, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
            axis_names=frozenset(["pp"]), check_vma=False))

    t_blk = _timeit(build(False), stacked, x_mb)
    t_ovl = _timeit(build(True), stacked, x_mb)
    return dict(blocking=t_blk, overlapped=t_ovl)


def bench_telemetry(cpus, dp=8, width=256, depth=4, batch=64, cap_mb=0.25,
                    steps=8, logdir=None):
    """Telemetry acceptance run: a bucketed-dp train step with telemetry on
    emits a JSONL step log carrying step_time_ms / tokens_per_sec / MFU plus
    a summary record with the per-bucket grad-sync bytes and MoE routing
    stats (drops / load imbalance from a skewed router)."""
    import tempfile

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed import sharding_utils
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import moe

    logdir = logdir or tempfile.mkdtemp(prefix="paddle_tpu_telemetry_")
    obs.reset_counters()
    mesh = Mesh(np.array(cpus[:dp]).reshape(dp, 1), ("dp", "mp"))
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, 16).astype(np.float32))

    paddle.set_device("cpu")
    paddle.seed(7)
    layers = []
    for _ in range(depth):
        layers += [nn.Linear(width, width), nn.GELU()]
    model = nn.Sequential(*layers, nn.Linear(width, 16))
    opt = AdamW(learning_rate=1e-2, parameters=model.parameters(),
                weight_decay=0.01)
    step = TrainStep(model, loss_fn=lambda o, l: paddle.mean((o - l) ** 2),
                     optimizer=opt, mesh=mesh, batch_spec=P("dp"),
                     grad_sync="bucketed", grad_bucket_mb=cap_mb,
                     telemetry=True, telemetry_dir=logdir)
    for _ in range(steps):
        step(x, labels=y)

    # MoE routing stats from a deliberately skewed router (expert 0 favored
    # beyond capacity -> real drops and imbalance), on the same mesh
    T, D, E, k = 256, 32, 4, 2
    tok = jnp.asarray(rng.randn(T, D), jnp.float32)
    logits = jnp.asarray(rng.randn(T, E), jnp.float32) + \
        jnp.array([4.0] + [0.0] * (E - 1), jnp.float32)
    ew1 = jnp.asarray(rng.randn(E, D, 64), jnp.float32) * 0.02
    ew2 = jnp.asarray(rng.randn(E, 64, D), jnp.float32) * 0.02

    def expert_fn(params, t_):
        a, b = params
        return jax.nn.gelu(t_ @ a) @ b

    _, _, moe_stats = jax.jit(lambda t_, l_: moe.moe_dispatch_combine(
        t_, l_, expert_fn, (ew1, ew2), E, k=k, strict_capacity=True,
        return_stats=True))(tok, logits)

    m = step.telemetry
    shapes = {kk: (tuple(step.params[kk].shape), step.params[kk].dtype.itemsize)
              for kk in step.trainable_keys}
    bucket_sizes = sharding_utils.bucket_bytes(shapes, step.grad_buckets)
    summary_rec = dict(m.summary())
    summary_rec["record"] = "summary"
    summary_rec["grad_sync_bucket_bytes"] = bucket_sizes
    summary_rec.update({kk: float(v) for kk, v in moe_stats.items()})
    for e in m._exporters:
        e.write(summary_rec)
    m.close()
    obs.set_active(None)

    path = os.path.join(
        logdir, f"steps_rank{obs.process_rank():03d}.jsonl")
    records = obs.load_jsonl(path)
    step_recs = [r for r in records if r.get("record") != "summary"]
    timed = [r for r in step_recs if r.get("step_time_ms")]
    return dict(logdir=logdir, path=path, n_records=len(records),
                n_steps=len(step_recs),
                step_time_ms=(min(r["step_time_ms"] for r in timed)
                              if timed else None),
                tokens_per_sec=(max(r["tokens_per_sec"] for r in timed
                                    if r.get("tokens_per_sec")) or None
                                if timed else None),
                mfu=next((r["mfu"] for r in reversed(step_recs)
                          if r.get("mfu") is not None), None),
                grad_sync_bucket_bytes=bucket_sizes,
                moe_dropped_tokens=float(moe_stats["moe_dropped_tokens"]),
                moe_load_imbalance=float(moe_stats["moe_load_imbalance"]))


def bench_overhead(cpus, dp=8, width=256, depth=4, batch=64, cap_mb=0.25):
    """Telemetry-on vs telemetry-off step time on the CPU mesh, plus the
    serve-side twin: request tracing + SLO histograms + flight recorder on
    vs off in the engine dryrun — the acceptance bound is <2% overhead on
    both (the collectors are interval timing + in-memory appends; nothing
    touches the device, and tokens must be bit-identical)."""
    import tempfile

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import observability as obs
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    mesh = Mesh(np.array(cpus[:dp]).reshape(dp, 1), ("dp", "mp"))
    rng = np.random.RandomState(4)
    x = paddle.to_tensor(rng.randn(batch, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, 16).astype(np.float32))

    res = {}
    for on in (False, True):
        paddle.set_device("cpu")
        paddle.seed(7)
        layers = []
        for _ in range(depth):
            layers += [nn.Linear(width, width), nn.GELU()]
        model = nn.Sequential(*layers, nn.Linear(width, 16))
        opt = AdamW(learning_rate=1e-2, parameters=model.parameters(),
                    weight_decay=0.01)
        step = TrainStep(model,
                         loss_fn=lambda o, l: paddle.mean((o - l) ** 2),
                         optimizer=opt, mesh=mesh, batch_spec=P("dp"),
                         grad_sync="bucketed", grad_bucket_mb=cap_mb,
                         telemetry=on,
                         telemetry_dir=(tempfile.mkdtemp() if on else None))
        step(x, labels=y)  # compile + warm
        res["on" if on else "off"] = _timeit(
            lambda: step(x, labels=y), reps=3, inner=10)
        if on and step.telemetry is not None:
            step.telemetry.close()
            obs.set_active(None)
    res["overhead_pct"] = (res["on"] / res["off"] - 1.0) * 100.0
    res.update(bench_serve_overhead())
    return res


class _TimedProxy:
    """Attribute proxy that wall-times every method call on the target.

    The timing clamp itself (two ``perf_counter`` reads + an attribute
    hop per call) is billed to the target, so the attributed total is an
    UPPER bound on what the unwrapped instrumentation costs."""

    def __init__(self, target, counter):
        self._target = target
        self._counter = counter  # single-element list, shared across proxies

    def __getattr__(self, name):
        attr = getattr(self._target, name)
        if not callable(attr):
            return attr
        counter = self._counter

        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return attr(*args, **kwargs)
            finally:
                counter[0] += time.perf_counter() - t0
        # cache the bound wrapper so repeat calls skip __getattr__ —
        # the clamp should time the instrumentation, not itself
        object.__setattr__(self, name, timed)
        return timed


def bench_serve_overhead(reps=3):
    """Request-tracing + histogram + flight-recorder overhead in the serve
    dryrun. Two measurements:

    - **attributed** (the <2% gate): wall time spent inside observability
      calls during a traced run, clamped per call via ``_TimedProxy``
      (conservative — the clamp bills its own cost to the layers), as a
      share of the run's wall. Stable to well under a percent even on a
      noisy 1-vCPU host because it sums µs-scale intervals instead of
      differencing two ~100ms walls.
    - **A/B tokens/s** (reported for reference): traced vs untraced runs
      of the same deterministic arrival trace. Identical schedules, so
      generated tokens must match bit for bit; on a shared host the
      ratio itself carries several percent of scheduler noise.
    """
    import tempfile

    from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
    from paddle_tpu.models.llama import init_llama_params, llama_tiny
    from paddle_tpu.ops import _common

    # two layers, hidden 128: still a toy, but the per-iteration device
    # work is no longer degenerate next to the fixed ~25us of host
    # instrumentation (the serve dryrun's 1-layer hidden-64 config exists
    # to make the FUNCTIONAL checks fast, not to proxy a real step time)
    cfg = llama_tiny(vocab=96, hidden=128, layers=2, heads=4, kv_heads=2,
                     seq=256)
    params = init_llama_params(cfg, seed=3)
    serve = ServeConfig(block_size=128, num_blocks=17, max_batch=4,
                        prefill_chunk=32, max_seq_len=256)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist()
               for n in (7, 40, 130, 25, 60, 90)]

    def one(on, attribute=False):
        # "on" also enables the PR-14 robustness layers (append-only
        # journal + admission control) so the attributed share covers
        # the FULL instrumented surface, not just observability
        jdir = tempfile.mkdtemp() if on else None
        eng = InferenceEngine(
            params, cfg, serve, trace_requests=on, flight_recorder=on,
            journal=(os.path.join(jdir, "engine.jsonl") if on else None))
        counter = [0.0]
        if attribute:
            eng.tracer = _TimedProxy(eng.tracer, counter)
            eng.recorder = _TimedProxy(eng.recorder, counter)
            eng.slo = {k: _TimedProxy(h, counter)
                       for k, h in eng.slo.items()}
            eng._journal = _TimedProxy(eng._journal, counter)
            eng.admission = _TimedProxy(eng.admission, counter)
        reqs = [Request(p, max_new_tokens=48, arrival=float(i))
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        st = eng.run(reqs, deterministic=True)
        wall = time.perf_counter() - t0
        toks = {s.req.request_id: list(s.generated) for s in eng.finished}
        return st["generated_tokens"] / wall, toks, counter[0] / wall

    prev = _common._FORCE_INTERPRET
    _common.set_interpret(True)
    try:
        one(False)  # compile + warm outside the timed reps
        attributed, offs, ons = [], [], []
        toks_off = toks_on = None
        for _ in range(reps):
            tps, toks_off, _ = one(False)
            offs.append(tps)
            tps, toks_on, _ = one(True)
            ons.append(tps)
            _, _, share = one(True, attribute=True)
            attributed.append(share)
    finally:
        _common.set_interpret(prev)
    return dict(serve_off_tps=max(offs), serve_on_tps=max(ons),
                serve_overhead_pct=max(attributed) * 100.0,
                serve_ab_overhead_pct=(max(offs) / max(ons) - 1.0) * 100.0,
                serve_tokens_identical=toks_on == toks_off)


def run(cpus=None, prefix="overlap_bench"):
    if cpus is None:
        cpus = jax.devices("cpu")
    assert len(cpus) >= N_DEV, (len(cpus), N_DEV)
    tp = bench_tp(cpus)
    chunk = bench_tp_chunks(cpus)
    dp = bench_dp(cpus)
    s3 = bench_stage3_prefetch(cpus)
    pp = bench_pp(cpus)
    tel = bench_telemetry(cpus)
    ovh = bench_overhead(cpus)
    print(f"{prefix}({N_DEV}): tp mp=4 row ring {tp['row_ring']:.1f}ms vs "
          f"fused {tp['row_blk']:.1f}ms, col ring {tp['col_ring']:.1f}ms vs "
          f"fused {tp['col_blk']:.1f}ms (virtual-cpu serializes hops; "
          f"overlap needs real ICI)")
    verdict = "OK" if dp["bucketed"] <= dp["blocking"] else "SLOWER"
    print(f"{prefix}({N_DEV}): dp=8 e2e step: bucketed-overlap "
          f"({dp['n_buckets']} fused psums) {dp['bucketed']:.1f}ms vs "
          f"blocking GSPMD {dp['blocking']:.1f}ms, loss "
          f"{dp['bucketed_loss']:.6f}=={dp['blocking_loss']:.6f} "
          f"overlapped<=blocking: {verdict}")
    print(f"{prefix}({N_DEV}): pp=2 1F1B async-p2p {pp['overlapped']:.1f}ms "
          f"vs blocking {pp['blocking']:.1f}ms (+1 skew tick on emulation; "
          f"transfer hides behind compute on real ICI)")
    mfu = tel["mfu"]
    print(f"{prefix}({N_DEV}): telemetry JSONL {tel['path']}: "
          f"{tel['n_records']} records, step best "
          f"{tel['step_time_ms']:.2f}ms, {tel['tokens_per_sec']:.0f} tok/s, "
          f"mfu {mfu:.2e}" + (" (cpu-nominal peak)" if mfu else "") +
          f", buckets {tel['grad_sync_bucket_bytes']} B, moe dropped "
          f"{tel['moe_dropped_tokens']:.0f} imbalance "
          f"{tel['moe_load_imbalance']:.2f}")
    verdict2 = "OK" if ovh["overhead_pct"] < 2.0 else "OVER"
    print(f"{prefix}({N_DEV}): telemetry overhead: on "
          f"{ovh['on']:.2f}ms vs off {ovh['off']:.2f}ms = "
          f"{ovh['overhead_pct']:+.2f}% (<2%: {verdict2})")
    v_tr = "OK" if ovh["serve_overhead_pct"] < 2.0 else "OVER"
    print(f"{prefix}({N_DEV}): serve tracing overhead: traced "
          f"{ovh['serve_on_tps']:.1f} tok/s vs untraced "
          f"{ovh['serve_off_tps']:.1f} tok/s = "
          f"{ovh['serve_overhead_pct']:+.2f}% (<2%: {v_tr}), tokens "
          f"identical: {ovh['serve_tokens_identical']}")
    for mp, sweep in chunk.items():
        parts = []
        for nc, rec in sweep["sweep"].items():
            bw = "bitwise" if rec["bitwise_vs_unchunked"] else "DIVERGED"
            parts.append(f"c{nc} {rec['ms']:.1f}ms[{bw}]")
        best = min(r["ms"] for r in sweep["sweep"].values())
        v = ("OK" if best <= sweep["blocking_ms"] else
             "SLOWER (virtual-cpu serializes hops; chunking only adds ops "
             "here — the overlap win needs real ICI)")
        print(f"{prefix}({N_DEV}): tp mp={mp} chunk sweep: blocking "
              f"{sweep['blocking_ms']:.1f}ms vs ring " + ", ".join(parts) +
              f" chunked<=blocking: {v}")
    v3 = ("OK" if s3["prefetch"] <= s3["blocking"] else
          "SLOWER (gathers already as-consumed on the emulated mesh)")
    print(f"{prefix}({N_DEV}): zero-3 sharding=4 step: bucketed prefetch "
          f"({s3['n_buckets']} param-gather buckets) {s3['prefetch']:.1f}ms "
          f"vs as-consumed {s3['blocking']:.1f}ms, loss "
          f"{s3['prefetch_loss']:.6f}=={s3['blocking_loss']:.6f} "
          f"(bitwise: {s3['prefetch_loss'] == s3['blocking_loss']}) "
          f"prefetch<=blocking: {v3}")
    # persist the chunk-sweep + prefetch attribution next to the telemetry
    # step log: one JSONL record carrying the per-hop and per-bucket
    # comm_span bytes the dryrun archives
    from paddle_tpu import observability as obs
    rec_path = os.path.join(tel["logdir"], "overlap_rings.jsonl")
    writer = obs.JsonlWriter(rec_path)
    writer.write(dict(
        record="ring_chunk_sweep",
        per_mp={str(mp): dict(
            blocking_ms=sweep["blocking_ms"],
            sweep={str(nc): dict(ms=rec["ms"],
                                 bitwise=rec["bitwise_vs_unchunked"],
                                 hop_counters=rec["hop_counters"])
                   for nc, rec in sweep["sweep"].items()})
            for mp, sweep in chunk.items()},
        stage3_prefetch=dict(
            prefetch_ms=s3["prefetch"], blocking_ms=s3["blocking"],
            loss_bitwise=s3["prefetch_loss"] == s3["blocking_loss"],
            n_buckets=s3["n_buckets"],
            bucket_counters=s3["bucket_counters"])))
    writer.close()
    n_ring_recs = len(obs.load_jsonl(rec_path))
    print(f"{prefix}({N_DEV}): ring/prefetch attribution JSONL {rec_path}: "
          f"{n_ring_recs} record(s)")
    return dict(tp=tp, tp_chunks=chunk, dp=dp, stage3=s3, pp=pp,
                telemetry=tel, overhead=ovh)


if __name__ == "__main__":
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    run()
