"""Ring-attention per-step micro-bench: Pallas flash block vs fp32 einsum.

Ring wall-time is n steps of per-block compute (rotation overlaps); a single
chip can't host the 4-device ring, so this measures the per-step block
compute both ways at long-context shard sizes (>= 8k per shard), fwd and
fwd+bwd. Run on the TPU: `python benchmarks/ring_bench.py`.
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import peak_flops
from paddle_tpu.ops.flash_attention import flash_block_fwd, flash_block_bwd
from paddle_tpu.parallel.ring_attention import _merge_partials

N = 8


def bench(f, *args, n=5):
    o = f(*args)
    jax.device_get(jax.tree_util.tree_leaves(o)[0].ravel()[0])
    t0 = time.perf_counter()
    for _ in range(n):
        o = f(*args)
    jax.device_get(jax.tree_util.tree_leaves(o)[0].ravel()[0])
    return (time.perf_counter() - t0) / n / N


def einsum_block_step(q, k_blk, v_blk, o, m, l, scale):
    """One ring step of the fp32-einsum path (pre-r2 implementation)."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k_blk.astype(jnp.float32)) * scale
    blk_max = jnp.max(s, axis=-1)
    new_m = jnp.maximum(m, blk_max)
    alpha = jnp.exp(m - new_m)
    p = jnp.exp(s - new_m[..., None])
    new_l = l * alpha + p.sum(-1)
    new_o = o * alpha[..., None] + jnp.einsum(
        "bqk,bkd->bqd", p, v_blk.astype(jnp.float32))
    return new_o, new_m, new_l


def flash_block_step(q, k_blk, v_blk, o, lse, scale):
    """One ring step of the flash path: Pallas block kernel + lse merge."""
    o_blk, lse_blk = flash_block_fwd(q, k_blk, v_blk, causal=False,
                                     scale=scale)
    return _merge_partials(o, lse, o_blk, lse_blk)


def chunk_sweep(cpus=None, mp=4, t=512, k=512, out=512, chunks=(1, 2, 4),
                reps=3, inner=3):
    """Chunked ring collective-matmul sweep at mp>2 (importable; the n=8
    multichip dryrun calls this through overlap_bench for mp=4 and mp=8).

    For each sub-tile count, times the row-parallel all-reduce ring against
    the fused-psum blocking twin and snapshots the per-hop comm_span trace
    counters (tp_ring_allreduce.hop / .gather_hop calls and bytes), which is
    how the chunking shows up in the step log: same total bytes, n_chunks x
    the collective-permute count at 1/n_chunks the payload each.
    """
    import functools

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu import observability as obs
    from jax import shard_map
    from paddle_tpu.parallel import collective_matmul as cm

    if cpus is None:
        cpus = jax.devices("cpu")
    mesh = Mesh(np.array(cpus[:mp]), ("mp",))
    rng = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(rng.randn(t, k), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P("mp", None)))
    specs = (P(None, "mp"), P("mp", None))

    def island(kern, **kw):
        return jax.jit(shard_map(
            functools.partial(kern, n=mp, axis_name="mp", **kw), mesh=mesh,
            in_specs=specs, out_specs=P(),
            axis_names=frozenset(["mp"]), check_vma=False))

    def timeit(f):
        jax.block_until_ready(f(x, w))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(inner):
                o = f(x, w)
            jax.block_until_ready(o)
            best = min(best, (time.perf_counter() - t0) / inner)
        return best * 1e3

    res = {"mp": mp, "blocking_ms": timeit(island(cm.blocking_allreduce_matmul)),
           "sweep": {}}
    ref = None
    for nc in chunks:
        if (t // mp) % nc:
            continue
        obs.reset_counters()
        f = island(cm.ring_allreduce_matmul, nchunks=nc)
        ms = timeit(f)
        snap = {name: v for name, v in obs.counters().items()
                if name.startswith("tp_ring_allreduce.")}
        out_val = f(x, w)
        if ref is None:
            ref = out_val
        res["sweep"][nc] = dict(
            ms=ms, bitwise_vs_unchunked=bool((out_val == ref).all()),
            hop_counters=snap)
    return res


def main():
    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)}")
    # NOTE: per-shard S is VMEM-bounded (~12k at D=128) because the fwd
    # kernel stages the full KV block in VMEM; ring shards the sequence so
    # 8k/shard x sep=4 already covers 32k contexts.
    for (bh, s, d) in [(8, 8192, 128), (8, 4096, 128)]:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        do = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        scale = 1.0 / d ** 0.5

        @jax.jit
        def einsum_N(q, k, v):
            o = jnp.zeros((bh, s, d), jnp.float32)
            m = jnp.full((bh, s), -jnp.inf, jnp.float32)
            l = jnp.zeros((bh, s), jnp.float32)

            def body(i, carry):
                return einsum_block_step(q, k, v, *carry, scale)
            return lax.fori_loop(0, N, body, (o, m, l))

        @jax.jit
        def flash_N(q, k, v):
            o0, lse0 = flash_block_fwd(q, k, v, causal=False, scale=scale)

            def body(i, carry):
                return flash_block_step(q, k, v, *carry, scale)
            return lax.fori_loop(0, N - 1, body,
                                 (o0.astype(jnp.float32), lse0))

        @jax.jit
        def flash_bwd_N(q, k, v, do):
            o, lse = flash_block_fwd(q, k, v, causal=False, scale=scale)
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1)

            def body(i, carry):
                dq, dk, dv = flash_block_bwd(q, k, v, do, lse, delta,
                                             causal=False, scale=scale)
                return (carry[0] + dq.astype(jnp.float32),
                        carry[1] + dk.astype(jnp.float32),
                        carry[2] + dv.astype(jnp.float32))
            z = jnp.zeros((bh, s, d), jnp.float32)
            return lax.fori_loop(0, N, body, (z, z, z))

        peak = peak_flops(dev)
        t_e = bench(einsum_N, q, k, v)
        t_f = bench(flash_N, q, k, v)
        t_b = bench(flash_bwd_N, q, k, v, do)
        fl = 2 * 2 * s * s * d * bh
        print(f"BH{bh} S{s} D{d}: einsum {t_e*1e3:.2f}ms | "
              f"flash {t_f*1e3:.2f}ms ({t_e/t_f:.2f}x, "
              f"eff={fl/t_f/peak:.3f}) | blk bwd {t_b*1e3:.2f}ms")


if __name__ == "__main__":
    main()
