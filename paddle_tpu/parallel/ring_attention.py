"""Context parallelism over the 'sep' mesh axis: ring attention and Ulysses.

Ref: SURVEY.md §5.7 — the reference provides sep-axis process groups
(fleet/base/topology.py) and varlen flash-attn; ring/Ulysses live downstream
(PaddleNLP RingFlashAttention). Here both are first-class, TPU-native:

- ring_attention: Q stays local to its sequence shard; K/V blocks rotate
  around the 'sep' ring via lax.ppermute (ICI neighbor exchange). Each ring
  step runs the Pallas flash kernel (ops/flash_attention.py) on the local
  (Q, K_block) pair — bf16 MXU matmuls, f32 accumulators, the [S, S] score
  matrix never materializes — and merges the per-block (o, lse) partials
  with the standard log-sum-exp combine. Causal masking is BLOCK-level:
  blocks entirely above the diagonal are skipped via lax.cond (no FLOPs,
  just the rotate), the diagonal block runs the causal kernel, blocks below
  run unmasked. Backward is a second ring pass reusing the FA2 per-block
  kernels with global statistics; dK/dV accumulators travel with their K/V
  block so each rotation's compute lands on the right shard.
- ulysses_attention: all-to-all over 'sep' redistributes heads<->sequence so
  each device runs full-sequence attention on a head slice, then a reverse
  all-to-all. Cheaper at moderate S, ring wins at very long S.

Both are called INSIDE shard_map with q/k/v already sequence-sharded:
q, k, v: [B, S_local, H, D].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size as _axis_size

from ..ops.flash_attention import flash_block_fwd, flash_block_bwd


# ---------------------------------------------------------------------------
# flash ring (default path)
# ---------------------------------------------------------------------------

def _merge_partials(o, lse, o_blk, lse_blk):
    """Log-sum-exp merge of two normalized attention partials.
    o: [BH, S, D] f32 running; lse: [BH, S] f32; o_blk may be bf16."""
    m = jnp.maximum(lse, lse_blk)
    w = jnp.exp(lse - m)
    w_blk = jnp.exp(lse_blk - m)
    den = w + w_blk
    o_new = (o * (w / den)[..., None]
             + o_blk.astype(jnp.float32) * (w_blk / den)[..., None])
    return o_new, m + jnp.log(den)


def _ring_fwd_impl(q, k, v, axis_name, causal, scale):
    """q/k/v: [BH, S_local, D]. Returns (o [BH, S_local, D], lse [BH, S])."""
    n = _axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # diagonal block first: KV is local, causal masking applies as-is
    # (q and k share the same global offset, which cancels in row>=col).
    o0, lse0 = flash_block_fwd(q, k, v, causal=causal, scale=scale)

    def step(carry, i):
        o, lse, k_blk, v_blk = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (my - i) % n  # whose chunk arrived

        def compute(o, lse):
            o_blk, lse_blk = flash_block_fwd(q, k_blk, v_blk, causal=False,
                                             scale=scale)
            return _merge_partials(o, lse, o_blk, lse_blk)

        if causal:
            # src > my: block entirely above the diagonal — skip the FLOPs
            # (lax.cond takes one branch at runtime inside shard_map manual
            # regions, so skipped ranks genuinely idle through this step).
            o, lse = lax.cond(src < my, compute, lambda o, l: (o, l), o, lse)
        else:
            o, lse = compute(o, lse)
        return (o, lse, k_blk, v_blk), None

    if n > 1:
        (o, lse, _, _), _ = lax.scan(
            step, (o0.astype(jnp.float32), lse0, k, v), jnp.arange(1, n))
    else:
        o, lse = o0.astype(jnp.float32), lse0
    return o.astype(q.dtype), lse


def _ring_bwd_impl(q, k, v, o, lse, do, axis_name, causal, scale):
    """Second ring pass: per-block FA2 backward with GLOBAL lse/delta.
    dK/dV accumulators rotate together with their K/V block, so after the
    final rotation each shard holds the fully-accumulated grads for its own
    chunk."""
    n = _axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    dq0, dk0, dv0 = flash_block_bwd(q, k, v, do, lse, delta, causal=causal,
                                    scale=scale)

    def step(carry, i):
        dq, dk_acc, dv_acc, k_blk, v_blk = carry
        # rotate KV and its grad accumulator as one unit
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        src = (my - i) % n

        def compute(dq, dk_acc, dv_acc):
            dqb, dkb, dvb = flash_block_bwd(q, k_blk, v_blk, do, lse, delta,
                                            causal=False, scale=scale)
            return (dq + dqb.astype(dq.dtype), dk_acc + dkb.astype(dq.dtype),
                    dv_acc + dvb.astype(dq.dtype))

        if causal:
            dq, dk_acc, dv_acc = lax.cond(
                src < my, compute, lambda a, b, c: (a, b, c),
                dq, dk_acc, dv_acc)
        else:
            dq, dk_acc, dv_acc = compute(dq, dk_acc, dv_acc)
        return (dq, dk_acc, dv_acc, k_blk, v_blk), None

    f32 = jnp.float32
    if n > 1:
        (dq, dk_acc, dv_acc, _, _), _ = lax.scan(
            step,
            (dq0.astype(f32), dk0.astype(f32), dv0.astype(f32), k, v),
            jnp.arange(1, n))
        # accumulators sit one hop short of home — final rotation
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
    else:
        dq, dk_acc, dv_acc = dq0.astype(f32), dk0.astype(f32), dv0.astype(f32)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name, causal, scale):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return o


def _ring_flash_fwd(q, k, v, axis_name, causal, scale):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, causal, scale, res, do):
    q, k, v, o, lse = res
    return _ring_bwd_impl(q, k, v, o, lse, do, axis_name, causal, scale)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# ---------------------------------------------------------------------------
# XLA einsum ring (fallback / comparison path)
# ---------------------------------------------------------------------------

def _ring_attention_xla(q, k, v, axis_name, causal, scale):
    """fp32-einsum flash-style ring: per-block scores materialize in HBM.
    Kept as the non-Pallas fallback and the micro-bench comparison point."""
    B, Sq, H, D = q.shape
    n = _axis_size(axis_name)
    my = lax.axis_index(axis_name)

    o = jnp.zeros((B, H, Sq, D), jnp.float32)
    m = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)  # running max
    l = jnp.zeros((B, H, Sq), jnp.float32)           # running denom

    perm = [(i, (i + 1) % n) for i in range(n)]
    pos_q = my * Sq + jnp.arange(Sq)

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        # which chunk is this k block from? it started at (my - i) mod n
        src = (my - i) % n
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k_blk.astype(jnp.float32)) * scale
        if causal:
            pos_k = src * Sq + jnp.arange(k_blk.shape[1])
            mask = (pos_q[:, None] >= pos_k[None, :])[None, None]
            s = jnp.where(mask, s, jnp.float32(-1e30))
        blk_max = jnp.max(s, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m[..., None])
        new_l = l * alpha + p.sum(-1)
        new_o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (new_o, new_m, new_l, k_next, v_next), None

    (o, m, l, _, _), _ = lax.scan(step, (o, m, l, k, v), jnp.arange(n))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                   scale=None, impl: str = "flash"):
    """Ring attention over `axis_name`. Device i holds sequence chunk i of
    Q, K, V; returns the attention output [B, S_local, H, D].

    impl: 'flash' (Pallas per-block kernels, default) or 'xla' (fp32 einsum
    fallback). Both are differentiable: flash via a ring-aware custom_vjp,
    xla through jax autodiff of the scan."""
    if impl not in ("flash", "xla"):
        raise ValueError(f"impl must be 'flash' or 'xla', got {impl!r}")
    B, Sq, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    # GQA: repeat kv heads to match q heads (the repeat's transpose — a sum
    # over the repeats — is handled by autodiff outside the custom_vjp)
    if k.shape[2] != H:
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    if impl == "flash" and (Sq % 128 or k.shape[1] % 128):
        impl = "xla"  # Pallas backward needs 128-aligned shard lengths
    if impl == "xla":
        return _ring_attention_xla(q, k, v, axis_name, causal, scale)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    o = _ring_flash(to_bh(q), to_bh(k), to_bh(v), axis_name, causal,
                    float(scale))
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


# r7: the ulysses strategy lives in its own module now (custom_vjp flash
# path whose backward all_to_alls carry comm_span bytes, GQA kv-head
# routing with a ring fallback, strategy env/config validation);
# re-exported here so existing `from .ring_attention import
# ulysses_attention` call sites keep working.
from .ulysses_attention import ulysses_attention  # noqa: E402,F401
