"""Scaled dot-product attention (ref: paddle/phi/kernels/gpu/flash_attn_kernel.cu
+ python/paddle/nn/functional/flash_attention.py).

Layout matches the reference: [batch, seq, num_heads, head_dim]. On TPU the op
routes to the Pallas flash-attention kernel (ops/flash_attention.py); elsewhere
(or when FLAGS_use_pallas_kernels=0) it falls back to the XLA softmax path with
fp32 accumulation.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ...framework import flags
from ...tensor.tensor import Tensor, _run_op


def _xla_sdpa(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None):
    # [B, S, H, D] -> compute in [B, H, S, D]
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2)
    hq, hk = qh.shape[1], kh.shape[1]
    if hk != hq:  # GQA: repeat kv heads
        rep = hq // hk
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(jnp.float32(d))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        # ADDITIVE mask, not select: a broadcasted-pred select over
        # sharded logits made GSPMD replicate the operand ("Involuntary
        # full rematerialization" on the select_n in the r4 multichip
        # dryrun); addition partitions elementwise with no resharding
        neg = jnp.triu(jnp.full((sq, sk), -1e30, jnp.float32),
                       k=sk - sq + 1)
        logits = logits + neg
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -1e30)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vh.dtype), vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _use_pallas(query) -> bool:
    if not flags.get_flag("use_pallas_kernels"):
        return False
    data = query._data if isinstance(query, Tensor) else query
    if isinstance(data, jax.core.Tracer) or not isinstance(data, jax.Array):
        # no concrete device to ask: the kernels' own predicate decides
        # (compiled unless the computation's devices are CPU)
        from ...ops._common import interpret_mode
        return not interpret_mode()
    return next(iter(data.devices())).platform != "cpu"


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    if _use_pallas(query) and attn_mask is None and dropout_p == 0.0:
        from ...ops.flash_attention import flash_attention_bshd
        def f(q, k, v):
            return flash_attention_bshd(q, k, v, causal=is_causal, scale=scale)
        return _run_op("flash_attention", f, (query, key, value), {})
    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    def f(q, k, v, *m):
        return _xla_sdpa(q, k, v, m[0] if m else None, dropout_p, is_causal, scale)
    return _run_op("sdpa", f, args, {})


@contextlib.contextmanager
def sdp_kernel(enable_flash=True, enable_math=True, enable_mem_efficient=True):
    prev = flags.get_flag("use_pallas_kernels")
    flags.set_flags({"use_pallas_kernels": enable_flash})
    try:
        yield
    finally:
        flags.set_flags({"use_pallas_kernels": prev})


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, name=None):
    """paddle.nn.functional.flash_attention parity wrapper."""
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal)
    if return_softmax:
        return out, None
    return out, None


def _varlen_attention(q, k, v, cu_q, cu_k, max_q, max_k, scale, causal):
    """Packed varlen attention core: q [total_q, H, D], k/v [total_k, Hkv, D],
    cu_* are [B+1] cumulative sequence offsets. Returns [total_q, H, D].

    TPU shape strategy: scatter the packed tokens into a padded [B, max, H, D]
    batch (static shapes for XLA), run masked attention with fp32 logits, and
    gather the valid rows back to the packed layout. Fully-padded rows never
    reach the output gather, so no gradient flows through them. O(B*max_q*
    max_k) logits — the flash-kernel segment-mask route is the upgrade path
    for long packed batches."""
    B = cu_q.shape[0] - 1
    lens_q = cu_q[1:] - cu_q[:-1]
    lens_k = cu_k[1:] - cu_k[:-1]
    iq = jnp.arange(max_q)
    ik = jnp.arange(max_k)
    idx_q = jnp.clip(cu_q[:-1, None] + iq[None], 0, q.shape[0] - 1)
    idx_k = jnp.clip(cu_k[:-1, None] + ik[None], 0, k.shape[0] - 1)
    valid_q = iq[None] < lens_q[:, None]                      # [B, max_q]
    valid_k = ik[None] < lens_k[:, None]                      # [B, max_k]
    qp = jnp.take(q, idx_q, axis=0)                           # [B,max_q,H,D]
    kp = jnp.take(k, idx_k, axis=0)
    vp = jnp.take(v, idx_k, axis=0)
    mask = valid_q[:, None, :, None] & valid_k[:, None, None, :]
    if causal:
        # per-sequence top-left causal (reference semantics): query position
        # i within its sequence attends key positions <= i
        mask = mask & (iq[:, None] >= ik[None, :])[None, None]
    out = _xla_sdpa(qp, kp, vp, attn_mask=mask, scale=scale)  # [B,max_q,H,D]
    t = jnp.arange(q.shape[0])
    seg = jnp.searchsorted(cu_q, t, side="right") - 1
    src = seg * max_q + (t - cu_q[seg])
    flat = out.reshape(B * max_q, *out.shape[2:])
    return jnp.take(flat, src, axis=0).astype(q.dtype)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen (unpadded) attention over packed sequences (ref:
    python/paddle/nn/functional/flash_attention.py flash_attn_unpadded).

    query: [total_q, num_heads, head_dim] — all sequences concatenated;
    cu_seqlens_q/k: [batch+1] int32 cumulative offsets (cu[0]=0,
    cu[-1]=total). Returns (out [total_q, H, D], softmax=None).

    On TPU (and within the segment-code limits) this runs the Pallas
    streaming flash kernels directly on the PACKED layout — O(total * D)
    memory, no [B, max_q, max_k] logits; elsewhere it falls back to the
    padded-batch XLA path (_varlen_attention)."""
    max_q, max_k = int(max_seqlen_q), int(max_seqlen_k)

    n_seqs = cu_seqlens_q.shape[0] - 1
    use_kernel = (_use_pallas(query) and dropout == 0.0
                  and n_seqs < 1024 and max(max_q, max_k) < (1 << 20))
    if use_kernel:
        from ...ops.flash_varlen import flash_varlen_attention
        self_attn = cu_seqlens_q is cu_seqlens_k

        def fk(q, k, v, cq, ck):
            s = (1.0 / float(q.shape[-1]) ** 0.5) if scale is None else scale
            return flash_varlen_attention(q, k, v, cq, ck, s, causal,
                                          self_attn=self_attn,
                                          max_seqlen=max(max_q, max_k))

        out = _run_op("flash_attn_unpadded", fk,
                      (query, key, value, cu_seqlens_q, cu_seqlens_k), {})
        return out, None

    def f(q, k, v, cq, ck):
        if scale is None:
            s = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
        else:
            s = scale
        return _varlen_attention(q, k, v, cq.astype(jnp.int32),
                                 ck.astype(jnp.int32), max_q, max_k, s,
                                 causal)

    out = _run_op("flash_attn_unpadded", f,
                  (query, key, value, cu_seqlens_q, cu_seqlens_k), {})
    if return_softmax:
        return out, None
    return out, None
