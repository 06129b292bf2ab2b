"""Ulysses (all-to-all) context parallelism over the 'sep' mesh axis.

Ref: SURVEY.md §5.7 / DeepSpeed-Ulysses; the reference's sep-axis process
groups live in fleet/base/topology.py. The GSPMD-style head-sharded layout:
each device starts with its SEQUENCE shard [B, S/sep, NH, D], an all_to_all
redistributes to a HEAD shard [B, S, NH/sep, D], the full-sequence Pallas
flash kernel runs locally (exactly the dense fused-backward hot path —
ops/flash_attention.py), and a reverse all_to_all restores the sequence
shard. Per rank that is 3 all_to_alls forward (q, k, v) + 1 gather (o),
and 1 scatter (do) + 3 gathers (dq, dk, dv) backward — O(S·D·NH/sep)
bytes each, vs the ring's (sep−1) full-KV rotations; on ICI-rich meshes
the all-to-all wins (BENCH_DETAIL cp_compare_s32k_sep4: 3.32 ms vs
6.16 ms worst rank at S=32k, sep=4), while the ring keeps an edge when
NH < sep (no head split exists) or on ICI-poor (hop-limited) meshes.

Strategy selection is threaded through ParallelConfig(sep_strategy=...) /
PADDLE_TPU_SEP_STRATEGY (validated up front, house pattern); GQA routes on
KV-head divisibility and falls back to the ring with a warning otherwise.

Called INSIDE shard_map with q/k/v sequence-sharded: [B, S_local, H, D].
The flash path is a custom_vjp so the backward's extra all_to_alls carry
comm_span bytes like every other overlap site (tests/test_comm_span_lint).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size as _axis_size

from ..observability import trace as _obs
from .. import envs
from ..ops.flash_attention import flash_block_bwd, flash_block_fwd

# House pattern (cf. PADDLE_TPU_TP_OVERLAP_CHUNKS): validated on read, the
# ValueError names the variable. None/unset -> 'ring' (the pre-r7 default).
ENV_SEP_STRATEGY = "PADDLE_TPU_SEP_STRATEGY"
SEP_STRATEGIES = ("ring", "ulysses")


def sep_strategy_default() -> str:
    """The env-selected strategy; read per call so tests can monkeypatch."""
    return envs.get(ENV_SEP_STRATEGY)


def resolve_sep_strategy(value=None) -> str:
    """ParallelConfig.sep_strategy -> validated strategy name. None defers
    to PADDLE_TPU_SEP_STRATEGY (default 'ring'); anything else must be a
    member of SEP_STRATEGIES."""
    if value is None:
        return sep_strategy_default()
    v = str(value).strip().lower()
    if v not in SEP_STRATEGIES:
        raise ValueError(
            f"sep_strategy must be one of {'/'.join(SEP_STRATEGIES)} (or "
            f"None to follow {ENV_SEP_STRATEGY}), got {value!r}")
    return v


# ---------------------------------------------------------------------------
# the two all-to-all layouts
# ---------------------------------------------------------------------------

def _a2a_seq_to_heads(x, axis_name, n, span):
    """[B, S/n, h, D] -> [B, S, h/n, D]: keep head slice, gather sequence."""
    b, s_loc, h, d = x.shape
    with _obs.comm_span(span, nbytes=x.size * x.dtype.itemsize,
                        site="sep_ulysses.a2a"):
        xs = x.reshape(b, s_loc, n, h // n, d)
        xs = jnp.moveaxis(xs, 2, 0)                  # [n, B, S/n, h/n, D]
        xs = lax.all_to_all(xs, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
        xs = jnp.moveaxis(xs, 0, 1)                  # [B, n, S/n, h/n, D]
    return xs.reshape(b, n * s_loc, h // n, d)


def _a2a_heads_to_seq(x, axis_name, n, span):
    """[B, S, h/n, D] -> [B, S/n, h, D]: the exact inverse layout."""
    b, s_full, hl, d = x.shape
    s_loc = s_full // n
    with _obs.comm_span(span, nbytes=x.size * x.dtype.itemsize,
                        site="sep_ulysses.a2a"):
        xs = x.reshape(b, n, s_loc, hl, d)
        xs = jnp.moveaxis(xs, 1, 0)                  # [n, B, S/n, h/n, D]
        xs = lax.all_to_all(xs, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
        xs = jnp.moveaxis(xs, 0, 2)                  # [B, S/n, n, h/n, D]
    return xs.reshape(b, s_loc, hl * n, d)


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b):
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# flash path (custom_vjp: the backward's all_to_alls carry comm_span bytes)
# ---------------------------------------------------------------------------

def _ulysses_fwd_impl(q, k, v, axis_name, causal, scale, rep):
    n = _axis_size(axis_name)
    b = q.shape[0]
    qg = _a2a_seq_to_heads(q, axis_name, n, "ulysses.q_scatter")
    kg = _a2a_seq_to_heads(k, axis_name, n, "ulysses.k_scatter")
    vg = _a2a_seq_to_heads(v, axis_name, n, "ulysses.v_scatter")
    if rep > 1:
        # GQA repeat AFTER the all_to_all: the wire carries only the true
        # kv heads; the repeat's transpose (sum over the group) is applied
        # to dk/dv in the backward before the return all_to_all.
        kg = jnp.repeat(kg, rep, axis=2)
        vg = jnp.repeat(vg, rep, axis=2)
    qb, kb, vb = _to_bh(qg), _to_bh(kg), _to_bh(vg)
    # full-sequence dense flash on the local head slice — each rank runs
    # the fused flat backward over the whole S (see ops/flash_attention)
    ob, lse = flash_block_fwd(qb, kb, vb, causal=causal, scale=scale)
    o = _a2a_heads_to_seq(_from_bh(ob, b), axis_name, n, "ulysses.o_gather")
    return o, (qb, kb, vb, ob, lse)


def _ulysses_bwd_impl(axis_name, causal, scale, rep, res, do):
    qb, kb, vb, ob, lse = res
    n = _axis_size(axis_name)
    b = do.shape[0]
    d = do.shape[-1]
    dog = _a2a_seq_to_heads(do, axis_name, n, "ulysses.do_scatter")
    dob = _to_bh(dog)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)
    dqb, dkb, dvb = flash_block_bwd(qb, kb, vb, dob, lse, delta,
                                    causal=causal, scale=scale)
    dqg, dkg, dvg = _from_bh(dqb, b), _from_bh(dkb, b), _from_bh(dvb, b)
    if rep > 1:
        bs, s_full, hl, _ = dkg.shape
        dkg = dkg.reshape(bs, s_full, hl // rep, rep, d).sum(axis=3) \
            .astype(dkb.dtype)
        dvg = dvg.reshape(bs, s_full, hl // rep, rep, d).sum(axis=3) \
            .astype(dvb.dtype)
    dq = _a2a_heads_to_seq(dqg, axis_name, n, "ulysses.dq_gather")
    dk = _a2a_heads_to_seq(dkg, axis_name, n, "ulysses.dk_gather")
    dv = _a2a_heads_to_seq(dvg, axis_name, n, "ulysses.dv_gather")
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ulysses_flash(q, k, v, axis_name, causal, scale, rep):
    o, _ = _ulysses_fwd_impl(q, k, v, axis_name, causal, scale, rep)
    return o


def _ulysses_flash_fwd(q, k, v, axis_name, causal, scale, rep):
    return _ulysses_fwd_impl(q, k, v, axis_name, causal, scale, rep)


_ulysses_flash.defvjp(_ulysses_flash_fwd, _ulysses_bwd_impl)


def _sdpa_full(q, k, v, causal, scale):
    """fp32 einsum sdpa on the gathered [B, S, h/n, D] layout — the
    non-Pallas fallback for unaligned lengths (mirrors ring_attention's
    impl='xla' fallback); autodiff handles the all_to_all transposes."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                      scale=None, attn_fn=None):
    """DeepSpeed-Ulysses style: all_to_all heads<->sequence over `axis_name`.
    Device i holds sequence chunk i of q/k/v ([B, S_local, H, D], kv heads
    may differ for GQA); returns the attention output [B, S_local, H, D].

    Requires num_heads % sep == 0 (hard error — there is no head slice to
    shard otherwise); GQA additionally needs num_kv_heads % sep == 0 and
    falls back to ring attention with a warning when it doesn't hold.
    attn_fn overrides the local attention callable (XLA reference/dryrun
    path, differentiated by autodiff); default is the Pallas flash
    custom_vjp whose backward all_to_alls carry comm_span bytes."""
    n = _axis_size(axis_name)
    B, S_local, H, D = q.shape
    hkv = k.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses sep strategy needs num_heads % sep == 0 for the "
            f"all-to-all head split; got num_heads={H}, sep={n}. Pick a "
            f"sep degree dividing the head count or select the ring "
            f"strategy (sep_strategy='ring' / {ENV_SEP_STRATEGY}=ring).")
    scale = float(scale if scale is not None else 1.0 / (D ** 0.5))
    if hkv != H and hkv % n:
        warnings.warn(
            f"ulysses sep strategy: num_kv_heads={hkv} is not divisible by "
            f"sep={n}; falling back to ring attention for this call (the "
            f"GQA kv-head all-to-all needs num_kv_heads % sep == 0)",
            RuntimeWarning, stacklevel=2)
        from .ring_attention import ring_attention
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              scale=scale,
                              impl="flash" if attn_fn is None else "xla")
    rep = H // hkv
    if attn_fn is not None:
        qg = _a2a_seq_to_heads(q, axis_name, n, "ulysses.q_scatter")
        kg = _a2a_seq_to_heads(k, axis_name, n, "ulysses.k_scatter")
        vg = _a2a_seq_to_heads(v, axis_name, n, "ulysses.v_scatter")
        if rep > 1:
            kg = jnp.repeat(kg, rep, axis=2)
            vg = jnp.repeat(vg, rep, axis=2)
        return _a2a_heads_to_seq(attn_fn(qg, kg, vg), axis_name, n,
                                 "ulysses.o_gather")
    if (n * S_local) % 128:
        # Pallas backward needs 128-aligned gathered lengths (mirrors
        # ring_attention's alignment fallback to the XLA einsum path)
        return ulysses_attention(
            q, k, v, axis_name=axis_name, causal=causal, scale=scale,
            attn_fn=lambda qg, kg, vg: _sdpa_full(qg, kg, vg, causal,
                                                  scale))
    return _ulysses_flash(q, k, v, axis_name, causal, scale, rep)
