"""Varlen (packed-sequence) flash attention as Pallas TPU kernels.

Ref: the reference's flash_attn_unpadded (python/paddle/nn/functional/
flash_attention.py + its FA2 varlen_fwd CUDA binding): packed sequences
[total_tokens, H, D] with cu_seqlens offsets, no cross-sequence attention.

TPU-native design — NOT the CUDA ragged-batch route. The packed stream is
treated as ONE long sequence per head, run through the streaming-KV flash
kernels (see flash_attention.py), and sequence isolation is enforced by a
per-token i32 CODE = segment_id << 20 | position:

- same-segment test: (code_a ^ code_b) < 2**20  (XOR clears equal high
  bits; any segment difference sets a bit >= 2**20)
- intra-segment causal: the code order IS (segment, position) lex order,
  so same_seg & (code_q >= code_k) masks exactly pos_q >= pos_k.

One i32 array per side replaces separate segment-id and position arrays —
half the mask DMA and two vector compares per tile. Padding rows carry
code PAD_CODE (a reserved segment) so they match nothing real; their
outputs/grads are sliced off and their upstream cotangents are zero, so
no masking epilogue is needed (see _flash_varlen_bwd).

Layouts follow the in-tree TPU convention to avoid in-kernel relayouts:
q-side codes are lane-replicated [T, 128] (a q tile reads [block_q, 128]
sublane-major), kv-side codes are sublane-replicated [8, T] (a kv tile
reads [1, block_k] lane-major); the [block_q, block_k] mask is then a
tile+broadcast compare with no transposes.

Limits (checked by the public wrapper, which falls back to the padded-
batch XLA path): < 1024 sequences per pack, < 2**20 tokens per sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import cost_estimate as _cost_estimate
from ._common import interpret_mode as _interpret
from ._common import mosaic_trace_ctx as _mosaic_ctx
from .flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _LN2, \
    _fit_block, _pad_rows

POS_BITS = 20
SEG_LIMIT = 1 << 10          # max sequences per pack (i32 headroom)
POS_LIMIT = 1 << POS_BITS    # max tokens per sequence
PAD_CODE = SEG_LIMIT << POS_BITS


def _live_col_tiles(cu_rows, cu_cols, n_tiles, block_rows, block_cols,
                    total_rows):
    """Per ROW tile, the contiguous [lo, hi] range of COLUMN tiles holding
    any same-segment pair: segments are contiguous runs of the packed
    stream, so row tile i (rows [i*br, (i+1)*br)) spans segments
    seg(first_row)..seg(last_row), whose columns occupy
    cu_cols[seg_first] .. cu_cols[seg_last + 1] - 1 — one contiguous
    column range. These bounds are SCALAR-PREFETCHED into the kernels'
    index maps, so tiles outside the range are never DMA'd or computed
    (splash-attention-style data-dependent scheduling)."""
    i = jnp.arange(n_tiles)
    r0 = jnp.clip(i * block_rows, 0, total_rows - 1)
    r1 = jnp.clip((i + 1) * block_rows - 1, 0, total_rows - 1)
    seg0 = jnp.searchsorted(cu_rows, r0, side="right").astype(jnp.int32) - 1
    seg1 = jnp.searchsorted(cu_rows, r1, side="right").astype(jnp.int32) - 1
    lo = (cu_cols[seg0] // block_cols).astype(jnp.int32)
    hi = ((jnp.maximum(cu_cols[seg1 + 1], cu_cols[seg1] + 1) - 1)
          // block_cols).astype(jnp.int32)
    return lo, jnp.maximum(hi, lo)


def _tile_mask(s, cq_ref, ck_ref, causal):
    """Mask one [BQ, BK] score tile from the packed codes.

    cq_ref block: [block_q, 128] (lane-replicated); ck_ref block:
    [8, block_k] (sublane-replicated)."""
    bq, bk = s.shape
    cq = cq_ref[...]                        # [BQ, 128]
    ck = ck_ref[:1, :]                      # [1, BK]
    cqt = jnp.tile(cq, (1, bk // 128))      # [BQ, BK] lane-replicated
    same = (cqt ^ ck) < POS_LIMIT
    ok = same & (cqt >= ck) if causal else same
    return jnp.where(ok, s, jnp.float32(-1e30))


def _flat_schedule(lo, hi, n_q, n_flat):
    """Front-packed flat schedule over the LIVE (q-tile, k-tile) pairs.

    The rectangular grid (n_q, per-tile-span-bound) spends one grid step
    (~1.3 µs of fixed Mosaic cost) on every dead (clamped) slot; on short
    -sequence packs dead steps outnumber live ones ~30:1 and dominate the
    kernel (measured: the 16-seq/16k pack ran 1280 steps for ~40 live
    tiles). Flattening packs the live pairs first: step s works on
    (qi[s], ki[s]); the dead remainder collapses to a clamped tail that
    re-presents the last window (no DMA, no compute). All arrays are
    computed IN-GRAPH from cu, so the schedule is jit-correct for any
    cu values at the same shapes; n_flat is the same static bound the
    rectangular grid used (n_q x span bound), so worst-case work is
    unchanged."""
    spans = (hi - lo + 1).astype(jnp.int32)
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(spans).astype(jnp.int32)])
    s = jnp.arange(n_flat, dtype=jnp.int32)
    qi = jnp.clip(jnp.searchsorted(cum, s, side="right") - 1,
                  0, n_q - 1).astype(jnp.int32)
    ki = jnp.clip(lo[qi] + (s - cum[qi]), lo[qi], hi[qi]).astype(jnp.int32)
    live = (s < cum[n_q]).astype(jnp.int32)
    first = ((s == cum[qi]) & (live == 1)).astype(jnp.int32)
    last = ((s == cum[qi + 1] - 1) & (live == 1)).astype(jnp.int32)
    return qi, ki, first, last, live


def _fwd_kernel_varlen(qi_ref, ki_ref, first_ref, last_ref, live_ref,
                       q_ref, k_ref, v_ref, cq_ref, ck_ref,
                       o_ref, lse_ref, m_s, l_s, acc_s, *, causal, scale):
    """Streaming forward over the packed stream: FLAT grid (H, n_flat),
    one live (q-tile, k-tile) pair per step (_flat_schedule), classic
    ONLINE-softmax scratch scheme (running max + alpha rescale). NOTE:
    flash_attention's dense kernels moved to the r5 fixed-base scheme
    (tile-0-anchored exponent base, no rescale) — the varlen kernels
    still rescale online; the two no longer share softmax semantics.
    Init/finalize are driven by the scalar-prefetched first/last flags
    (a q tile's steps are consecutive in the flat order); masking needs
    no positional bookkeeping — the segment codes carry it."""
    s_idx = pl.program_id(1)

    @pl.when(first_ref[s_idx] == 1)
    def _init():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(live_ref[s_idx] == 1)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = _tile_mask(s, cq_ref, ck_ref, causal)
        m = m_s[:, :1]
        l = l_s[:, :1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(last_ref[s_idx] == 1)
    def _finalize():
        m = m_s[:, :1]
        l = l_s[:, :1]
        # a row with NO live key (cross-attn q segment whose k side is
        # empty) ends with m == -1e30 (the mask overwrite value): its
        # online softmax degenerated to p=1 over masked slots. Its true
        # output is all-padding -> 0, and its lse must be a value that
        # makes the backward's p = exp(s + bias - lse) vanish (bias is
        # -1e30, so any lse >> -1e30 does; 0 keeps it finite).
        dead = m <= jnp.float32(-1e29)
        o_ref[0] = jnp.where(
            dead, jnp.float32(0.0),
            acc_s[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            dead, jnp.float32(0.0),
            m + jnp.log(jnp.maximum(l, 1e-30))).T


def _bwd_bounds(cu_q, cu_k, n_k, block_q, block_k, tk, causal, self_attn):
    """Live Q-tile [lo, hi] per K tile (the backward's k-major
    orientation), with the causal START folded in for self-attention
    packing: k tile j only receives gradient from q rows at or past its
    own diagonal, so the live run begins at max(segment start,
    (j*block_k)//block_q). For self-attention this is EXACTLY the
    transpose of _fwd_bounds' live set (j*block_k <= (i+1)*block_q - 1
    iff (j*block_k)//block_q <= i), so the flat backward walks the same
    live pairs as the forward, k-major."""
    lo, hi = _live_col_tiles(cu_k, cu_q, n_k, block_k, block_q, tk)
    if causal and self_attn:
        j = jnp.arange(n_k, dtype=jnp.int32)
        lo = jnp.maximum(lo, ((j * block_k) // block_q).astype(jnp.int32))
        hi = jnp.maximum(hi, lo)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _bwd_fused_kernel_varlen(ki_ref, qi_ref, first_ref, last_ref, live_ref,
                             q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, cq_ref, ck_ref, dq_ref, dk_ref,
                             dv_ref, dk_s, dv_s, dq_s, *, causal, scale,
                             nh, block_q, block_k, tp):
    """Fused dK/dV/dQ in ONE streaming pass per live tile: FLAT grid
    (H/nh, n_flat) in k-major order (_flat_schedule over the per-k-tile
    live q ranges), the varlen analogue of the dense path's
    _bwd_fused_kernel_stream. Each live (k-tile, q-tile) pair fetches
    q/do/lse/delta and k/v ONCE and runs all five matmuls (s, dv, dp,
    dk, dq) — the split two-kernel scheme fetched every block twice and
    ran seven matmuls (s and dp recomputed in the dq kernel).

    This is also the rows-stacked head-fusion port to the backward
    (cf. _fwd_kernel_varlen_stacked): `nh` heads ride one grid step, the
    segment mask is built ONCE per step as an additive f32 bias (it is
    head-independent), and short-segment packs amortize the per-step
    fixed cost across heads. Adding -1e30 to a finite masked score is
    bitwise-identical in f32 to overwriting it with -1e30 (|s| < 1e23
    is absorbed; +0.0 is exact), so the fused kernel matches the split
    kernels bit-for-bit at equal block sizes.

    dK/dV accumulate in scratch across a k tile's consecutive live steps
    (first/last flags) exactly like the split kernel. dQ accumulates in
    a PERSISTENT full-length scratch (dq_s, [nh*tp, d] f32, zeroed once
    at step 0): a q tile's steps are NOT consecutive in k-major order,
    so the running partial is re-written to the dq out block on every
    live step — the grid is sequential, so the final write-back of each
    presented block (the tile's LAST visit) carries the complete sum.
    Padding q rows need no epilogue: their do/delta are zero-padded, so
    dk/dv contributions vanish; pad k columns mask against every real q
    row via the codes."""
    import numpy as np
    s_idx = pl.program_id(1)
    bq = np.int32(block_q)

    @pl.when(s_idx == 0)
    def _init_dq():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    @pl.when(first_ref[s_idx] == 1)
    def _init_dkv():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(live_ref[s_idx] == 1)
    def _compute():
        qi = qi_ref[s_idx]
        cq = cq_ref[:, :1]
        ck = ck_ref[:1, :]
        same = (cq ^ ck) < POS_LIMIT
        ok = same & (cq >= ck) if causal else same
        bias = jnp.where(ok, jnp.float32(0.0), jnp.float32(-1e30))
        for hh in range(nh):
            qb = q_ref[hh]
            kb = k_ref[hh]
            vb = v_ref[hh]
            dob = do_ref[hh]
            lseb = lse_ref[hh, 0, :]
            deltab = delta_ref[hh, 0, :]
            sl = slice(hh * block_k, (hh + 1) * block_k)
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32) * scale + bias
            p = jnp.exp(s - lseb[:, None])
            p_lo = p.astype(vb.dtype)
            dv_s[sl] = dv_s[sl] + jnp.dot(
                p_lo.T, dob, preferred_element_type=jnp.float32)
            dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - deltab[:, None]) * scale).astype(vb.dtype)
            dk_s[sl] = dk_s[sl] + jnp.dot(
                ds.T, qb, preferred_element_type=jnp.float32)
            row = qi * bq + np.int32(hh * tp)
            dq_new = dq_s[pl.ds(row, block_q), :] + jnp.dot(
                ds, kb, preferred_element_type=jnp.float32)
            dq_s[pl.ds(row, block_q), :] = dq_new
            dq_ref[hh] = dq_new.astype(dq_ref.dtype)

    @pl.when(last_ref[s_idx] == 1)
    def _flush_dkv():
        for hh in range(nh):
            sl = slice(hh * block_k, (hh + 1) * block_k)
            dk_ref[hh] = dk_s[sl].astype(dk_ref.dtype)
            dv_ref[hh] = dv_s[sl].astype(dv_ref.dtype)


def _bwd_dkv_flat_kernel(ki_ref, qi_ref, first_ref, last_ref, live_ref,
                         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         cq_ref, ck_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                         causal, scale):
    """Split-kernel dK/dV on the FLAT k-major live-tile schedule: grid
    (H, n_flat), one live (k-tile, q-tile) pair per step. Fallback for
    shapes where the fused kernel's persistent dQ scratch does not fit
    scoped VMEM (_bwd_fused_nh == 0 — very long packed streams); still
    skips every dead tile the old rectangular (H, n_k, n_q) grid burned
    a predicated step on."""
    s_idx = pl.program_id(1)

    @pl.when(first_ref[s_idx] == 1)
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(live_ref[s_idx] == 1)
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        qb = q_ref[0]
        dob = do_ref[0]
        lseb = lse_ref[0, 0, :]
        deltab = delta_ref[0, 0, :]
        s = jnp.dot(qb, k.T, preferred_element_type=jnp.float32) * scale
        s = _tile_mask(s, cq_ref, ck_ref, causal)
        p = jnp.exp(s - lseb[:, None])
        p_lo = p.astype(v.dtype)
        dv_s[...] = dv_s[...] + jnp.dot(p_lo.T, dob,
                                        preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - deltab[:, None]) * scale).astype(v.dtype)
        dk_s[...] = dk_s[...] + jnp.dot(ds.T, qb,
                                        preferred_element_type=jnp.float32)

    @pl.when(last_ref[s_idx] == 1)
    def _finalize():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd_dq_flat_kernel(qi_ref, ki_ref, first_ref, last_ref, live_ref,
                        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        cq_ref, ck_ref, dq_ref, dq_s, *, causal, scale):
    """Split-kernel dQ on the FLAT q-major live-tile schedule (the same
    _flat_schedule arrays the forward runs): grid (H, n_flat). Fallback
    companion of _bwd_dkv_flat_kernel."""
    s_idx = pl.program_id(1)

    @pl.when(first_ref[s_idx] == 1)
    def _init():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    @pl.when(live_ref[s_idx] == 1)
    def _compute():
        qb = q_ref[0]
        dob = do_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        lseb = lse_ref[0, 0, :]
        deltab = delta_ref[0, 0, :]
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
        s = _tile_mask(s, cq_ref, ck_ref, causal)
        p = jnp.exp(s - lseb[:, None])
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - deltab[:, None]) * scale).astype(kb.dtype)
        dq_s[...] = dq_s[...] + jnp.dot(ds, kb,
                                        preferred_element_type=jnp.float32)

    @pl.when(last_ref[s_idx] == 1)
    def _finalize():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


# Scoped-VMEM plan for the fused backward. The persistent dQ accumulator
# (nh * padded_total_q rows of f32) is the big consumer, so the head
# grouping is fitted per SHAPE, not just per dtype; the Mosaic scoped-
# VMEM window is raised accordingly (the dense fused backward already
# runs at 48 MB — see flash_attention._bwd_fused_stream_chunk).
_FUSED_BWD_VMEM_BUDGET = 52 * 1024 * 1024
_BWD_VMEM_LIMIT = 80 * 1024 * 1024


def _bwd_fused_vmem_bytes(nh, itemsize, bq, bk, d, tp):
    """Estimated scoped-VMEM footprint of one fused-backward grid step:
    f32 scratch (persistent dq + dk/dv accumulators) plus double-buffered
    in/out blocks."""
    scratch = 4 * (nh * tp * d + 2 * nh * bk * d)
    blocks = (2 * nh * bq * d * itemsize      # q, do
              + 2 * nh * bk * d * itemsize    # k, v
              + 2 * nh * bq * 4               # lse, delta
              + bq * 128 * 4 + 8 * bk * 4     # code tiles
              + nh * bq * d * itemsize        # dq
              + 2 * nh * bk * d * itemsize)   # dk, dv
    temps = 4 * bq * bk * 4                   # s/p/dp/ds tiles
    return scratch + 2 * blocks + temps


def _bwd_fused_nh(h, itemsize, d, bq, bk, tp):
    """Heads fused per fused-backward grid step: largest power-of-two
    divisor of h whose footprint (incl. the [nh*tp, d] persistent dQ
    scratch) fits the budget. Returns 0 when not even nh=1 fits — the
    caller falls back to the split flat kernels, which stream dQ through
    a per-tile scratch instead."""
    for cand in (8, 4, 2, 1):
        if h % cand == 0 and _bwd_fused_vmem_bytes(
                cand, itemsize, bq, bk, d, tp) <= _FUSED_BWD_VMEM_BUDGET:
            return cand
    return 0


def _fwd_kernel_varlen_stacked(qi_ref, ki_ref, first_ref, last_ref, live_ref,
                               q_ref, k_ref, v_ref, cq_ref, ck_ref,
                               o_ref, lse_ref, s_s, m_s, l_s, acc_s, *,
                               causal, nh, block_q):
    """Rows-stacked head-fused forward: one grid step processes `nh` heads
    of the SAME live (q-tile, k-tile) pair, with every head's score tile
    stacked along the ROW axis of one scratch buffer so the online-softmax
    chain (rowmax -> alpha -> exp2 -> rowsum -> rescale) runs ONCE per
    step for all nh heads.

    Why: the chain costs ~1-1.6 us of serial (non-overlapped) VPU latency
    per score chunk REGARDLESS of chunk size (measured on v5e: 1.1 us at
    256^2, 1.6 at 512^2, 1.5 at 1024^2 — row-parallel, latency-bound),
    and Mosaic does not overlap it with the MXU matmuls. Per-head kernels
    pay it once per (chunk, head); stacking pays it once per chunk. The
    mask is also head-independent and is built once as an additive f32
    bias. Best for SHORT-segment packs, where small tiles (low waste)
    make the chain the dominant cost; long-segment packs keep the
    per-head streaming kernel (full-rate 1024^2 matmuls, waste ~0).
    """
    bq = block_q
    s_idx = pl.program_id(1)

    @pl.when(first_ref[s_idx] == 1)
    def _init():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(live_ref[s_idx] == 1)
    def _compute():
        cq = cq_ref[:, :1]
        ck = ck_ref[:1, :]
        same = (cq ^ ck) < POS_LIMIT
        ok = same & (cq >= ck) if causal else same
        bias = jnp.where(ok, jnp.float32(0.0), jnp.float32(-1e30))
        for hh in range(nh):
            s_s[hh * bq:(hh + 1) * bq] = jnp.dot(
                q_ref[hh], k_ref[hh].T,
                preferred_element_type=jnp.float32) + bias
        s = s_s[...]
        m = m_s[:, :1]
        l = l_s[:, :1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp2(m - m_new)
        p = jnp.exp2(s - m_new)
        l_s[...] = jnp.broadcast_to(
            l * alpha + p.sum(axis=-1, keepdims=True), l_s.shape)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        pb = p.astype(v_ref.dtype)
        for hh in range(nh):
            sl = slice(hh * bq, (hh + 1) * bq)
            acc_s[sl] = acc_s[sl] * alpha[sl] + jnp.dot(
                pb[sl], v_ref[hh], preferred_element_type=jnp.float32)

    @pl.when(last_ref[s_idx] == 1)
    def _finalize():
        m = m_s[:, :1]
        l = l_s[:, :1]
        big_o = acc_s[...] / jnp.maximum(l, 1e-30)
        big_lse = (m + jnp.log2(jnp.maximum(l, 1e-30))) * _LN2
        for hh in range(nh):
            sl = slice(hh * bq, (hh + 1) * bq)
            o_ref[hh] = big_o[sl].astype(o_ref.dtype)
            lse_ref[hh] = big_lse[sl].T


# Scoped-VMEM budget for one stacked grid step. v5e exposes ~16 MB of
# scoped VMEM to a Mosaic kernel; leave headroom for compiler temporaries.
# (Measured: f32 inputs at nh=8 request 20.72 MB and fail to compile;
# bf16 at nh=8 is ~13.9 MB and compiles.)
_STACKED_VMEM_BUDGET = 14 * 1024 * 1024


def _stacked_vmem_bytes(nh, itemsize, bq, bk, d):
    """Estimated scoped-VMEM footprint of one stacked-kernel grid step:
    f32 scratch (scores + m/l columns + acc) plus double-buffered in/out
    blocks (q, k, v, code tiles, o, lse)."""
    scratch = 4 * (nh * bq * bk + 2 * nh * bq * 128 + nh * bq * d)
    blocks = (nh * bq * d * itemsize          # q
              + 2 * nh * bk * d * itemsize    # k, v
              + bq * 128 * 4 + 8 * bk * 4     # code tiles
              + nh * bq * d * itemsize        # o
              + nh * bq * 4)                  # lse
    return scratch + 2 * blocks


def _stacked_nh(h, itemsize=2, d=128, bq=None, bk=None):
    """Heads fused per grid step: largest power-of-two divisor of h that
    is <= 8 (powers of two keep the stacked scratch row count
    tile-aligned; non-power-of-two head counts amortize less) AND whose
    grid-step footprint fits the scoped-VMEM budget — f32 inputs double
    the block bytes, so nh=8 that compiles in bf16 OOMs at f32 (advisor
    r4 finding). Returns 0 when no grouping fits (caller falls back to
    the per-head streaming kernel)."""
    bq = STACKED_BLOCK_Q if bq is None else bq
    bk = STACKED_BLOCK_K if bk is None else bk
    for cand in (8, 4, 2, 1):
        if h % cand == 0 and _stacked_vmem_bytes(
                cand, itemsize, bq, bk, d) <= _STACKED_VMEM_BUDGET:
            return cand
    return 0


def _flash_varlen_fwd_stacked(q, k, v, cu_q, causal, scale, block_q,
                              block_k, n_flat_hint=None):
    """Stacked-kernel forward for SELF-ATTENTION short-segment packs.

    q/k/v: [H, T, D] packed; q is pre-scale-folded HERE (scale*log2e into
    q once — the kernel softmax runs in the exp2 domain; lse is returned
    in the natural-log domain for vjp compatibility)."""
    from .flash_attention import _LOG2E
    h, t, d = q.shape
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, t)
    it = jnp.dtype(q.dtype).itemsize
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    qp, _ = _pad_rows(q, block_q)
    kp, _ = _pad_rows(k, block_k)
    vp, _ = _pad_rows(v, block_k)
    tp, tkp = qp.shape[1], kp.shape[1]
    code = _codes_from_cu(cu_q, t)
    cq2d, _ = _expand_codes(code, tp)
    _, ck2d = _expand_codes(code, tkp)
    n_q, n_k = tp // block_q, tkp // block_k
    lo, hi = _fwd_bounds(cu_q, cu_q, n_q, block_q, block_k, t, causal, True)
    n_flat = min(n_flat_hint, n_q * n_k) if n_flat_hint else n_q * n_k
    qi_a, ki_a, first_a, last_a, live_a = _flat_schedule(lo, hi, n_q, n_flat)
    nh = _stacked_nh(h, jnp.dtype(q.dtype).itemsize, d, block_q, block_k)
    if nh == 0:
        raise ValueError(
            "stacked varlen kernel does not fit VMEM at this dtype/shape; "
            "selection should have fallen back to the streaming kernel")
    kernel = functools.partial(_fwd_kernel_varlen_stacked, causal=causal,
                               nh=nh, block_q=block_q)
    with _mosaic_ctx():
        o, lse = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(h // nh, n_flat),
                in_specs=[
                    pl.BlockSpec((nh, block_q, d),
                                 lambda g, s, qi, ki, f, l, lv: (g, qi[s], 0)),
                    pl.BlockSpec((nh, block_k, d),
                                 lambda g, s, qi, ki, f, l, lv: (g, ki[s], 0)),
                    pl.BlockSpec((nh, block_k, d),
                                 lambda g, s, qi, ki, f, l, lv: (g, ki[s], 0)),
                    pl.BlockSpec((block_q, 128),
                                 lambda g, s, qi, ki, f, l, lv: (qi[s], 0)),
                    pl.BlockSpec((8, block_k),
                                 lambda g, s, qi, ki, f, l, lv: (0, ki[s])),
                ],
                out_specs=[
                    pl.BlockSpec((nh, block_q, d),
                                 lambda g, s, qi, ki, f, l, lv: (g, qi[s], 0)),
                    pl.BlockSpec((nh, 1, block_q),
                                 lambda g, s, qi, ki, f, l, lv: (g, 0, qi[s])),
                ],
                scratch_shapes=[
                    pltpu.VMEM((nh * block_q, block_k), jnp.float32),
                    pltpu.VMEM((nh * block_q, 128), jnp.float32),
                    pltpu.VMEM((nh * block_q, 128), jnp.float32),
                    pltpu.VMEM((nh * block_q, d), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(qp.shape, q.dtype),
                jax.ShapeDtypeStruct((h, 1, tp), jnp.float32),
            ],
            cost_estimate=_cost_estimate(
                flops=4 * h * n_flat * block_q * block_k * d,
                transcendentals=h * n_flat * block_q * block_k,
                bytes_accessed=(h * n_flat * (block_q + 2 * block_k) * d
                                * it + h * tp * d * it),
                name="varlen.fwd_stacked"),
            interpret=_interpret(),
        )(qi_a, ki_a, first_a, last_a, live_a, qp, kp, vp, cq2d, ck2d)
    return o[:, :t], lse.reshape(h, tp)[:, :t]


# blocks for the stacked short-segment path. r5 re-sweep on the 16-seq
# 16k bench pack: 512x512 (nh drops 8->4 for VMEM) edges out 256x512
# (0.179 vs 0.173 eff); 384x512, 256x768, 512x768, 128x512 all worse.
STACKED_BLOCK_Q = 512
STACKED_BLOCK_K = 512


def _expand_codes(code, t):
    """[T] i32 -> (q-side [T, 128] lane-replicated,
                   kv-side [8, T] sublane-replicated), padded to t rows
    with PAD_CODE."""
    n = code.shape[0]
    if t != n:
        code = jnp.pad(code, (0, t - n), constant_values=PAD_CODE)
    qside = jax.lax.broadcast_in_dim(code, (t, 128), (0,))
    kvside = jax.lax.broadcast_in_dim(code, (8, t), (1,))
    return qside.astype(jnp.int32), kvside.astype(jnp.int32)


def _codes_from_cu(cu, total):
    """cu [B+1] i32 cumulative offsets -> packed [total] codes."""
    t = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu, t, side="right").astype(jnp.int32) - 1
    pos = t - cu[seg]
    return (seg << POS_BITS) | pos


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash_varlen(q, k, v, cu_q, cu_k, causal, scale, block_q, block_k,
                  self_attn, max_seqlen, n_flat_hint=None, stacked=False,
                  n_flat_bwd_hint=None):
    o, _ = _flash_varlen_fwd_impl(q, k, v, cu_q, cu_k, causal, scale,
                                  block_q, block_k, self_attn, max_seqlen,
                                  n_flat_hint, stacked)
    return o


def _inner_steps(n_full, block_rows, block_cols, max_seqlen):
    """Static bound on the live column-tile span of any row tile: the
    spanned segments cover at most block_rows + 2*max_seqlen columns
    (partial first/last segments extend beyond the tile's rows), i.e.
    that many cols / block_cols tiles plus alignment slack. Shrinking the
    inner grid to this removes the dead steps entirely — max_seqlen is
    the same STATIC int the reference's flash_attn_unpadded requires.

    SELF-ATTENTION ONLY: with distinct q/k packings a block_rows-row tile
    can span up to block_rows segments of up to max_seqlen columns EACH,
    so no useful static bound exists; callers must pass max_seqlen=None
    (enforced in the impl/bwd entry points)."""
    if not max_seqlen:
        return n_full
    return min(n_full, (block_rows + 2 * int(max_seqlen)) // block_cols + 3)


def _fwd_bounds(cu_q, cu_k, n_q, block_q, block_k, t, causal, self_attn):
    """Live k-tile [lo, hi] per q tile, with the causal diagonal folded in
    for self-attention packing."""
    lo, hi = _live_col_tiles(cu_q, cu_k, n_q, block_q, block_k, t)
    if causal and self_attn:
        # int32 throughout: the package runs with x64 on, and int64 scalar-
        # prefetch operands break Mosaic's SMEM lowering
        i = jnp.arange(n_q, dtype=jnp.int32)
        diag = ((i + 1) * block_q - 1) // block_k
        hi = jnp.minimum(hi, diag.astype(jnp.int32))
        hi = jnp.maximum(hi, lo)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _flash_varlen_fwd_impl(q, k, v, cu_q, cu_k, causal, scale, block_q,
                           block_k, self_attn, max_seqlen=None,
                           n_flat_hint=None, stacked=False):
    """q/k/v: [H, T, D] packed; cu_*: [B+1] i32 offsets. Returns (o, lse)."""
    if stacked and self_attn:
        return _flash_varlen_fwd_stacked(q, k, v, cu_q, causal, scale,
                                         STACKED_BLOCK_Q, STACKED_BLOCK_K,
                                         n_flat_hint)
    h, t, d = q.shape
    tk = k.shape[1]
    it = jnp.dtype(q.dtype).itemsize
    if not self_attn:
        max_seqlen = None  # the static span bound is unsound cross-attn
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, tk)
    qp, _ = _pad_rows(q, block_q)
    kp, _ = _pad_rows(k, block_k)
    vp, _ = _pad_rows(v, block_k)
    tp, tkp = qp.shape[1], kp.shape[1]
    code_q = _codes_from_cu(cu_q, t)
    code_k = code_q if self_attn and tk == t else _codes_from_cu(cu_k, tk)
    cq2d, _ = _expand_codes(code_q, tp)
    _, ck2d = _expand_codes(code_k, tkp)
    n_q, n_k = tp // block_q, tkp // block_k
    lo, hi = _fwd_bounds(cu_q, cu_k, n_q, block_q, block_k, t, causal,
                         self_attn)
    n_flat = n_q * _inner_steps(n_k, block_q, block_k, max_seqlen)
    if n_flat_hint is not None:
        # live-pair count measured by the wrapper while cu was still
        # concrete (cu is a tracer HERE — the custom_vjp boundary traces
        # its array args); the grid's ~1.3 µs fixed cost per step is what
        # dominates short-sequence packs, and the static bound is ~4x
        # over-provisioned for them
        n_flat = min(n_flat, n_flat_hint)
    qi_a, ki_a, first_a, last_a, live_a = _flat_schedule(lo, hi, n_q, n_flat)
    kernel = functools.partial(_fwd_kernel_varlen, causal=causal,
                               scale=scale)
    with _mosaic_ctx():
        o, lse = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(h, n_flat),
                in_specs=[
                    pl.BlockSpec((1, block_q, d),
                                 lambda b, s, qi, ki, f, l, lv: (b, qi[s], 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda b, s, qi, ki, f, l, lv: (b, ki[s], 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda b, s, qi, ki, f, l, lv: (b, ki[s], 0)),
                    pl.BlockSpec((block_q, 128),
                                 lambda b, s, qi, ki, f, l, lv: (qi[s], 0)),
                    pl.BlockSpec((8, block_k),
                                 lambda b, s, qi, ki, f, l, lv: (0, ki[s])),
                ],
                out_specs=[
                    pl.BlockSpec((1, block_q, d),
                                 lambda b, s, qi, ki, f, l, lv: (b, qi[s], 0)),
                    pl.BlockSpec((1, 1, block_q),
                                 lambda b, s, qi, ki, f, l, lv: (b, 0, qi[s])),
                ],
                scratch_shapes=[
                    pltpu.VMEM((block_q, 128), jnp.float32),
                    pltpu.VMEM((block_q, 128), jnp.float32),
                    pltpu.VMEM((block_q, d), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(qp.shape, q.dtype),
                jax.ShapeDtypeStruct((h, 1, tp), jnp.float32),
            ],
            cost_estimate=_cost_estimate(
                flops=4 * h * n_flat * block_q * block_k * d,
                transcendentals=h * n_flat * block_q * block_k,
                bytes_accessed=(h * n_flat * (block_q + 2 * block_k) * d
                                * it + h * tp * d * it),
                name="varlen.fwd"),
            interpret=_interpret(),
        )(qi_a, ki_a, first_a, last_a, live_a, qp, kp, vp, cq2d, ck2d)
    return o[:, :t], lse.reshape(h, tp)[:, :t]


def _flash_varlen_fwd(q, k, v, cu_q, cu_k, causal, scale, block_q,
                      block_k, self_attn, max_seqlen, n_flat_hint=None,
                      stacked=False, n_flat_bwd_hint=None):
    o, lse = _flash_varlen_fwd_impl(q, k, v, cu_q, cu_k, causal, scale,
                                    block_q, block_k, self_attn, max_seqlen,
                                    n_flat_hint, stacked)
    return o, (q, k, v, cu_q, cu_k, o, lse)


def _bwd_fused_call(qp, kp, vp, dop, lse3, delta3, cq2d, ck2d, ki_a, qi_a,
                    first_a, last_a, live_a, n_flat, nh, block_q, block_k,
                    causal, scale):
    """pallas_call plumbing for _bwd_fused_kernel_varlen: grid
    (H/nh, n_flat), five scalar-prefetched schedule arrays feeding every
    index map, nh heads per block."""
    h, tp, d = qp.shape
    tkp = kp.shape[1]
    it = jnp.dtype(qp.dtype).itemsize
    kernel = functools.partial(_bwd_fused_kernel_varlen, causal=causal,
                               scale=scale, nh=nh, block_q=block_q,
                               block_k=block_k, tp=tp)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(h // nh, n_flat),
            in_specs=[
                pl.BlockSpec((nh, block_q, d),
                             lambda g, s, ki, qi, f, l, lv: (g, qi[s], 0)),
                pl.BlockSpec((nh, block_k, d),
                             lambda g, s, ki, qi, f, l, lv: (g, ki[s], 0)),
                pl.BlockSpec((nh, block_k, d),
                             lambda g, s, ki, qi, f, l, lv: (g, ki[s], 0)),
                pl.BlockSpec((nh, block_q, d),
                             lambda g, s, ki, qi, f, l, lv: (g, qi[s], 0)),
                pl.BlockSpec((nh, 1, block_q),
                             lambda g, s, ki, qi, f, l, lv: (g, 0, qi[s])),
                pl.BlockSpec((nh, 1, block_q),
                             lambda g, s, ki, qi, f, l, lv: (g, 0, qi[s])),
                pl.BlockSpec((block_q, 128),
                             lambda g, s, ki, qi, f, l, lv: (qi[s], 0)),
                pl.BlockSpec((8, block_k),
                             lambda g, s, ki, qi, f, l, lv: (0, ki[s])),
            ],
            out_specs=[
                pl.BlockSpec((nh, block_q, d),
                             lambda g, s, ki, qi, f, l, lv: (g, qi[s], 0)),
                pl.BlockSpec((nh, block_k, d),
                             lambda g, s, ki, qi, f, l, lv: (g, ki[s], 0)),
                pl.BlockSpec((nh, block_k, d),
                             lambda g, s, ki, qi, f, l, lv: (g, ki[s], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((nh * block_k, d), jnp.float32),
                pltpu.VMEM((nh * block_k, d), jnp.float32),
                pltpu.VMEM((nh * tp, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, qp.dtype),
            jax.ShapeDtypeStruct(kp.shape, kp.dtype),
            jax.ShapeDtypeStruct(vp.shape, vp.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
        cost_estimate=_cost_estimate(
            flops=10 * h * n_flat * block_q * block_k * d,
            transcendentals=h * n_flat * block_q * block_k,
            bytes_accessed=(2 * h * n_flat * (block_q + block_k) * d * it
                            + h * (tp + 2 * tkp) * d * it),
            name="varlen.bwd_fused"),
        interpret=_interpret(),
    )(ki_a, qi_a, first_a, last_a, live_a, qp, kp, vp, dop, lse3, delta3,
      cq2d, ck2d)


def _flash_varlen_bwd(causal, scale, block_q, block_k, self_attn,
                      max_seqlen, n_flat_hint, stacked, n_flat_bwd_hint,
                      res, do):
    """Flat-scheduled varlen backward: one k-major live-tile schedule
    drives a FUSED dK/dV/dQ kernel when the persistent dQ scratch fits
    VMEM (_bwd_fused_nh), else the split flat kernels (dK/dV k-major,
    dQ on the forward's q-major schedule). Either way every grid step is
    a live (q-tile, k-tile) pair — the old rectangular (H, n_k, n_q) /
    (H, n_q, n_k) grids burned a fixed-cost predicated step on every
    dead tile, which dominated short-segment packs ~30:1."""
    q, k, v, cu_q, cu_k, o, lse = res
    h, t, d = q.shape
    tk = k.shape[1]
    if not self_attn:
        max_seqlen = None  # see _inner_steps: bound unsound cross-attn
    if stacked and self_attn:
        # the stacked forward ran at the stacked tiling; keep the
        # backward on the same blocks so short-segment packs get the
        # same quadratic dead-area savings (1024^2 tiles on 512-token
        # segments are 75% dead even inside live tiles)
        block_q, block_k = STACKED_BLOCK_Q, STACKED_BLOCK_K
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, tk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qp, _ = _pad_rows(q, block_q)
    dop, _ = _pad_rows(do, block_q)
    kp, _ = _pad_rows(k, block_k)
    vp, _ = _pad_rows(v, block_k)
    tp, tkp = qp.shape[1], kp.shape[1]
    lse3, _ = _pad_rows(lse.reshape(h, t, 1), block_q)
    delta3, _ = _pad_rows(delta.reshape(h, t, 1), block_q)
    lse3 = lse3.reshape(h, 1, tp)
    delta3 = delta3.reshape(h, 1, tp)
    code_q = _codes_from_cu(cu_q, t)
    code_k = code_q if self_attn and tk == t else _codes_from_cu(cu_k, tk)
    cq2d, _ = _expand_codes(code_q, tp)
    _, ck2d = _expand_codes(code_k, tkp)
    n_q, n_k = tp // block_q, tkp // block_k
    it = jnp.dtype(q.dtype).itemsize

    # k-major live-tile schedule (dK/dV accumulation order); same static
    # bound + concrete-cu hint scheme as the forward grid
    lo_q, hi_q = _bwd_bounds(cu_q, cu_k, n_k, block_q, block_k, tk,
                             causal, self_attn)
    n_flat = n_k * _inner_steps(n_q, block_k, block_q, max_seqlen)
    if n_flat_bwd_hint is not None:
        n_flat = min(n_flat, n_flat_bwd_hint)
    ki_a, qi_a, first_a, last_a, live_a = _flat_schedule(lo_q, hi_q, n_k,
                                                         n_flat)
    nh = _bwd_fused_nh(h, it, d, block_q, block_k, tp)
    with _mosaic_ctx():
        if nh:
            dq, dk, dv = _bwd_fused_call(
                qp, kp, vp, dop, lse3, delta3, cq2d, ck2d, ki_a, qi_a,
                first_a, last_a, live_a, n_flat, nh, block_q, block_k,
                causal, scale)
            if not self_attn:
                # k-major presentation only reaches q tiles inside some
                # k tile's live range; a cross-attn pack can LEAD/TRAIL
                # with q segments that have zero k tokens, whose dq HBM
                # blocks are then never written. Their true gradient is
                # zero (no keys -> masked-to-zero output), so zero any
                # uncovered tile in-graph. Self-attention needs no fix:
                # its k-major live set is the transpose of the forward's
                # q-major set, which presents every q tile.
                i = jnp.arange(n_q, dtype=jnp.int32)
                cover = jnp.any((i[None, :] >= lo_q[:, None])
                                & (i[None, :] <= hi_q[:, None]), axis=0)
                dq = jnp.where(jnp.repeat(cover, block_q)[None, :, None],
                               dq, jnp.zeros((), dq.dtype)).astype(qp.dtype)
        else:
            dk, dv = pl.pallas_call(
                functools.partial(_bwd_dkv_flat_kernel, causal=causal,
                                  scale=scale),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=5,
                    grid=(h, n_flat),
                    in_specs=[
                        pl.BlockSpec(
                            (1, block_q, d),
                            lambda b, s, ki, qi, f, l, lv: (b, qi[s], 0)),
                        pl.BlockSpec(
                            (1, block_k, d),
                            lambda b, s, ki, qi, f, l, lv: (b, ki[s], 0)),
                        pl.BlockSpec(
                            (1, block_k, d),
                            lambda b, s, ki, qi, f, l, lv: (b, ki[s], 0)),
                        pl.BlockSpec(
                            (1, block_q, d),
                            lambda b, s, ki, qi, f, l, lv: (b, qi[s], 0)),
                        pl.BlockSpec(
                            (1, 1, block_q),
                            lambda b, s, ki, qi, f, l, lv: (b, 0, qi[s])),
                        pl.BlockSpec(
                            (1, 1, block_q),
                            lambda b, s, ki, qi, f, l, lv: (b, 0, qi[s])),
                        pl.BlockSpec(
                            (block_q, 128),
                            lambda b, s, ki, qi, f, l, lv: (qi[s], 0)),
                        pl.BlockSpec(
                            (8, block_k),
                            lambda b, s, ki, qi, f, l, lv: (0, ki[s])),
                    ],
                    out_specs=[
                        pl.BlockSpec(
                            (1, block_k, d),
                            lambda b, s, ki, qi, f, l, lv: (b, ki[s], 0)),
                        pl.BlockSpec(
                            (1, block_k, d),
                            lambda b, s, ki, qi, f, l, lv: (b, ki[s], 0)),
                    ],
                    scratch_shapes=[
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                    ],
                ),
                out_shape=[
                    jax.ShapeDtypeStruct(kp.shape, k.dtype),
                    jax.ShapeDtypeStruct(vp.shape, v.dtype),
                ],
                cost_estimate=_cost_estimate(
                    flops=8 * h * n_flat * block_q * block_k * d,
                    transcendentals=h * n_flat * block_q * block_k,
                    bytes_accessed=(2 * h * n_flat * (block_q + block_k)
                                    * d * it + 2 * h * tkp * d * it),
                    name="varlen.bwd_dkv"),
                interpret=_interpret(),
            )(ki_a, qi_a, first_a, last_a, live_a, qp, kp, vp, dop, lse3,
              delta3, cq2d, ck2d)

            # dQ rides the forward's q-major schedule (same bounds, same
            # hint): every q tile is presented, so no coverage fix needed
            lo_k, hi_k = _fwd_bounds(cu_q, cu_k, n_q, block_q, block_k, t,
                                     causal, self_attn)
            n_flat_q = n_q * _inner_steps(n_k, block_q, block_k,
                                          max_seqlen)
            if n_flat_hint is not None:
                n_flat_q = min(n_flat_q, n_flat_hint)
            qi_b, ki_b, first_b, last_b, live_b = _flat_schedule(
                lo_k, hi_k, n_q, n_flat_q)
            dq = pl.pallas_call(
                functools.partial(_bwd_dq_flat_kernel, causal=causal,
                                  scale=scale),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=5,
                    grid=(h, n_flat_q),
                    in_specs=[
                        pl.BlockSpec(
                            (1, block_q, d),
                            lambda b, s, qi, ki, f, l, lv: (b, qi[s], 0)),
                        pl.BlockSpec(
                            (1, block_k, d),
                            lambda b, s, qi, ki, f, l, lv: (b, ki[s], 0)),
                        pl.BlockSpec(
                            (1, block_k, d),
                            lambda b, s, qi, ki, f, l, lv: (b, ki[s], 0)),
                        pl.BlockSpec(
                            (1, block_q, d),
                            lambda b, s, qi, ki, f, l, lv: (b, qi[s], 0)),
                        pl.BlockSpec(
                            (1, 1, block_q),
                            lambda b, s, qi, ki, f, l, lv: (b, 0, qi[s])),
                        pl.BlockSpec(
                            (1, 1, block_q),
                            lambda b, s, qi, ki, f, l, lv: (b, 0, qi[s])),
                        pl.BlockSpec(
                            (block_q, 128),
                            lambda b, s, qi, ki, f, l, lv: (qi[s], 0)),
                        pl.BlockSpec(
                            (8, block_k),
                            lambda b, s, qi, ki, f, l, lv: (0, ki[s])),
                    ],
                    out_specs=pl.BlockSpec(
                        (1, block_q, d),
                        lambda b, s, qi, ki, f, l, lv: (b, qi[s], 0)),
                    scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
                ),
                out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
                cost_estimate=_cost_estimate(
                    flops=6 * h * n_flat_q * block_q * block_k * d,
                    transcendentals=h * n_flat_q * block_q * block_k,
                    bytes_accessed=(2 * h * n_flat_q * (block_q + block_k)
                                    * d * it + h * tp * d * it),
                    name="varlen.bwd_dq"),
                interpret=_interpret(),
            )(qi_b, ki_b, first_b, last_b, live_b, qp, kp, vp, dop, lse3,
              delta3, cq2d, ck2d)
    return dq[:, :t], dk[:, :tk], dv[:, :tk], None, None


_flash_varlen.defvjp(_flash_varlen_fwd, _flash_varlen_bwd)


def _host_bounds(cu_rows, cu_cols, n_tiles, block_rows, block_cols,
                 total_rows):
    """Pure-NUMPY mirror of _live_col_tiles: jnp ops issued during an
    enclosing trace are staged even on concrete inputs, so the wrapper's
    schedule sizing must not touch jnp."""
    import numpy as np
    i = np.arange(n_tiles)
    r0 = np.clip(i * block_rows, 0, total_rows - 1)
    r1 = np.clip((i + 1) * block_rows - 1, 0, total_rows - 1)
    seg0 = np.searchsorted(cu_rows, r0, side="right") - 1
    seg1 = np.searchsorted(cu_rows, r1, side="right") - 1
    lo = cu_cols[seg0] // block_cols
    hi = (np.maximum(cu_cols[seg1 + 1], cu_cols[seg1] + 1) - 1) // block_cols
    return lo, np.maximum(hi, lo)


def _host_schedule(cuq_np, cuk_np, tq, tk, bq, bk, causal, self_attn):
    """Live (q-tile, k-tile) pair counts for BOTH flat-grid orientations
    at a concrete cu: q-major (forward / split dQ, _fwd_bounds' causal
    diagonal clamp) and k-major (backward dK/dV + fused kernel,
    _bwd_bounds' diagonal start). Returns
    (n_live_fwd, n_live_bwd, n_q, n_k)."""
    import numpy as np
    n_q = -(-tq // bq)
    n_k = -(-tk // bk)
    lo, hi = _host_bounds(cuq_np, cuk_np, n_q, bq, bk, tq)
    if causal and self_attn:
        i = np.arange(n_q)
        hi = np.maximum(np.minimum(hi, ((i + 1) * bq - 1) // bk), lo)
    n_live_fwd = int(np.sum(hi - lo + 1))
    lo2, hi2 = _host_bounds(cuk_np, cuq_np, n_k, bk, bq, tk)
    if causal and self_attn:
        j = np.arange(n_k)
        lo2 = np.maximum(lo2, (j * bk) // bq)
        hi2 = np.maximum(hi2, lo2)
    n_live_bwd = int(np.sum(hi2 - lo2 + 1))
    return n_live_fwd, n_live_bwd, n_q, n_k


def _pow2_hint(n_live):
    """Flat-grid length for a measured live-pair count: next power of two
    (>= 8) so repacked batches of similar size reuse compiled programs."""
    h = 8
    while h < n_live:
        h *= 2
    return h


def _host_plan(cuq_np, cuk_np, tq, tk, h, d, itemsize, causal, self_attn,
               block_q, block_k, max_seqlen=None):
    """Concrete-cu kernel plan: stacked-path selection, fitted blocks,
    and per-orientation schedule sizes. `flat` is the grid the flat
    schedule actually runs (live count pow2-rounded, capped by the
    static bound); `rect` is what the old rectangular grid would have
    burned — the gap is all dead steps.

    Short-segment packs (mean segment < 1024 tokens) at the DEFAULT
    blocks go to the rows-stacked head-fused tiling: small tiles cut the
    dead-area waste of 1024^2 tiles quadratically, and stacking pays the
    serial softmax-chain latency once per chunk instead of once per
    (chunk, head). The stacked kernel must also FIT scoped VMEM at this
    dtype (f32 doubles the block bytes — advisor r4: nh=8 f32 was a
    compile-time OOM) and needs >= 2 fused heads to amortize anything.
    Callers passing EXPLICIT block sizes keep the streaming kernel with
    exactly those blocks (tuning stays honored)."""
    stacked = False
    if self_attn and len(cuq_np) > 1 \
            and (block_q, block_k) == (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K):
        mean_seg = tq / (len(cuq_np) - 1)
        nh_fit = _stacked_nh(h, itemsize, d,
                             _fit_block(STACKED_BLOCK_Q, tq),
                             _fit_block(STACKED_BLOCK_K, tk))
        stacked = bool(mean_seg < 1024) and nh_fit >= 2
    if stacked:
        bq = _fit_block(STACKED_BLOCK_Q, tq)
        bk = _fit_block(STACKED_BLOCK_K, tk)
    else:
        bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    live_fwd, live_bwd, n_q, n_k = _host_schedule(
        cuq_np, cuk_np, tq, tk, bq, bk, causal, self_attn)
    if not self_attn:
        max_seqlen = None  # see _inner_steps
    rect_fwd = n_q * _inner_steps(n_k, bq, bk, max_seqlen)
    rect_bwd = n_k * _inner_steps(n_q, bk, bq, max_seqlen)
    return {
        "stacked": stacked,
        "block_q": int(bq),
        "block_k": int(bk),
        "fwd": {"live": live_fwd, "rect": int(rect_fwd),
                "flat": int(min(_pow2_hint(live_fwd), rect_fwd)),
                "flat_hint": _pow2_hint(live_fwd)},
        "bwd": {"live": live_bwd, "rect": int(rect_bwd),
                "flat": int(min(_pow2_hint(live_bwd), rect_bwd)),
                "flat_hint": _pow2_hint(live_bwd)},
    }


def varlen_schedule_stats(cu_q, cu_k, heads, head_dim, *, causal,
                          self_attn=True, dtype=jnp.bfloat16,
                          block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                          max_seqlen=None):
    """Dead-vs-live grid-step accounting for a concrete pack: what the
    flat live-tile schedule runs vs what the rectangular grids burned.
    All values are plain ints/bools (JSON-ready — bench.py records this
    in BENCH_DETAIL.json)."""
    import numpy as np
    cuq_np = np.asarray(cu_q)  # noqa: PTA006 -- bench/telemetry helper on concrete cu, outside any step
    cuk_np = cuq_np if self_attn else np.asarray(cu_k)  # noqa: PTA006 -- bench/telemetry helper on concrete cu, outside any step
    tq, tk = int(cuq_np[-1]), int(cuk_np[-1])
    plan = _host_plan(cuq_np, cuk_np, tq, tk, heads, head_dim,
                      jnp.dtype(dtype).itemsize, causal, self_attn,
                      block_q, block_k,
                      int(max_seqlen) if max_seqlen else None)
    out = {"stacked": bool(plan["stacked"]),
           "block_q": plan["block_q"], "block_k": plan["block_k"]}
    for pss in ("fwd", "bwd"):
        p = plan[pss]
        out[pss] = {"live_tiles": p["live"],
                    "flat_steps": p["flat"],
                    "rect_steps": p["rect"],
                    "dead_steps_flat": p["flat"] - p["live"],
                    "dead_steps_rect": p["rect"] - p["live"]}
    return out


def flash_varlen_attention(q, k, v, cu_seqlens_q, cu_seqlens_k, scale,
                           causal, self_attn=None,
                           block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                           max_seqlen=None):
    """Kernel-backed packed varlen attention.

    q: [total_q, H, D]; k/v: [total_k, Hkv, D] (GQA repeats kv heads);
    cu_seqlens_*: [B+1] i32 cumulative offsets. Returns [total_q, H, D].
    self_attn=True (auto-detected from object identity of the cu arrays)
    additionally skips DMA/compute of above-diagonal tiles under causal.
    """
    if self_attn is None:
        self_attn = cu_seqlens_q is cu_seqlens_k
    tq, h, d = q.shape
    tk = k.shape[0]
    hkv = k.shape[1]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    cu_q = cu_seqlens_q.astype(jnp.int32)
    cu_k = cu_q if self_attn else cu_seqlens_k.astype(jnp.int32)
    if max_seqlen and self_attn:
        # a lying max_seqlen silently shrinks the live-tile span bound
        # (_inner_steps) below real segments → wrong output. Validate on
        # the host when cu is concrete (the common eager path); under a
        # trace fall back to the always-sound full inner grid. Cross-attn
        # already ignores max_seqlen (span bound unsound there).
        import jax.core as _jc
        concrete = not isinstance(cu_q, _jc.Tracer)
        if concrete:
            import numpy as _np
            longest = int(_np.max(_np.diff(_np.asarray(cu_q))))  # noqa: PTA006 -- guarded to concrete (non-tracer) cu only
            if longest > int(max_seqlen):
                raise ValueError(
                    f"flash_varlen_attention: max_seqlen={int(max_seqlen)} "
                    f"is smaller than the longest packed segment "
                    f"({longest}); the static live-tile bound would skip "
                    f"live tiles and produce wrong attention output")
        else:
            max_seqlen = None
    n_flat_hint = None
    n_flat_bwd_hint = None
    stacked = False
    if not isinstance(cu_q, jax.core.Tracer) \
            and not isinstance(cu_k, jax.core.Tracer):
        # cu concrete here (it becomes a tracer at the custom_vjp
        # boundary): measure the actual live-pair counts so BOTH flat
        # grids (forward q-major, backward k-major) are sized to the
        # work, not the worst-case static bound — the grid's ~1.3 µs
        # fixed cost per step is what dominates short-sequence packs,
        # and the static bound is ~4x over-provisioned for them.
        import numpy as np
        plan = _host_plan(np.asarray(cu_q), np.asarray(cu_k), tq, tk, h, d,  # noqa: PTA006 -- flat schedule is planned on host from concrete cu
                          jnp.dtype(q.dtype).itemsize, causal,
                          bool(self_attn), block_q, block_k,
                          int(max_seqlen) if max_seqlen else None)
        stacked = plan["stacked"]
        n_flat_hint = plan["fwd"]["flat_hint"]
        n_flat_bwd_hint = plan["bwd"]["flat_hint"]
    qh = q.transpose(1, 0, 2)
    kh = k.transpose(1, 0, 2)
    vh = v.transpose(1, 0, 2)
    o = _flash_varlen(qh, kh, vh, cu_q, cu_k, causal, float(scale),
                      block_q, block_k, bool(self_attn),
                      int(max_seqlen) if max_seqlen else None, n_flat_hint,
                      stacked, n_flat_bwd_hint)
    return o.transpose(1, 0, 2)
