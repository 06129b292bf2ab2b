"""Ragged paged decode attention over a block-table KV pool.

The serving engine (paddle_tpu/inference/) keeps the KV cache as a pool
of fixed-size blocks [L, NP, KVD, block_size] plus per-sequence int32
block tables — the vLLM PagedAttention layout (Kwon et al., SOSP '23)
restated for TPU static shapes. The kernel walks a FLAT schedule of
live (sequence, block) pairs built host/trace-side with the same
cumsum + searchsorted group-boundary trick as grouped_matmul's
tile_schedule: dead table slots are never stepped, dead grid steps
re-present the last live block index so Mosaic elides their DMA, and
all per-step bounds arrive via SMEM scalar prefetch.

Numerics contract (see PARITY.md): the kernel runs the EXACT op
sequence of decode_attention._kernel per sequence — tile-0-anchored
exp2 softmax (or the PADDLE_TPU_FLASH_SOFTMAX=online recurrence),
q PRE-SCALED by scale*log2(e), finalize acc / max(l, 1e-30) — so at
B=1 with block_size == the slab kernel's T tile (128) the output is
BITWISE-equal to decode_attention_slab on a contiguous layout, and a
fragmented block table is bitwise-equal to a contiguous one at any
batch (the schedule changes only WHERE a block lives, never the op
order).

paged_attend_update fuses the new token's KV write into the walk (the
pool aliases through the custom call, mirroring
decode_attend_update_slab): the schedule is built over len+1 positions
so the newest block is always the sequence's last live tile, the new
column is merged there, and that step's scores read the just-written
tile back from the aliased out refs.

paged_prefill_attention (PR 29) is the chunked prefill's read of the same
pools: one sequence, a chunk of queries, causal, a grid of (query tile,
table slot) bounded by the live context; see its section below.

Layouts:
  q_bd    [B, NH, KVD]          pre-scaled block-diagonal queries
  pools   [L, NP, KVD, bs]      k and v block pools, time in lanes
  tables  [B, max_nb] int32     pool block ids per sequence slot
  lengths [B] int32             live tokens (read path) / positions [B]
                                of the NEW token (update path)
Block 0 of the pool is reserved as a null block by the engine: padding
rows point every table slot at it, so their (masked) garbage never
lands in a live block.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import cost_estimate as _cost_estimate
from ._common import interpret_mode as _interpret
from ._common import mosaic_trace_ctx as _mosaic_ctx
from .flash_attention import softmax_mode

_LOG2E = 1.4426950408889634

# sched row indices (one [N_FIELDS, n_steps] i32 scalar-prefetch array)
_SEQ, _BLK, _START, _FIRST, _LAST, _LIVE, _POS, _COL, _UBLK = range(9)
N_FIELDS = 9


def _column_tile(row, block_size):
    """One new KV column as a [KVD, bs] f32 tile carrying it in EVERY
    lane: row [1, KVD] arrives with KVD in lanes (how XLA produces a
    token's k/v), the pool tile wants KVD in sublanes, so broadcast the
    row down bs sublanes and transpose — pure data movement, exact. The
    operand stays a lane-major [.., 1, KVD] array whose (1, KVD) block
    is the whole of its last two dims (what the Mosaic lowering asks
    of a block shape); a [.., KVD, 1] operand would lower too but pads
    every column to 128 lanes in HBM."""
    row = row.astype(jnp.float32)
    return jnp.broadcast_to(row, (block_size, row.shape[1])).T


def paged_schedule(lengths, tables, n_steps, block_size):
    """Flat live-block schedule: [N_FIELDS, n_steps] i32.

    lengths [B] live token counts (a 0 row is skipped entirely),
    tables [B, max_nb]. Walks sequence s's ceil(lengths[s]/block_size)
    blocks in table order; steps past the live total repeat the LAST
    live step's (seq, blk) so their block windows re-present unchanged
    indices and Mosaic skips the copy — the grouped_matmul
    tile_schedule trick, keyed by sequence instead of expert. Works on
    traced values (pure jnp)."""
    B, max_nb = tables.shape
    bs = jnp.int32(block_size)
    lens = jnp.maximum(lengths.astype(jnp.int32), 0)
    counts = (lens + bs - 1) // bs
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    total = offsets[-1]
    step = jnp.arange(n_steps, dtype=jnp.int32)
    # clamp flat index so dead steps REPLAY the final live step exactly
    fs = jnp.minimum(step, jnp.maximum(total - 1, 0))
    seq = jnp.clip(jnp.searchsorted(offsets, fs, side="right") - 1,
                   0, B - 1).astype(jnp.int32)
    inner = fs - offsets[seq]
    blk = tables[seq, jnp.clip(inner, 0, max_nb - 1)].astype(jnp.int32)
    live = (step < total).astype(jnp.int32)
    first = ((inner == 0) & (step < total)).astype(jnp.int32)
    last = ((fs == offsets[seq + 1] - 1) & (step < total)).astype(jnp.int32)
    pos = lens[seq] - 1
    last_slot = jnp.clip((lens[seq] - 1) // bs, 0, max_nb - 1)
    col = pos - ((lens[seq] - 1) // bs) * bs
    ublk = tables[seq, last_slot].astype(jnp.int32)
    return jnp.stack([seq, blk, inner * bs, first, last, live,
                      pos, col, ublk])


def paged_schedule_stats(lengths, tables, n_steps, block_size):
    """Host-side occupancy of a schedule: dict with live/dead step
    counts and the pool-block touch count (telemetry + bench)."""
    import numpy as np
    lens = np.maximum(np.asarray(lengths, np.int64), 0)  # noqa: PTA006 -- host-side schedule stats for telemetry, not a step path
    counts = (lens + block_size - 1) // block_size
    total = int(counts.sum())
    return {"n_steps": int(n_steps), "live_steps": min(total, int(n_steps)),
            "dead_steps": max(int(n_steps) - total, 0),
            "overflow_steps": max(total - int(n_steps), 0)}


def _paged_kernel(lp_ref, sc_ref, q_ref, k_ref, v_ref, o_ref,
                  l_s, b_s, acc_s, *, block_size, online=False):
    j = pl.program_id(0)
    pos = sc_ref[_POS, j]
    start = sc_ref[_START, j]

    def scores():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [NH, bs]
        t = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(t <= pos, s, jnp.float32(-1e30))

    def pv(p):
        return jax.lax.dot_general(
            p, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [NH, KVD]

    @pl.when(sc_ref[_FIRST, j] == np.int32(1))
    def _first():
        s = scores()
        base = s.max(axis=-1, keepdims=True)
        p = jnp.exp2(s - base)
        b_s[...] = jnp.broadcast_to(base, b_s.shape)
        l_s[...] = jnp.broadcast_to(p.sum(axis=-1, keepdims=True),
                                    l_s.shape)
        acc_s[...] = pv(p.astype(v_ref.dtype))

    @pl.when(jnp.logical_and(sc_ref[_LIVE, j] == np.int32(1),
                             sc_ref[_FIRST, j] == np.int32(0)))
    def _more():
        s = scores()
        if online:
            m_prev = b_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.exp2(s - m_new)
            b_s[...] = jnp.broadcast_to(m_new, b_s.shape)
            l_s[...] = l_s[...] * alpha + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] * alpha + pv(p.astype(v_ref.dtype))
        else:
            p = jnp.exp2(s - b_s[:, :1])
            l_s[...] = l_s[...] + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] + pv(p.astype(v_ref.dtype))

    @pl.when(sc_ref[_LAST, j] == np.int32(1))
    def _fin():
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[:, :1], jnp.float32(1e-30))


def paged_attention(q_bd, k_pool, v_pool, tables, lengths, layer, *,
                    n_steps=None):
    """Read-only paged decode attention for one layer.

    q_bd [B, NH, KVD] PRE-SCALED by scale*log2(e); pools
    [L, NP, KVD, bs]; tables [B, max_nb] i32; lengths [B] i32 live
    token counts (every attended row must have lengths >= 1 — a 0 row
    is skipped and its output left unwritten). Returns [B, NH, KVD]
    f32. n_steps defaults to B * max_nb (the worst case); pass the
    engine's bucketed bound to shrink the grid."""
    b, nh, kvd = q_bd.shape
    L, NP, _, bs = k_pool.shape
    B, max_nb = tables.shape
    if n_steps is None:
        n_steps = B * max_nb
    it = jnp.dtype(k_pool.dtype).itemsize
    sched = paged_schedule(lengths, tables, n_steps, bs)
    lp = jnp.asarray([layer], jnp.int32)

    def kv_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    kernel = functools.partial(_paged_kernel, block_size=bs,
                               online=softmax_mode() == "online")
    with _mosaic_ctx():
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_steps,),
                in_specs=[
                    pl.BlockSpec((1, nh, kvd), q_map),
                    pl.BlockSpec((1, 1, kvd, bs), kv_map),
                    pl.BlockSpec((1, 1, kvd, bs), kv_map),
                ],
                out_specs=pl.BlockSpec((1, nh, kvd), q_map),
                scratch_shapes=[
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, kvd), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, nh, kvd), jnp.float32),
            cost_estimate=_cost_estimate(
                flops=4 * nh * kvd * bs * n_steps,
                transcendentals=nh * bs * n_steps,
                bytes_accessed=2 * kvd * bs * it * n_steps,
                name="paged.attention"),
            interpret=_interpret(),
        )(lp, sched, q_bd, k_pool, v_pool)
    return out


def _paged_update_kernel(lp_ref, sc_ref, q_ref, nk_ref, nv_ref,
                         k_ref, v_ref, o_ref, ko_ref, vo_ref,
                         l_s, b_s, acc_s, *, block_size, online=False):
    j = pl.program_id(0)
    pos = sc_ref[_POS, j]
    start = sc_ref[_START, j]
    col = sc_ref[_COL, j]
    first = sc_ref[_FIRST, j] == np.int32(1)
    upd = sc_ref[_LAST, j] == np.int32(1)   # the new token's block IS the last
    kvd = q_ref.shape[2]
    lane = lax.broadcasted_iota(jnp.int32, (kvd, block_size), 1)

    def merged(tile_ref, new_ref):
        # minor-dim insert goes through f32 (Mosaic bf16 limitation,
        # same as decode_attention._kernel_update)
        return jnp.where(lane == col, _column_tile(new_ref[0], block_size),
                         tile_ref[0, 0].astype(jnp.float32)) \
            .astype(tile_ref.dtype)

    @pl.when(upd)
    def _write_cache():
        # full tile written every update step: the aliased out window
        # starts uninitialized, so every lane must be defined before
        # the flush at the next sequence boundary
        ko_ref[0, 0] = merged(k_ref, nk_ref)
        vo_ref[0, 0] = merged(v_ref, nv_ref)

    def chain(k_at, v_at, is_first):
        s = jax.lax.dot_general(
            q_ref[0], k_at, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [NH, bs]
        t = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t <= pos, s, jnp.float32(-1e30))
        alpha = None
        if is_first:
            bvec = s.max(axis=-1, keepdims=True)
            b_s[...] = jnp.broadcast_to(bvec, b_s.shape)
        elif online:
            m_prev = b_s[:, :1]
            bvec = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - bvec)
            b_s[...] = jnp.broadcast_to(bvec, b_s.shape)
        else:
            bvec = b_s[:, :1]
        p = jnp.exp2(s - bvec)
        psum = jnp.broadcast_to(p.sum(axis=-1, keepdims=True), l_s.shape)
        d = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_at, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if is_first:
            l_s[...] = psum
            acc_s[...] = d
        elif online:
            l_s[...] = l_s[...] * alpha + psum
            acc_s[...] = acc_s[...] * alpha + d
        else:
            l_s[...] = l_s[...] + psum
            acc_s[...] = acc_s[...] + d

    # 4-way branch: (first tile?) x (update tile?) — the update tile
    # reads the just-merged slabs back from the aliased out refs
    @pl.when(jnp.logical_and(first, upd))
    def _first_updated():
        chain(ko_ref[0, 0], vo_ref[0, 0], True)

    @pl.when(jnp.logical_and(first, jnp.logical_not(upd)))
    def _first_raw():
        chain(k_ref[0, 0], v_ref[0, 0], True)

    @pl.when(jnp.logical_and(jnp.logical_not(first), upd))
    def _more_updated():
        chain(ko_ref[0, 0], vo_ref[0, 0], False)

    @pl.when(jnp.logical_and(
            jnp.logical_not(first),
            jnp.logical_and(sc_ref[_LIVE, j] == np.int32(1), jnp.logical_not(upd))))
    def _more_raw():
        chain(k_ref[0, 0], v_ref[0, 0], False)

    @pl.when(sc_ref[_LAST, j] == np.int32(1))
    def _fin():
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[:, :1], jnp.float32(1e-30))


def paged_update_walk(tables, positions, block_size):
    """What the fused update kernels walk for one decode batch, the same in
    every layer, so a step makes it once before its layer loop: (the flat
    schedule over every slot of every table, its live total as a traced
    i32). tables [B, max_nb] i32; positions [B] i32 = the NEW token's
    position per row (its block must already be in the table). The
    schedule runs over len+1, so the written position's block is the
    walk's last live tile even when it was freshly allocated; the kernels'
    grid ends at the live total (a dynamic grid bound: steps past it would
    replay the last live one and move nothing, at about a quarter of a
    microsecond each)."""
    B, max_nb = tables.shape
    sched = paged_schedule(positions + 1, tables, B * max_nb, block_size)
    return sched, jnp.sum(sched[_LIVE], dtype=jnp.int32)


def paged_attend_update(q_bd, new_k, new_v, k_pool, v_pool, walk, layer):
    """Fused pool-update + paged attention for one decode layer: writes
    each sequence's new k/v column IN PLACE (the pools alias through
    the custom call) and attends over the prefix INCLUDING it.

    q_bd [B, NH, KVD] pre-scaled; new_k/new_v [B, KVD]; ``walk`` the
    batch's ``paged_update_walk``. Every row writes — padding rows must
    point their tables at the reserved null block 0 with positions 0.
    Returns (attn [B, NH, KVD] f32, k_pool, v_pool)."""
    b, nh, kvd = q_bd.shape
    bs = k_pool.shape[-1]
    it = jnp.dtype(k_pool.dtype).itemsize
    sched, live_steps = walk
    n_steps = sched.shape[1]        # the cost estimate's worst case
    lp = jnp.asarray([layer], jnp.int32)

    def kv_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    def new_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    def upd_map(j, lp_ref, sc_ref):
        # constant per sequence: the block holding the new column; the
        # buffer is fully written on the seq's last live step, then
        # flushes when the presented index moves to the next sequence
        return (lp_ref[0], sc_ref[_UBLK, j], 0, 0)

    kernel = functools.partial(_paged_update_kernel, block_size=bs,
                               online=softmax_mode() == "online")
    with _mosaic_ctx():
        out, kp, vp = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(live_steps,),
                in_specs=[
                    pl.BlockSpec((1, nh, kvd), q_map),
                    pl.BlockSpec((1, 1, kvd), new_map),
                    pl.BlockSpec((1, 1, kvd), new_map),
                    pl.BlockSpec((1, 1, kvd, bs), kv_map),
                    pl.BlockSpec((1, 1, kvd, bs), kv_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, nh, kvd), q_map),
                    pl.BlockSpec((1, 1, kvd, bs), upd_map),
                    pl.BlockSpec((1, 1, kvd, bs), upd_map),
                ],
                scratch_shapes=[
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, kvd), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, nh, kvd), jnp.float32),
                jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
            ],
            # operand indices count scalar-prefetch first: 0=lp,
            # 1=sched, 2=q, 3=new_k, 4=new_v, 5=k_pool, 6=v_pool
            input_output_aliases={5: 1, 6: 2},
            cost_estimate=_cost_estimate(
                flops=4 * nh * kvd * bs * n_steps,
                transcendentals=nh * bs * n_steps,
                bytes_accessed=(2 * kvd * bs * it * n_steps
                                + 4 * b * kvd * bs * it),
                name="paged.attend_update"),
            interpret=_interpret(),
        )(lp, sched, q_bd, new_k[:, None], new_v[:, None], k_pool, v_pool)
    return out, kp, vp


# -- int8 paged KV (PR 16) ----------------------------------------------------
#
# Storage halves to one byte per cached element, with f32 scales at
# per-block / per-kv-head / per-COLUMN granularity ([L, NP, NKV, bs]).
# Per-column scales are the load-bearing choice: every column is
# quantized exactly once, from its own fp values, by the same helper on
# both the prefill-scatter and decode-update paths — so the cache BYTES
# are a pure function of the token prefix, independent of chunk
# grouping or prefill-vs-decode history. That is what keeps prefix-hit
# reuse and journal recovery bit-identical with int8 on (PARITY.md).
# Conventions follow quantization/quanters.py: qmax = 2^(b-1)-1 = 127,
# scale floor 1e-8.

KV_QMAX = 127.0
KV_SCALE_FLOOR = 1e-8

# double-buffered window budget for the paged kernels' fitter: one
# TPU core's scoped VMEM (pallas guide) — far under PTA002's 64 MiB
# static ceiling, because these windows must ALSO leave room for the
# decode batch's other kernels resident in the same step
PAGED_VMEM_BUDGET = 16 * 1024 * 1024


def kv_quant_columns(x, nkv):
    """Symmetric per-column-per-kv-head int8 quantization of KV columns.

    x [N, KVD] fp values (KVD = nkv * hd) -> (q int8 [N, KVD],
    scales f32 [N, NKV]) with scale = max(absmax/127, 1e-8) over each
    column's hd-slice — the quantization/ absmax convention. The ONLY
    quantizer for paged KV bytes: prefill scatter and decode update
    both route through it, so identical fp columns always produce
    identical int8 bytes + scales."""
    n, kvd = x.shape
    hd = kvd // int(nkv)
    xf = x.astype(jnp.float32).reshape(n, int(nkv), hd)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / KV_QMAX,
                    KV_SCALE_FLOOR)                        # [N, nkv]
    q = jnp.clip(jnp.round(xf / s[:, :, None]), -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8).reshape(n, kvd), s


def _fit_paged_kv_blocks(nh, kvd, nkv, bs, itemsize):
    """Window fitter for the quantized paged kernels (PTA002 contract).

    Block geometry is pinned by the pool layout — block_size IS the
    allocator's unit and KVD the model's — so unlike _fit_block_t this
    fitter sizes nothing; it PRICES the per-step double-buffered
    windows (q + int8 k/v tiles + f32 scale tiles + outputs + scratch)
    and fails at trace time if a configuration could not fit, instead
    of compile-failing only on hardware. Returns (kvd, bs, nkv)
    unchanged.

    Under tensor-parallel serving (PR 19) this fitter runs INSIDE the
    shard_map island, so nh/nkv here are the per-rank head counts
    (NH/mp, NKV/mp) read off the rank's pool slice — per-shard window
    budgets fall out of the argument shapes with no TP-specific fitter
    code, and a geometry that only fits when sharded is accepted
    exactly when the sharded kernel actually runs."""
    win = (2 * nh * kvd * 4                 # q window (f32-priced)
           + 2 * 2 * kvd * bs * itemsize    # k/v tiles
           + 2 * 2 * nkv * bs * 4           # scale tiles
           + 2 * nh * kvd * 4               # attn out
           + 2 * 2 * (kvd * bs * itemsize + nkv * bs * 4)  # aliased outs
           + 2 * nh * 128 * 4 + nh * kvd * 4)              # scratch
    if win > PAGED_VMEM_BUDGET:
        raise ValueError(
            f"paged int8 kernel windows need {win} B VMEM "
            f"(> {PAGED_VMEM_BUDGET} B): shrink block_size or heads")
    return kvd, bs, nkv


def _dequant_tile(tile, scale, nkv):
    """Fused in-kernel dequant of one [KVD, bs] int8 tile with its
    [NKV, bs] f32 per-column scales: expand scales across each head's
    hd rows. Reshape-based broadcast (per-head row grouping); runs in
    interpret mode and lowers to a relayout+mul on Mosaic."""
    kvd, bs = tile.shape
    hd = kvd // nkv
    return (tile.astype(jnp.float32).reshape(nkv, hd, bs)
            * scale[:, None, :]).reshape(kvd, bs)


def _paged_quant_kernel(lp_ref, sc_ref, q_ref, k_ref, v_ref, ks_ref,
                        vs_ref, o_ref, l_s, b_s, acc_s, *, block_size,
                        nkv, online=False):
    """_paged_kernel with int8 tiles: identical op chain, except k/v
    dequantize in-register before the dots (p stays f32 — there is no
    low-precision v to cast to)."""
    j = pl.program_id(0)
    pos = sc_ref[_POS, j]
    start = sc_ref[_START, j]

    def scores():
        k_deq = _dequant_tile(k_ref[0, 0], ks_ref[0, 0], nkv)
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_deq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [NH, bs]
        t = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(t <= pos, s, jnp.float32(-1e30))

    def pv(p):
        v_deq = _dequant_tile(v_ref[0, 0], vs_ref[0, 0], nkv)
        return jax.lax.dot_general(
            p, v_deq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [NH, KVD]

    @pl.when(sc_ref[_FIRST, j] == np.int32(1))
    def _first():
        s = scores()
        base = s.max(axis=-1, keepdims=True)
        p = jnp.exp2(s - base)
        b_s[...] = jnp.broadcast_to(base, b_s.shape)
        l_s[...] = jnp.broadcast_to(p.sum(axis=-1, keepdims=True),
                                    l_s.shape)
        acc_s[...] = pv(p)

    @pl.when(jnp.logical_and(sc_ref[_LIVE, j] == np.int32(1),
                             sc_ref[_FIRST, j] == np.int32(0)))
    def _more():
        s = scores()
        if online:
            m_prev = b_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.exp2(s - m_new)
            b_s[...] = jnp.broadcast_to(m_new, b_s.shape)
            l_s[...] = l_s[...] * alpha + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] * alpha + pv(p)
        else:
            p = jnp.exp2(s - b_s[:, :1])
            l_s[...] = l_s[...] + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] + pv(p)

    @pl.when(sc_ref[_LAST, j] == np.int32(1))
    def _fin():
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[:, :1], jnp.float32(1e-30))


def paged_attention_quant(q_bd, k_pool, v_pool, k_scale, v_scale,
                          tables, lengths, layer, *, n_steps=None):
    """Read-only paged decode attention over an int8 pool with fused
    per-column dequant. Same contract as :func:`paged_attention`, plus
    scale pools [L, NP, NKV, bs] f32 riding their own (tiny) windows
    down the same flat schedule."""
    b, nh, kvd = q_bd.shape
    L, NP, _, bs = k_pool.shape
    nkv = k_scale.shape[2]
    B, max_nb = tables.shape
    if n_steps is None:
        n_steps = B * max_nb
    it = jnp.dtype(k_pool.dtype).itemsize
    kvd_b, bs_b, nkv_b = _fit_paged_kv_blocks(nh, kvd, nkv, bs, it)
    sched = paged_schedule(lengths, tables, n_steps, bs)
    lp = jnp.asarray([layer], jnp.int32)

    def kv_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    kernel = functools.partial(_paged_quant_kernel, block_size=bs,
                               nkv=nkv, online=softmax_mode() == "online")
    with _mosaic_ctx():
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_steps,),
                in_specs=[
                    pl.BlockSpec((1, nh, kvd_b), q_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), kv_map),
                ],
                out_specs=pl.BlockSpec((1, nh, kvd_b), q_map),
                scratch_shapes=[
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, kvd), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, nh, kvd), jnp.float32),
            cost_estimate=_cost_estimate(
                flops=(4 * nh * kvd * bs + 2 * kvd * bs) * n_steps,
                transcendentals=nh * bs * n_steps,
                bytes_accessed=(2 * kvd * bs * it
                                + 2 * nkv * bs * 4) * n_steps,
                name="paged.attention_quant"),
            interpret=_interpret(),
        )(lp, sched, q_bd, k_pool, v_pool, k_scale, v_scale)
    return out


def _paged_update_quant_kernel(lp_ref, sc_ref, q_ref, nk_ref, nv_ref,
                               nks_ref, nvs_ref, k_ref, v_ref, ks_ref,
                               vs_ref, o_ref, ko_ref, vo_ref, kso_ref,
                               vso_ref, l_s, b_s, acc_s, *, block_size,
                               nkv, online=False):
    """_paged_update_kernel over int8 tiles + f32 scale tiles. The new
    column arrives ALREADY quantized (kv_quant_columns outside the
    call, so decode writes the same bytes a prefill of the same tokens
    would); the kernel merges bytes + scale into the update tile and
    dequantizes whichever tile each step reads."""
    j = pl.program_id(0)
    pos = sc_ref[_POS, j]
    start = sc_ref[_START, j]
    col = sc_ref[_COL, j]
    first = sc_ref[_FIRST, j] == np.int32(1)
    upd = sc_ref[_LAST, j] == np.int32(1)
    kvd = q_ref.shape[2]
    lane = lax.broadcasted_iota(jnp.int32, (kvd, block_size), 1)
    lane_s = lax.broadcasted_iota(jnp.int32, (nkv, block_size), 1)

    @pl.when(upd)
    def _write_cache():
        # full tiles written every update step (the aliased out windows
        # start uninitialized); the int8 insert routes through f32 like
        # the fp16 kernel's minor-dim insert — exact for int8 values
        ko_ref[0, 0] = jnp.where(
            lane == col, _column_tile(nk_ref[0], block_size),
            k_ref[0, 0].astype(jnp.float32)).astype(jnp.int8)
        vo_ref[0, 0] = jnp.where(
            lane == col, _column_tile(nv_ref[0], block_size),
            v_ref[0, 0].astype(jnp.float32)).astype(jnp.int8)
        kso_ref[0, 0] = jnp.where(lane_s == col, nks_ref[0], ks_ref[0, 0])
        vso_ref[0, 0] = jnp.where(lane_s == col, nvs_ref[0], vs_ref[0, 0])

    def chain(k_at, v_at, ks_at, vs_at, is_first):
        k_deq = _dequant_tile(k_at, ks_at, nkv)
        v_deq = _dequant_tile(v_at, vs_at, nkv)
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_deq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [NH, bs]
        t = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t <= pos, s, jnp.float32(-1e30))
        alpha = None
        if is_first:
            bvec = s.max(axis=-1, keepdims=True)
            b_s[...] = jnp.broadcast_to(bvec, b_s.shape)
        elif online:
            m_prev = b_s[:, :1]
            bvec = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - bvec)
            b_s[...] = jnp.broadcast_to(bvec, b_s.shape)
        else:
            bvec = b_s[:, :1]
        p = jnp.exp2(s - bvec)
        psum = jnp.broadcast_to(p.sum(axis=-1, keepdims=True), l_s.shape)
        d = jax.lax.dot_general(
            p, v_deq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if is_first:
            l_s[...] = psum
            acc_s[...] = d
        elif online:
            l_s[...] = l_s[...] * alpha + psum
            acc_s[...] = acc_s[...] * alpha + d
        else:
            l_s[...] = l_s[...] + psum
            acc_s[...] = acc_s[...] + d

    @pl.when(jnp.logical_and(first, upd))
    def _first_updated():
        chain(ko_ref[0, 0], vo_ref[0, 0], kso_ref[0, 0], vso_ref[0, 0],
              True)

    @pl.when(jnp.logical_and(first, jnp.logical_not(upd)))
    def _first_raw():
        chain(k_ref[0, 0], v_ref[0, 0], ks_ref[0, 0], vs_ref[0, 0], True)

    @pl.when(jnp.logical_and(jnp.logical_not(first), upd))
    def _more_updated():
        chain(ko_ref[0, 0], vo_ref[0, 0], kso_ref[0, 0], vso_ref[0, 0],
              False)

    @pl.when(jnp.logical_and(
            jnp.logical_not(first),
            jnp.logical_and(sc_ref[_LIVE, j] == np.int32(1),
                            jnp.logical_not(upd))))
    def _more_raw():
        chain(k_ref[0, 0], v_ref[0, 0], ks_ref[0, 0], vs_ref[0, 0], False)

    @pl.when(sc_ref[_LAST, j] == np.int32(1))
    def _fin():
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[:, :1], jnp.float32(1e-30))


def paged_attend_update_quant(q_bd, new_k, new_v, new_ks, new_vs,
                              k_pool, v_pool, k_scale, v_scale, walk,
                              layer):
    """Fused int8 pool-update + paged attention for one decode layer.

    Same contract as :func:`paged_attend_update`, except the pools are
    int8 with [L, NP, NKV, bs] f32 scale pools, and the new columns
    arrive pre-quantized: new_k/new_v int8 [B, KVD], new_ks/new_vs f32
    [B, NKV] from :func:`kv_quant_columns`. All four pools alias
    through the custom call. Returns (attn [B, NH, KVD] f32, k_pool,
    v_pool, k_scale, v_scale)."""
    b, nh, kvd = q_bd.shape
    bs = k_pool.shape[-1]
    nkv = k_scale.shape[2]
    it = jnp.dtype(k_pool.dtype).itemsize
    kvd_b, bs_b, nkv_b = _fit_paged_kv_blocks(nh, kvd, nkv, bs, it)
    sched, live_steps = walk
    n_steps = sched.shape[1]        # the cost estimate's worst case
    lp = jnp.asarray([layer], jnp.int32)

    def kv_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    def new_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    def upd_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_UBLK, j], 0, 0)

    kernel = functools.partial(_paged_update_quant_kernel, block_size=bs,
                               nkv=nkv, online=softmax_mode() == "online")
    with _mosaic_ctx():
        out, kp, vp, ks, vs = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(live_steps,),
                in_specs=[
                    pl.BlockSpec((1, nh, kvd_b), q_map),
                    pl.BlockSpec((1, 1, kvd_b), new_map),
                    pl.BlockSpec((1, 1, kvd_b), new_map),
                    pl.BlockSpec((1, nkv_b, 1), new_map),
                    pl.BlockSpec((1, nkv_b, 1), new_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), kv_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, nh, kvd_b), q_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), upd_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), upd_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), upd_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), upd_map),
                ],
                scratch_shapes=[
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, kvd), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, nh, kvd), jnp.float32),
                jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
                jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
            ],
            # operand indices count scalar-prefetch first: 0=lp,
            # 1=sched, 2=q, 3=new_k, 4=new_v, 5=new_ks, 6=new_vs,
            # 7=k_pool, 8=v_pool, 9=k_scale, 10=v_scale
            input_output_aliases={7: 1, 8: 2, 9: 3, 10: 4},
            cost_estimate=_cost_estimate(
                flops=(4 * nh * kvd * bs + 2 * kvd * bs) * n_steps,
                transcendentals=nh * bs * n_steps,
                bytes_accessed=((2 * kvd * bs * it + 2 * nkv * bs * 4)
                                * n_steps
                                + 4 * b * (kvd + nkv) * bs * it),
                name="paged.attend_update_quant"),
            interpret=_interpret(),
        )(lp, sched, q_bd, new_k[:, None], new_v[:, None],
          new_ks[:, :, None], new_vs[:, :, None],
          k_pool, v_pool, k_scale, v_scale)
    return out, kp, vp, ks, vs


def paged_attention_xla(q, k_pool, v_pool, tables, lengths, layer,
                        scale):
    """Plain-XLA reference: q [B, NH, KVD] UNSCALED, standard e-base
    softmax in f32. Gathers each table's blocks into a contiguous
    [B, KVD, max_nb*bs] view — the layout-parity oracle for the
    kernels (allclose, not bitwise: different exponent base)."""
    B, max_nb = tables.shape
    bs = k_pool.shape[-1]
    kc = jnp.transpose(k_pool[layer][tables], (0, 2, 1, 3)) \
        .reshape(B, k_pool.shape[2], max_nb * bs)
    vc = jnp.transpose(v_pool[layer][tables], (0, 2, 1, 3)) \
        .reshape(B, v_pool.shape[2], max_nb * bs)
    s = jnp.einsum("bhc,bct->bht", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale
    t = jnp.arange(max_nb * bs)[None, None, :]
    s = jnp.where(t < lengths[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,bct->bhc", p, vc.astype(jnp.float32))


# -- chunked prefill (PR 29) --------------------------------------------------
#
# One sequence's prefill chunk attends its LIVE context through the block
# table: queries [C, NH*HD] at positions start .. start + C, keys and values
# read from the pools after the chunk's own columns have landed there. The
# grid is (query tile, table slot). A tile of block_q query tokens walks the
# blocks up to its own causal frontier, all kv heads a step (the k/v window
# is the pool's whole [KVD, bs] block tile, as in the decode kernels), with
# the rep = NH / NKV query heads of a kv head stacked into the rows of one
# [rep * block_q, HD] x [HD, bs] product. Running max / sum / accumulator
# stay in VMEM: no score reaches HBM. Slots past a tile's frontier re-present
# its last live block (no DMA) and skip their compute; a tile of padding
# queries alone (past n_live) visits nothing and writes zeros.

# tile sched row indices ([2, n_tiles] i32 scalar prefetch)
_TQ0, _TNBLK = range(2)

PREFILL_BLOCK_Q = 128
PREFILL_VMEM_LIMIT = 32 * 1024 * 1024


def _fit_paged_prefill_blocks(c, nh, hd, nkv, bs, itemsize):
    """Query tile of the prefill attention (PTA002 contract): the largest
    divisor of the chunk ``c`` that is at most PREFILL_BLOCK_Q and a whole
    number of sublane tiles (or the chunk itself), priced with its
    double-buffered windows (q and out tiles, k/v block tiles, scale tiles)
    and its scratch (accumulator, running max and sum for every head)
    against the kernel's VMEM limit; fails at trace time where they could
    not fit. Under tensor-parallel serving nh/nkv are the rank's own."""
    tq = next((d for d in range(min(c, PREFILL_BLOCK_Q), 0, -1)
               if c % d == 0 and d % 16 == 0), c)
    kvd = nkv * hd
    win = (2 * 2 * tq * nh * hd * 4               # q + out tiles (f32-priced)
           + 2 * 2 * kvd * bs * itemsize          # k/v tiles
           + 2 * 2 * nkv * bs * 4                 # scale tiles (quant path)
           + nh * tq * (hd + 2 * 128) * 4)        # acc/m/l scratch
    if win > PREFILL_VMEM_LIMIT:
        raise ValueError(
            f"paged prefill kernel windows need {win} B VMEM "
            f"(> {PREFILL_VMEM_LIMIT} B): shrink prefill_chunk, block_size "
            f"or heads")
    return tq


def paged_prefill_schedule(table_row, start, n_live, n_tiles, block_q,
                           block_size):
    """(blk [n_tiles, max_nb], tiles [2, n_tiles]) i32 for one
    chunk: tile i holds the queries at positions q0 = start + i * block_q
    onward and walks table slots 0 .. nblk - 1, up to the block of its last
    live query (nblk = 0 for a tile wholly past n_live). blk[i, j] is the
    pool block that step presents: the slot's own while j < nblk, then the
    last live one again, so a dead step moves no data. Pure jnp on traced
    values; the same for every layer of the chunk."""
    max_nb = table_row.shape[0]
    bs, tq = jnp.int32(block_size), jnp.int32(block_q)
    start = jnp.asarray(start, jnp.int32)
    n_live = jnp.asarray(n_live, jnp.int32)
    i = jnp.arange(n_tiles, dtype=jnp.int32)
    q0 = start + i * tq
    last = jnp.minimum(q0 + tq, start + n_live) - 1
    nblk = jnp.where(i * tq < n_live, last // bs + 1, jnp.int32(0))
    j = jnp.arange(max_nb, dtype=jnp.int32)
    slot = jnp.clip(jnp.minimum(j[None, :], nblk[:, None] - 1), 0,
                    max_nb - 1)
    return table_row.astype(jnp.int32)[slot], jnp.stack([q0, nblk])


def _paged_prefill_kernel(lp_ref, blk_ref, tl_ref, q_ref, k_ref, v_ref,
                          *rest, block_size, nkv, sm_scale, quant):
    """Online-softmax chain of one (query tile, table slot) step over all
    kv heads. int8 pools differ only in dequantising the head's tile (to the
    queries' dtype, as the dense path's gather did)."""
    ks_ref, vs_ref = rest[:-4] if quant else (None, None)
    o_ref, m_s, l_s, acc_s = rest[-4:]
    i, j = pl.program_id(0), pl.program_id(1)
    q0 = tl_ref[_TQ0, i]
    nblk = tl_ref[_TNBLK, i]
    tq = q_ref.shape[0]
    hd = k_ref.shape[2] // nkv
    rep = q_ref.shape[1] // (nkv * hd)
    bs = block_size

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, -1e30, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def tile(ref, s_ref, g):
        t = ref[0, 0, g * hd:(g + 1) * hd, :]
        if quant:
            t = (t.astype(jnp.float32) * s_ref[0, 0, g:g + 1, :]) \
                .astype(q_ref.dtype)
        return t

    def attend(masked):
        if masked:
            # row r of a head group is query r % tq of the tile
            qpos = q0 + jnp.concatenate(
                [lax.broadcasted_iota(jnp.int32, (tq, bs), 0)] * rep, axis=0)
            t = j * bs + lax.broadcasted_iota(jnp.int32, (rep * tq, bs), 1)
            keep = t <= qpos
        for g in range(nkv):
            qg = jnp.concatenate(
                [q_ref[:, (g * rep + h) * hd:(g * rep + h + 1) * hd]
                 for h in range(rep)], axis=0)             # [rep*tq, hd]
            s = jax.lax.dot_general(
                qg, tile(k_ref, ks_ref, g),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                s = jnp.where(keep, s, jnp.float32(-1e30))
            m_prev = m_s[g, :, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.exp2(s - m_new)
            v_g = tile(v_ref, vs_ref, g)
            m_s[g] = jnp.broadcast_to(m_new, m_s.shape[1:])
            l_s[g] = l_s[g] * alpha + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape[1:])
            acc_s[g] = acc_s[g] * alpha + jax.lax.dot_general(
                p.astype(v_g.dtype), v_g, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [rep*tq, hd]

    live = j < nblk
    # every column of the block is at or before the tile's first query
    whole = (j + 1) * bs - 1 <= q0

    @pl.when(jnp.logical_and(live, whole))
    def _below():
        attend(False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _frontier():
        attend(True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        for g in range(nkv):
            o = acc_s[g] / jnp.maximum(l_s[g, :, :1], jnp.float32(1e-30))
            for h in range(rep):
                o_ref[:, (g * rep + h) * hd:(g * rep + h + 1) * hd] = \
                    o[h * tq:(h + 1) * tq].astype(o_ref.dtype)


def paged_prefill_attention(q, k_pool, v_pool, table_row, start, n_live,
                            layer, *, kv_scales=None):
    """Causal attention of one sequence's prefill chunk over its live
    context, read from the pools through the block table.

    q [C, NH, HD] UNSCALED, the queries of the chunk's tokens at positions
    start .. start + C; pools [L, NP, KVD, bs] ALREADY holding the chunk's
    own columns; table_row [max_nb] i32; start, n_live traced scalars
    (n_live >= 1 real tokens, the rest padding). With
    ``kv_scales=(k_scale, v_scale)`` ([L, NP, NKV, bs] f32) the pools are
    int8. Returns [C, NH, HD] in q's dtype; rows past n_live are finite and
    meaningless. Softmax statistics are f32, the probabilities are cast to
    the values' dtype before PV, and the work is that of the live blocks:
    ceil((start + n_live) / bs) at most, fewer for the earlier tiles."""
    c, nh, hd = q.shape
    L, NP, kvd, bs = k_pool.shape
    max_nb = table_row.shape[0]
    nkv = kvd // hd          # from the shapes: under TP the rank's own
    quant = kv_scales is not None
    it = jnp.dtype(k_pool.dtype).itemsize
    tq = _fit_paged_prefill_blocks(c, nh, hd, nkv, bs, it)
    n_tiles = c // tq
    rows = (nh // nkv) * tq
    blk, tiles = paged_prefill_schedule(table_row, start, n_live, n_tiles,
                                        tq, bs)
    lp = jnp.asarray([layer], jnp.int32)

    def kv_map(i, j, lp_ref, blk_ref, tl_ref):
        return (lp_ref[0], blk_ref[i, j], 0, 0)

    def q_map(i, j, lp_ref, blk_ref, tl_ref):
        return (i, 0)

    pool_specs = [pl.BlockSpec((1, 1, kvd, bs), kv_map)] * 2
    if quant:
        pool_specs += [pl.BlockSpec((1, 1, nkv, bs), kv_map)] * 2
    kernel = functools.partial(
        _paged_prefill_kernel, block_size=bs, nkv=nkv,
        sm_scale=_LOG2E / (hd ** 0.5), quant=quant)
    steps = n_tiles * max_nb
    with _mosaic_ctx():
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_tiles, max_nb),
                in_specs=[pl.BlockSpec((tq, nh * hd), q_map)] + pool_specs,
                out_specs=pl.BlockSpec((tq, nh * hd), q_map),
                scratch_shapes=[
                    pltpu.VMEM((nkv, rows, 128), jnp.float32),
                    pltpu.VMEM((nkv, rows, 128), jnp.float32),
                    pltpu.VMEM((nkv, rows, hd), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((c, nh * hd), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=PREFILL_VMEM_LIMIT),
            # the whole table's steps, as the decode kernels price theirs;
            # a chunk runs its live ones
            cost_estimate=_cost_estimate(
                flops=4 * nh * tq * hd * bs * steps,
                transcendentals=nh * tq * bs * steps,
                bytes_accessed=((2 * kvd * bs * it
                                 + (2 * nkv * bs * 4 if quant else 0))
                                * steps
                                + 2 * c * nh * hd * q.dtype.itemsize),
                name="paged.prefill_attention"),
            interpret=_interpret(),
        )(lp, blk, tiles, q.reshape(c, nh * hd), k_pool, v_pool,
          *(kv_scales or ()))
    return out.reshape(c, nh, hd)


# -- speculative verification (PR 18) -----------------------------------------
#
# Greedy speculative decoding scores K+1 fed tokens per sequence in ONE
# pass: the kernel below attends every fed token's query rows over the
# CACHED prefix only (the unchanged flat schedule — fed tokens are not
# in the pool yet), and returns UNFINALIZED online-softmax partials
# (acc, m, l) so the caller can merge the fed-token attention — computed
# outside in XLA, where the tiny [T, T] causal block is cheap — exactly:
# rescale both partial sums to a common max and finalize once. The merge
# identity holds for the tile-0-anchored m just as for a true running
# max, so both PADDLE_TPU_FLASH_SOFTMAX modes verify bit-stably.
#
# Commit is a second fused kernel: scalar-prefetched per-sequence accept
# lengths redirect every rejected or dead column to the reserved null
# block 0 (the engine's scribble target), so ONLY accepted tokens' KV
# lands in live blocks — int8 columns arrive pre-quantized by
# kv_quant_columns, keeping committed bytes equal to what sequential
# decode would have written (PARITY.md).

# commit sched row indices ([N_COMMIT_FIELDS, L*B*T] i32)
_CL, _CB, _CCOL, _CFIRST, _CSEQ, _CT = range(6)
N_COMMIT_FIELDS = 6


def _fit_paged_verify_blocks(r, kvd, nkv, bs, itemsize):
    """Window fitter for the verification kernels (PTA002 contract).

    Like _fit_paged_kv_blocks the geometry is pinned by the pool layout;
    this prices the verify read's double-buffered windows — r = T*NH
    query rows instead of NH, plus the three partial outputs — and
    fails at trace time if they could not fit. Returns (kvd, bs, nkv)
    unchanged. Under tensor-parallel serving (PR 19) r and nkv are the
    per-rank values seen inside the shard_map island, so verify
    windows are priced per shard automatically."""
    win = (2 * r * kvd * 4                  # q window
           + 2 * 2 * kvd * bs * itemsize    # k/v tiles
           + 2 * 2 * nkv * bs * 4           # scale tiles (quant path)
           + 2 * r * (kvd + 2 * 128) * 4    # acc/m/l partial outs
           + 2 * r * 128 * 4 + r * kvd * 4)  # scratch
    if win > PAGED_VMEM_BUDGET:
        raise ValueError(
            f"paged verify kernel windows need {win} B VMEM "
            f"(> {PAGED_VMEM_BUDGET} B): shrink draft_k, block_size or "
            f"heads")
    return kvd, bs, nkv


def _paged_verify_kernel(lp_ref, sc_ref, q_ref, k_ref, v_ref,
                         acc_ref, m_ref, l_ref, l_s, b_s, acc_s, *,
                         block_size, online=False):
    """_paged_kernel over R = T*NH query rows, finalization deferred:
    the last live step stores raw (acc, m, l) instead of acc/l."""
    j = pl.program_id(0)
    pos = sc_ref[_POS, j]
    start = sc_ref[_START, j]

    def scores():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [R, bs]
        t = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(t <= pos, s, jnp.float32(-1e30))

    def pv(p):
        return jax.lax.dot_general(
            p, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [R, KVD]

    @pl.when(sc_ref[_FIRST, j] == np.int32(1))
    def _first():
        s = scores()
        base = s.max(axis=-1, keepdims=True)
        p = jnp.exp2(s - base)
        b_s[...] = jnp.broadcast_to(base, b_s.shape)
        l_s[...] = jnp.broadcast_to(p.sum(axis=-1, keepdims=True),
                                    l_s.shape)
        acc_s[...] = pv(p.astype(v_ref.dtype))

    @pl.when(jnp.logical_and(sc_ref[_LIVE, j] == np.int32(1),
                             sc_ref[_FIRST, j] == np.int32(0)))
    def _more():
        s = scores()
        if online:
            m_prev = b_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.exp2(s - m_new)
            b_s[...] = jnp.broadcast_to(m_new, b_s.shape)
            l_s[...] = l_s[...] * alpha + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] * alpha + pv(p.astype(v_ref.dtype))
        else:
            p = jnp.exp2(s - b_s[:, :1])
            l_s[...] = l_s[...] + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] + pv(p.astype(v_ref.dtype))

    @pl.when(sc_ref[_LAST, j] == np.int32(1))
    def _fin():
        acc_ref[0] = acc_s[...]
        m_ref[0] = b_s[...]
        l_ref[0] = l_s[...]


def paged_attention_verify(q_bd, k_pool, v_pool, tables, qstart, layer,
                           *, n_steps=None):
    """Multi-token verification read over the CACHED prefix of each
    sequence.

    q_bd [B, R, KVD] with R = T*NH t-major block-diagonal rows (row
    r = t*NH + h is fed token t's head-h query), PRE-SCALED by
    scale*log2(e); qstart [B] i32 cached token counts (a 0 row is
    skipped and its outputs left unwritten — every live row must have
    qstart >= 1). All R rows of a sequence share the prefix mask
    t < qstart; the caller merges fed-token attention outside. Returns
    UNFINALIZED f32 partials (acc [B, R, KVD], m [B, R, 128],
    l [B, R, 128]) — only column 0 of m/l is meaningful."""
    b, r, kvd = q_bd.shape
    L, NP, _, bs = k_pool.shape
    B, max_nb = tables.shape
    if n_steps is None:
        n_steps = B * max_nb
    it = jnp.dtype(k_pool.dtype).itemsize
    sched = paged_schedule(qstart, tables, n_steps, bs)
    lp = jnp.asarray([layer], jnp.int32)

    def kv_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    kernel = functools.partial(_paged_verify_kernel, block_size=bs,
                               online=softmax_mode() == "online")
    with _mosaic_ctx():
        acc, m, l = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_steps,),
                in_specs=[
                    pl.BlockSpec((1, r, kvd), q_map),
                    pl.BlockSpec((1, 1, kvd, bs), kv_map),
                    pl.BlockSpec((1, 1, kvd, bs), kv_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, r, kvd), q_map),
                    pl.BlockSpec((1, r, 128), q_map),
                    pl.BlockSpec((1, r, 128), q_map),
                ],
                scratch_shapes=[
                    pltpu.VMEM((r, 128), jnp.float32),
                    pltpu.VMEM((r, 128), jnp.float32),
                    pltpu.VMEM((r, kvd), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, r, kvd), jnp.float32),
                jax.ShapeDtypeStruct((b, r, 128), jnp.float32),
                jax.ShapeDtypeStruct((b, r, 128), jnp.float32),
            ],
            cost_estimate=_cost_estimate(
                flops=4 * r * kvd * bs * n_steps,
                transcendentals=r * bs * n_steps,
                bytes_accessed=2 * kvd * bs * it * n_steps,
                name="paged.attention_verify"),
            interpret=_interpret(),
        )(lp, sched, q_bd, k_pool, v_pool)
    return acc, m, l


def _paged_verify_quant_kernel(lp_ref, sc_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, acc_ref, m_ref, l_ref,
                               l_s, b_s, acc_s, *, block_size, nkv,
                               online=False):
    """_paged_verify_kernel over int8 tiles (fused per-column dequant,
    same op chain as _paged_quant_kernel, finalization deferred)."""
    j = pl.program_id(0)
    pos = sc_ref[_POS, j]
    start = sc_ref[_START, j]

    def scores():
        k_deq = _dequant_tile(k_ref[0, 0], ks_ref[0, 0], nkv)
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k_deq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [R, bs]
        t = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(t <= pos, s, jnp.float32(-1e30))

    def pv(p):
        v_deq = _dequant_tile(v_ref[0, 0], vs_ref[0, 0], nkv)
        return jax.lax.dot_general(
            p, v_deq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [R, KVD]

    @pl.when(sc_ref[_FIRST, j] == np.int32(1))
    def _first():
        s = scores()
        base = s.max(axis=-1, keepdims=True)
        p = jnp.exp2(s - base)
        b_s[...] = jnp.broadcast_to(base, b_s.shape)
        l_s[...] = jnp.broadcast_to(p.sum(axis=-1, keepdims=True),
                                    l_s.shape)
        acc_s[...] = pv(p)

    @pl.when(jnp.logical_and(sc_ref[_LIVE, j] == np.int32(1),
                             sc_ref[_FIRST, j] == np.int32(0)))
    def _more():
        s = scores()
        if online:
            m_prev = b_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.exp2(s - m_new)
            b_s[...] = jnp.broadcast_to(m_new, b_s.shape)
            l_s[...] = l_s[...] * alpha + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] * alpha + pv(p)
        else:
            p = jnp.exp2(s - b_s[:, :1])
            l_s[...] = l_s[...] + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] + pv(p)

    @pl.when(sc_ref[_LAST, j] == np.int32(1))
    def _fin():
        acc_ref[0] = acc_s[...]
        m_ref[0] = b_s[...]
        l_ref[0] = l_s[...]


def paged_attention_verify_quant(q_bd, k_pool, v_pool, k_scale, v_scale,
                                 tables, qstart, layer, *, n_steps=None):
    """Multi-token verification read over an int8 pool with fused
    per-column dequant. Same contract as
    :func:`paged_attention_verify`, plus the [L, NP, NKV, bs] f32 scale
    pools riding the flat schedule."""
    b, r, kvd = q_bd.shape
    L, NP, _, bs = k_pool.shape
    nkv = k_scale.shape[2]
    B, max_nb = tables.shape
    if n_steps is None:
        n_steps = B * max_nb
    it = jnp.dtype(k_pool.dtype).itemsize
    kvd_b, bs_b, nkv_b = _fit_paged_verify_blocks(r, kvd, nkv, bs, it)
    sched = paged_schedule(qstart, tables, n_steps, bs)
    lp = jnp.asarray([layer], jnp.int32)

    def kv_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    kernel = functools.partial(_paged_verify_quant_kernel, block_size=bs,
                               nkv=nkv, online=softmax_mode() == "online")
    with _mosaic_ctx():
        acc, m, l = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_steps,),
                in_specs=[
                    pl.BlockSpec((1, r, kvd_b), q_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), kv_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), kv_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, r, kvd_b), q_map),
                    pl.BlockSpec((1, r, 128), q_map),
                    pl.BlockSpec((1, r, 128), q_map),
                ],
                scratch_shapes=[
                    pltpu.VMEM((r, 128), jnp.float32),
                    pltpu.VMEM((r, 128), jnp.float32),
                    pltpu.VMEM((r, kvd), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, r, kvd), jnp.float32),
                jax.ShapeDtypeStruct((b, r, 128), jnp.float32),
                jax.ShapeDtypeStruct((b, r, 128), jnp.float32),
            ],
            cost_estimate=_cost_estimate(
                flops=(4 * r * kvd * bs + 2 * kvd * bs) * n_steps,
                transcendentals=r * bs * n_steps,
                bytes_accessed=(2 * kvd * bs * it
                                + 2 * nkv * bs * 4) * n_steps,
                name="paged.attention_verify_quant"),
            interpret=_interpret(),
        )(lp, sched, q_bd, k_pool, v_pool, k_scale, v_scale)
    return acc, m, l


def merge_verify_partials(acc_c, m_c, l_c, acc_f, m_f, l_f):
    """Exact online-softmax merge of the kernel's cached-prefix partials
    with the caller's fed-token partials: rescale both exp2 sums to the
    common max and finalize once. Exact for ANY anchor m (tile-0 or
    running max): acc = sum_i exp2(s_i - m) * v_i rescales by
    exp2(m - m_tot) regardless of how m was chosen. Shapes: acc
    [B, R, KVD]; m/l [B, R, 1]. Returns attn [B, R, KVD] f32."""
    m_tot = jnp.maximum(m_c, m_f)
    a_c = jnp.exp2(m_c - m_tot)
    a_f = jnp.exp2(m_f - m_tot)
    num = acc_c * a_c + acc_f * a_f
    den = l_c * a_c + l_f * a_f
    return num / jnp.maximum(den, jnp.float32(1e-30))


def paged_commit_schedule(qstart, commit_len, tables, n_layers,
                          n_tokens, block_size):
    """Flat commit walk for the verification cache update:
    [N_COMMIT_FIELDS, L*B*T] i32, layer-major then sequence then token.

    Fed token t of sequence b commits at position qstart[b] + t iff
    t < commit_len[b]; rejected and dead columns redirect to the
    reserved null block 0 (the engine's scribble target), so the kernel
    writes every step and live blocks only ever receive accepted
    columns. Within one (layer, seq) the walk's block ids are
    non-decreasing and each block is visited consecutively, so the
    FIRST flag (out-window change) is computable by shifted comparison.
    Works on traced values (pure jnp)."""
    B, max_nb = tables.shape
    bs = jnp.int32(block_size)
    n = int(n_layers) * B * int(n_tokens)
    idx = jnp.arange(n, dtype=jnp.int32)
    li = idx // (B * int(n_tokens))
    bi = (idx // int(n_tokens)) % B
    ti = idx % int(n_tokens)
    pos = qstart[bi].astype(jnp.int32) + ti
    commit = ti < commit_len[bi].astype(jnp.int32)
    slot = jnp.clip(pos // bs, 0, max_nb - 1)
    bid = jnp.where(commit, tables[bi, slot].astype(jnp.int32),
                    jnp.int32(0))
    col = pos % bs
    prev_l = jnp.concatenate([jnp.full((1,), -1, jnp.int32), li[:-1]])
    prev_b = jnp.concatenate([jnp.full((1,), -1, jnp.int32), bid[:-1]])
    first = ((li != prev_l) | (bid != prev_b)).astype(jnp.int32)
    return jnp.stack([li, bid, col, first, bi, ti])


def _paged_commit_kernel(sc_ref, nk_ref, nv_ref, k_ref, v_ref,
                         ko_ref, vo_ref, *, block_size):
    """One fed token's column merged into its block tile per step. The
    first visit to an out window seeds it from the input pool tile;
    revisits (further columns of the same block) read the aliased out
    refs back — the paged_attend_update revisit-buffer semantics. The
    minor-dim insert routes through f32 (Mosaic bf16 limitation), exact
    for f32 and int8 values alike."""
    j = pl.program_id(0)
    col = sc_ref[_CCOL, j]
    first = sc_ref[_CFIRST, j] == np.int32(1)
    kvd = nk_ref.shape[4]
    lane = lax.broadcasted_iota(jnp.int32, (kvd, block_size), 1)

    def merged(base, new_ref):
        return jnp.where(lane == col,
                         _column_tile(new_ref[0, 0, 0], block_size),
                         base.astype(jnp.float32)).astype(ko_ref.dtype)

    @pl.when(first)
    def _fresh():
        ko_ref[0, 0] = merged(k_ref[0, 0], nk_ref)
        vo_ref[0, 0] = merged(v_ref[0, 0], nv_ref)

    @pl.when(jnp.logical_not(first))
    def _revisit():
        ko_ref[0, 0] = merged(ko_ref[0, 0], nk_ref)
        vo_ref[0, 0] = merged(vo_ref[0, 0], nv_ref)


def paged_verify_commit(new_k, new_v, k_pool, v_pool, tables, qstart,
                        commit_len):
    """Fused post-verification cache commit: writes fed token t's KV
    column at position qstart[b] + t for every t < commit_len[b],
    across all layers in one call. new_k/new_v [L, B, T, KVD] in pool
    dtype; rejected/dead columns scribble the reserved null block 0.
    The pools alias through the custom call. Returns (k_pool,
    v_pool)."""
    L, B, T, kvd = new_k.shape
    _, NP, _, bs = k_pool.shape
    n = L * B * T
    it = jnp.dtype(k_pool.dtype).itemsize
    sched = paged_commit_schedule(qstart, commit_len, tables, L, T, bs)

    def new_map(j, sc_ref):
        return (sc_ref[_CL, j], sc_ref[_CSEQ, j], sc_ref[_CT, j], 0, 0)

    def pool_map(j, sc_ref):
        return (sc_ref[_CL, j], sc_ref[_CB, j], 0, 0)

    with _mosaic_ctx():
        kp, vp = pl.pallas_call(
            functools.partial(_paged_commit_kernel, block_size=bs),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n,),
                in_specs=[
                    pl.BlockSpec((1, 1, 1, 1, kvd), new_map),
                    pl.BlockSpec((1, 1, 1, 1, kvd), new_map),
                    pl.BlockSpec((1, 1, kvd, bs), pool_map),
                    pl.BlockSpec((1, 1, kvd, bs), pool_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, kvd, bs), pool_map),
                    pl.BlockSpec((1, 1, kvd, bs), pool_map),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
            ],
            # operand indices count scalar-prefetch first: 0=sched,
            # 1=new_k, 2=new_v, 3=k_pool, 4=v_pool
            input_output_aliases={3: 0, 4: 1},
            cost_estimate=_cost_estimate(
                flops=2 * kvd * bs * n,
                transcendentals=0,
                bytes_accessed=(2 * kvd * bs * it + 2 * kvd * it) * n,
                name="paged.verify_commit"),
            interpret=_interpret(),
        )(sched, new_k[:, :, :, None], new_v[:, :, :, None],
          k_pool, v_pool)
    return kp, vp


def _paged_commit_quant_kernel(sc_ref, nk_ref, nv_ref, nks_ref, nvs_ref,
                               k_ref, v_ref, ks_ref, vs_ref,
                               ko_ref, vo_ref, kso_ref, vso_ref, *,
                               block_size, nkv):
    """_paged_commit_kernel over int8 byte tiles + f32 scale tiles. The
    fed columns arrive ALREADY quantized (kv_quant_columns outside the
    call), so committed bytes equal what sequential decode would have
    written; the int8 insert routes through f32 — exact for int8
    values."""
    j = pl.program_id(0)
    col = sc_ref[_CCOL, j]
    first = sc_ref[_CFIRST, j] == np.int32(1)
    kvd = nk_ref.shape[4]
    lane = lax.broadcasted_iota(jnp.int32, (kvd, block_size), 1)
    lane_s = lax.broadcasted_iota(jnp.int32, (nkv, block_size), 1)

    def merged(base, new_ref):
        return jnp.where(lane == col,
                         _column_tile(new_ref[0, 0, 0], block_size),
                         base.astype(jnp.float32)).astype(jnp.int8)

    def merged_s(base, new_ref):
        return jnp.where(lane_s == col, new_ref[0, 0, 0], base)

    @pl.when(first)
    def _fresh():
        ko_ref[0, 0] = merged(k_ref[0, 0], nk_ref)
        vo_ref[0, 0] = merged(v_ref[0, 0], nv_ref)
        kso_ref[0, 0] = merged_s(ks_ref[0, 0], nks_ref)
        vso_ref[0, 0] = merged_s(vs_ref[0, 0], nvs_ref)

    @pl.when(jnp.logical_not(first))
    def _revisit():
        ko_ref[0, 0] = merged(ko_ref[0, 0], nk_ref)
        vo_ref[0, 0] = merged(vo_ref[0, 0], nv_ref)
        kso_ref[0, 0] = merged_s(kso_ref[0, 0], nks_ref)
        vso_ref[0, 0] = merged_s(vso_ref[0, 0], nvs_ref)


def paged_verify_commit_quant(new_k, new_v, new_ks, new_vs, k_pool,
                              v_pool, k_scale, v_scale, tables, qstart,
                              commit_len):
    """Fused int8 post-verification cache commit. Same contract as
    :func:`paged_verify_commit`, except the fed columns arrive
    pre-quantized — new_k/new_v int8 [L, B, T, KVD] with new_ks/new_vs
    f32 [L, B, T, NKV] from :func:`kv_quant_columns` — and all four
    pools alias through the custom call. Returns (k_pool, v_pool,
    k_scale, v_scale)."""
    L, B, T, kvd = new_k.shape
    _, NP, _, bs = k_pool.shape
    nkv = k_scale.shape[2]
    n = L * B * T
    it = jnp.dtype(k_pool.dtype).itemsize
    kvd_b, bs_b, nkv_b = _fit_paged_kv_blocks(1, kvd, nkv, bs, it)
    sched = paged_commit_schedule(qstart, commit_len, tables, L, T, bs)

    def new_map(j, sc_ref):
        return (sc_ref[_CL, j], sc_ref[_CSEQ, j], sc_ref[_CT, j], 0, 0)

    def pool_map(j, sc_ref):
        return (sc_ref[_CL, j], sc_ref[_CB, j], 0, 0)

    with _mosaic_ctx():
        kp, vp, ks, vs = pl.pallas_call(
            functools.partial(_paged_commit_quant_kernel, block_size=bs,
                              nkv=nkv),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n,),
                in_specs=[
                    pl.BlockSpec((1, 1, 1, 1, kvd_b), new_map),
                    pl.BlockSpec((1, 1, 1, 1, kvd_b), new_map),
                    pl.BlockSpec((1, 1, 1, nkv_b, 1), new_map),
                    pl.BlockSpec((1, 1, 1, nkv_b, 1), new_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), pool_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), pool_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), pool_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), pool_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, kvd_b, bs_b), pool_map),
                    pl.BlockSpec((1, 1, kvd_b, bs_b), pool_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), pool_map),
                    pl.BlockSpec((1, 1, nkv_b, bs_b), pool_map),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
                jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
            ],
            # operand indices count scalar-prefetch first: 0=sched,
            # 1=new_k, 2=new_v, 3=new_ks, 4=new_vs, 5=k_pool, 6=v_pool,
            # 7=k_scale, 8=v_scale
            input_output_aliases={5: 0, 6: 1, 7: 2, 8: 3},
            cost_estimate=_cost_estimate(
                flops=2 * kvd * bs * n,
                transcendentals=0,
                bytes_accessed=((2 * kvd * bs + 2 * nkv * bs * 4) * it
                                + 2 * (kvd + 4 * nkv) * it) * n,
                name="paged.verify_commit_quant"),
            interpret=_interpret(),
        )(sched, new_k[:, :, :, None], new_v[:, :, :, None],
          new_ks[..., None], new_vs[..., None],
          k_pool, v_pool, k_scale, v_scale)
    return kp, vp, ks, vs


# -- latent attention (MLA) over one latent pool ------------------------------
#
# Multi-head latent attention keeps ONE pool [L, NP, W, bs]: per cached token
# and layer the compressed KV after its norm (rows 0 .. rank - 1) and the one
# rope key all heads share (rows rank .. W - 1). In the absorbed form every
# head's query is [W] wide (q_nope folded through W_kvb^K, then q_pe), the
# keys are the block tile's W rows and the values its first ``rank`` rows:
# one tile read serves both products, and the output is the latent [rank]
# vector that W_kvb^V expands outside. W = 576 is no multiple of 128 and so
# lies on the sublane axis, time in lanes as in the K/V pools.
#
# Both kernels run a DYNAMIC grid: the decode's flat schedule has exactly the
# live (sequence, block) steps, the prefill chunk's table axis ends at the
# chunk's last live block, so a long table costs nothing where the context is
# short. Softmax is the online recurrence in exp2 (queries arrive pre-scaled
# by scale * log2(e)); statistics f32, probabilities cast to the pool's dtype
# before PV.

MLA_VMEM_LIMIT = 64 * 1024 * 1024
MLA_PREFILL_BUDGET = 32 * 1024 * 1024
MLA_PREFILL_SLOTS = 4          # table slots a grid step of the prefill walks

# The decode kernels of this section walk ``paged_update_walk``'s schedule
# without its ``_LIVE`` row (their grid ends at the live total): 8 rows,
# which SMEM holds unpadded (9 pad to 16: at 64 rows x 262 table slots the
# padded schedule alone is over SMEM's 1 MiB).
_W_FIELDS = (_SEQ, _BLK, _START, _FIRST, _LAST, _POS, _COL, _UBLK)
_WPOS, _WCOL, _WUBLK = 5, 6, 7      # the rows before them keep their index


def mla_update_walk(tables, positions, block_size):
    """``paged_update_walk`` as the latent and index decode kernels hold it
    in SMEM: (the schedule's 8 rows they read, its live total)."""
    sched, total = paged_update_walk(tables, positions, block_size)
    return sched[jnp.asarray(_W_FIELDS)], total


def _online_step(s, tile, m_s, l_s, acc_s, rank):
    """One block's scores ``s`` [R, bs] f32 (masked) into the running max,
    sum and latent accumulator [R, rank]."""
    m_prev = m_s[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp2(m_prev - m_new)
    p = jnp.exp2(s - m_new)
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = l_s[...] * alpha + jnp.broadcast_to(
        p.sum(axis=-1, keepdims=True), l_s.shape)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p.astype(tile.dtype), tile[:rank, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _mla_decode_kernel(lp_ref, sc_ref, q_ref, new_ref, c_ref, *rest,
                       block_size, rank, selected):
    # ``selected``: one more window before the outputs, the row's
    # selection of this block's positions ([1, 1, bs], 1 = attend)
    sel_ref = rest[0] if selected else None
    o_ref, co_ref, m_s, l_s, acc_s = rest[1:] if selected else rest
    j = pl.program_id(0)
    pos = sc_ref[_WPOS, j]
    start = sc_ref[_START, j]
    col = sc_ref[_WCOL, j]
    upd = sc_ref[_LAST, j] == np.int32(1)   # the new token's block IS the last
    w = q_ref.shape[2]

    @pl.when(sc_ref[_FIRST, j] == np.int32(1))
    def _init():
        m_s[...] = jnp.full(m_s.shape, -1e30, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def chain(tile):
        s = jax.lax.dot_general(
            q_ref[0], tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [NH, bs]
        t = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = t <= pos
        if selected:
            keep = jnp.logical_and(
                keep, sel_ref[0].astype(jnp.float32) > jnp.float32(0.5))
        _online_step(jnp.where(keep, s, jnp.float32(-1e30)), tile,
                     m_s, l_s, acc_s, rank)

    @pl.when(upd)
    def _updated():
        # the new column arrives lane-major and padded to whole lane tiles
        # (see _column_tile); the full tile is written: the aliased out
        # window starts uninitialized
        lane = lax.broadcasted_iota(jnp.int32, (w, block_size), 1)
        tile = jnp.where(lane == col,
                         _column_tile(new_ref[0], block_size)[:w],
                         c_ref[0, 0].astype(jnp.float32)).astype(co_ref.dtype)
        co_ref[0, 0] = tile
        chain(tile)
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[:, :1], jnp.float32(1e-30))

    @pl.when(jnp.logical_not(upd))
    def _raw():
        chain(c_ref[0, 0])


def mla_paged_decode(q, new_col, pool, walk, layer, *, rank, select=None):
    """Fused pool-update + absorbed latent attention for one decode layer.

    q [B, NH, W] PRE-SCALED by scale*log2(e) (W = rank + rope dims: the
    absorbed nope query, then the roped query); new_col [B, W] the new
    token's latent column (normed compressed KV, then the roped shared key);
    pool [L, NP, W, bs]; ``walk`` the batch's ``mla_update_walk``, the same
    in every layer and made once before a step's layers (padding rows point
    at the null block 0 with position 0). Every row writes its column IN
    PLACE (the pool aliases through the call) and attends over its prefix
    including it; the grid is the walk's live steps alone. ``select``
    [B, 1, T] (T >= max_nb * bs, the pool's dtype; ``dsa_select``'s): a row
    attends the positions it marks 1 and no other. Returns (o_lat
    [B, NH, rank] f32, pool)."""
    b, nh, w = q.shape
    bs = pool.shape[-1]
    it = jnp.dtype(pool.dtype).itemsize
    sched, total = walk
    n_steps = sched.shape[1]        # the cost estimate's worst case
    lp = jnp.asarray([layer], jnp.int32)
    wp = -(-w // 128) * 128
    new = jnp.pad(new_col, ((0, 0), (0, wp - w)))[:, None]

    def c_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    def upd_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_WUBLK, j], 0, 0)

    def sel_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, sc_ref[_START, j] // bs)

    masked = select is not None
    kernel = functools.partial(_mla_decode_kernel, block_size=bs, rank=rank,
                               selected=masked)
    with _mosaic_ctx():
        out, pool = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(total,),
                in_specs=[
                    pl.BlockSpec((1, nh, w), q_map),
                    pl.BlockSpec((1, 1, wp), q_map),
                    pl.BlockSpec((1, 1, w, bs), c_map),
                ] + ([pl.BlockSpec((1, 1, bs), sel_map)] if masked else []),
                out_specs=[
                    pl.BlockSpec((1, nh, rank), q_map),
                    pl.BlockSpec((1, 1, w, bs), upd_map),
                ],
                scratch_shapes=[
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, 128), jnp.float32),
                    pltpu.VMEM((nh, rank), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, nh, rank), jnp.float32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            ],
            # operands count scalar prefetch first: 0=lp, 1=sched, 2=q,
            # 3=new, 4=pool
            input_output_aliases={4: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=MLA_VMEM_LIMIT),
            # priced at the whole table, as the other paged kernels are; a
            # step runs its live blocks
            cost_estimate=_cost_estimate(
                flops=2 * nh * (w + rank) * bs * n_steps,
                transcendentals=nh * bs * n_steps,
                bytes_accessed=(w * bs * it * n_steps
                                + 2 * b * w * bs * it),
                name="paged.mla_decode"),
            interpret=_interpret(),
        )(lp, sched, q, new, pool, *([select] if masked else []))
    return out, pool


def _fit_mla_prefill_tile(c, nh, w, rank, bs, itemsize, selected=False):
    """Query tokens a tile of the latent prefill attention holds (PTA002
    contract): the largest divisor of the chunk ``c``, at most
    PREFILL_BLOCK_Q, whose rows (tokens x heads) are whole sublane tiles and
    whose windows (q and out tiles double-buffered, the latent tiles, the
    scores of a step's MLA_PREFILL_SLOTS blocks) and scratch (accumulator,
    running max and sum) fit the budget."""
    def need(tq):
        rows = tq * nh
        return (2 * rows * w * itemsize + 2 * rows * rank * itemsize
                + rows * rank * 4 + 2 * rows * 128 * 4
                + (3 if selected else 2) * rows * MLA_PREFILL_SLOTS * bs * 4
                + 2 * MLA_PREFILL_SLOTS * w * bs * itemsize)
    for tq in range(min(c, PREFILL_BLOCK_Q), 0, -1):
        if c % tq == 0 and ((tq * nh) % 16 == 0 or tq == c) \
                and need(tq) <= MLA_PREFILL_BUDGET:
            return tq
    raise ValueError(
        f"latent prefill kernel windows need {need(1)} B VMEM a query token "
        f"(> {MLA_PREFILL_BUDGET} B): fewer heads or a smaller latent")


def _mla_prefill_kernel(lp_ref, blk_ref, tl_ref, q_ref, *rest, block_size,
                        rank, nh, slots, selected):
    """One (query tile, group of ``slots`` table slots) step: rows are
    (token, head) pairs, token-major, against the group's latent tiles (the
    same pool presented once a slot). Max, sum and accumulator are rescaled
    once a group, not once a block. A slot past the tile's frontier
    re-presents a live block and is masked whole by its nominal positions."""
    c_refs, rest = rest[:slots], rest[slots:]
    # ``selected``: one more window, the tile's tokens' selection of the
    # group's positions ([tq, slots * bs], 1 = attend; causal by itself)
    sel_ref = rest[0] if selected else None
    o_ref, m_s, l_s, acc_s = rest[1:] if selected else rest
    i, j = pl.program_id(0), pl.program_id(1)
    q0 = tl_ref[_TQ0, i]
    nblk = tl_ref[_TNBLK, i]
    bs = block_size

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, -1e30, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def attend(masked):
        tiles = [c[0, 0] for c in c_refs]
        s = jnp.concatenate([jax.lax.dot_general(
            q_ref[...], t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for t in tiles],
            axis=1)                                    # [rows, slots * bs]
        if selected:
            # a token's row of the selection to its ``nh`` rows of scores,
            # on the MXU: [rows, tq] one-hot x [tq, slots * bs]
            tq = sel_ref.shape[0]
            own = (lax.broadcasted_iota(jnp.int32, (tq * nh, tq), 0) // nh
                   == lax.broadcasted_iota(jnp.int32, (tq * nh, tq), 1))
            keep = jax.lax.dot_general(
                own.astype(sel_ref.dtype), sel_ref[...],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            s = jnp.where(keep > jnp.float32(0.5), s, jnp.float32(-1e30))
        elif masked:
            qpos = q0 + lax.broadcasted_iota(jnp.int32, s.shape, 0) // nh
            t = j * (slots * bs) + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(t <= qpos, s, jnp.float32(-1e30))
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = l_s[...] * alpha + jnp.broadcast_to(
            p.sum(axis=-1, keepdims=True), l_s.shape)
        pv = None
        for g, tile in enumerate(tiles):
            d = jax.lax.dot_general(
                p[:, g * bs:(g + 1) * bs].astype(tile.dtype), tile[:rank, :],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            pv = d if pv is None else pv + d
        acc_s[...] = acc_s[...] * alpha + pv

    live = j * slots < nblk
    # every column of the group at or before the tile's first query
    whole = (j + 1) * (slots * bs) - 1 <= q0

    @pl.when(jnp.logical_and(live, whole))
    def _below():
        attend(False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _frontier():
        attend(True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        o_ref[...] = (acc_s[...] / jnp.maximum(
            l_s[:, :1], jnp.float32(1e-30))).astype(o_ref.dtype)


def mla_paged_prefill(q, pool, table_row, start, n_live, layer, *, rank,
                      select=None):
    """Absorbed latent attention of one sequence's prefill chunk over its
    live context, read from the latent pool through the block table.

    q [C, NH, W] PRE-SCALED by scale*log2(e), the absorbed queries of the
    chunk's tokens at positions start .. start + C; pool [L, NP, W, bs]
    ALREADY holding the chunk's own columns; table_row [max_nb] i32; start,
    n_live traced scalars (n_live >= 1). Returns o_lat [C, NH, rank] in q's
    dtype; rows past n_live are zero. The table axis of the grid walks
    MLA_PREFILL_SLOTS slots a step and ends at the chunk's last live block.
    ``select`` [C, T] (T = ``dsa_width(max_nb, bs)``, q's dtype;
    ``dsa_select``'s): a token attends the positions it marks 1 and no
    other (the marks are causal by themselves)."""
    c, nh, w = q.shape
    L, NP, _, bs = pool.shape
    g = MLA_PREFILL_SLOTS
    max_nb = table_row.shape[0]
    it = jnp.dtype(pool.dtype).itemsize
    tq = _fit_mla_prefill_tile(c, nh, w, rank, bs, it, select is not None)
    n_tiles, rows = c // tq, tq * nh
    # the table padded to whole groups: a slot past a tile's frontier
    # presents the tile's last live block whatever the table holds there
    blk, tiles = paged_prefill_schedule(
        jnp.pad(table_row, (0, -max_nb % g)), start, n_live, n_tiles, tq, bs)
    n_blocks = jnp.clip((jnp.asarray(start, jnp.int32)
                         + jnp.asarray(n_live, jnp.int32) + bs - 1) // bs,
                        1, max_nb)
    n_groups = ((n_blocks + g - 1) // g).astype(jnp.int32)
    lp = jnp.asarray([layer], jnp.int32)

    def c_map(slot):
        return lambda i, j, lp_ref, blk_ref, tl_ref: (
            lp_ref[0], blk_ref[i, j * g + slot], 0, 0)

    def q_map(i, j, lp_ref, blk_ref, tl_ref):
        return (i, 0)

    masked = select is not None
    kernel = functools.partial(_mla_prefill_kernel, block_size=bs,
                               rank=rank, nh=nh, slots=g, selected=masked)
    steps = n_tiles * max_nb
    with _mosaic_ctx():
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_tiles, n_groups),
                in_specs=[pl.BlockSpec((rows, w), q_map)] + [
                    pl.BlockSpec((1, 1, w, bs), c_map(slot))
                    for slot in range(g)] + ([pl.BlockSpec(
                        (tq, g * bs),
                        lambda i, j, lp_ref, blk_ref, tl_ref: (i, j))]
                        if masked else []),
                out_specs=pl.BlockSpec((rows, rank), q_map),
                scratch_shapes=[
                    pltpu.VMEM((rows, 128), jnp.float32),
                    pltpu.VMEM((rows, 128), jnp.float32),
                    pltpu.VMEM((rows, rank), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((c * nh, rank), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=MLA_VMEM_LIMIT),
            cost_estimate=_cost_estimate(
                flops=2 * rows * (w + rank) * bs * steps,
                transcendentals=rows * bs * steps,
                bytes_accessed=(w * bs * it * steps
                                + c * nh * (w + rank) * q.dtype.itemsize),
                name="paged.mla_prefill"),
            interpret=_interpret(),
        )(lp, blk, tiles, q.reshape(c * nh, w), *([pool] * g),
          *([select] if masked else []))
    return out.reshape(c, nh, rank)


def mla_paged_attention_xla(q, pool, tables, lengths, layer, scale, rank,
                            select=None):
    """Plain-XLA oracle of both latent kernels: q [B, NH, W] UNSCALED, row b
    attends the first lengths[b] columns of its table's blocks (a prefill
    chunk is B = C rows over one table, lengths = position + 1), of them
    those ``select`` [B, >= max_nb * bs] marks where given. Standard e-base
    softmax in f32; returns [B, NH, rank] f32."""
    B, max_nb = tables.shape
    w, bs = pool.shape[2], pool.shape[3]
    cc = jnp.transpose(pool[layer][tables], (0, 2, 1, 3)) \
        .reshape(B, w, max_nb * bs).astype(jnp.float32)
    s = jnp.einsum("bhw,bwt->bht", q.astype(jnp.float32), cc) * scale
    t = jnp.arange(max_nb * bs)[None, None, :]
    keep = t < lengths[:, None, None]
    if select is not None:
        keep = keep & (select[:, None, :max_nb * bs] > 0)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bht,bct->bhc", p, cc[:, :rank])


def dsa_index_xla(qi, wi, ipool, tables, layer):
    """Plain-XLA oracle of both index kernels: qi [B, HI, DI], wi [B, HI],
    row b against every column of its table's blocks -> [B, max_nb * bs]
    f32 (the kernels write the blocks up to a row's own)."""
    B, max_nb = tables.shape
    di, bs = ipool.shape[2], ipool.shape[3]
    kk = jnp.transpose(ipool[layer][tables], (0, 2, 1, 3)) \
        .reshape(B, di, max_nb * bs).astype(jnp.float32)
    s = jnp.einsum("bhd,bdt->bht", qi.astype(jnp.float32), kk)
    return jnp.einsum("bh,bht->bt", wi.astype(jnp.float32),
                      jnp.maximum(s, 0.0))


# Learned sparse attention (a "lightning indexer" beside latent attention).
# A layer with an indexer keeps ONE more paged pool, of index keys
# [Li, NP, DI, bs] (one head, time in lanes, under the same block table as
# the latent pool), scores every cached position of a row,
#   I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),
# and the row attends the ``k`` positions s <= t of largest I alone
# (``dsa_select``). The selection is handed to the latent kernels above as a
# 0/1 array over positions (``select=``): they walk the row's whole live
# context and mask what was not selected, so the tokens attended to are
# exactly the selected ones and nothing is gathered. Scores are float32;
# blocks a walk never reaches hold whatever memory held and are masked by
# position in ``dsa_select``.

DSA_BLOCK_Q = 128


def dsa_width(max_nb, block_size):
    """Positions a selection spans: the table's, padded to whole groups of
    the latent prefill kernel's slots."""
    g = MLA_PREFILL_SLOTS
    return -(-max_nb // g) * g * block_size


def _dsa_scores(q, w, tile, n_heads):
    """q [n_heads * R, DI] head-major, w [n_heads * R, 1] f32, tile
    [DI, bs] -> [R, bs] f32."""
    s = jax.lax.dot_general(q, tile, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.maximum(s, jnp.float32(0.0)) * w
    r = s.shape[0] // n_heads
    if r == 1:
        return jnp.sum(s, axis=0, keepdims=True)
    out = s[:r]
    for h in range(1, n_heads):
        out = out + s[h * r:(h + 1) * r]
    return out


def _dsa_index_prefill_kernel(lp_ref, blk_ref, tl_ref, q_ref, w_ref, k_ref,
                              o_ref, *, n_heads):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j < tl_ref[_TNBLK, i])
    def _live():
        o_ref[...] = _dsa_scores(q_ref[...], w_ref[...], k_ref[0, 0],
                                 n_heads)


def dsa_index_prefill(qi, wi, ipool, table_row, start, n_live, layer):
    """Index scores of one sequence's prefill chunk against its cached index
    keys, read through the block table: qi [C, HI, DI] (roped), wi [C, HI]
    f32 (scaled), ipool [Li, NP, DI, bs] ALREADY holding the chunk's own
    keys. Returns I [C, T] f32, T = ``dsa_width``: row t's scores of the
    positions in the blocks up to its own (the rest is not written). The
    table axis of the grid ends at the chunk's last live block."""
    c, hi, di = qi.shape
    bs = ipool.shape[-1]
    max_nb = table_row.shape[0]
    it = jnp.dtype(ipool.dtype).itemsize
    tq = next((d for d in range(min(c, DSA_BLOCK_Q), 0, -1)
               if c % d == 0 and d % 16 == 0), c)
    n_tiles, rows = c // tq, tq * hi
    blk, tiles = paged_prefill_schedule(table_row, start, n_live, n_tiles,
                                        tq, bs)
    n_blocks = jnp.clip((jnp.asarray(start, jnp.int32)
                         + jnp.asarray(n_live, jnp.int32) + bs - 1) // bs,
                        1, max_nb).astype(jnp.int32)
    lp = jnp.asarray([layer], jnp.int32)
    # a tile's rows head-major, so that the sum over heads is a sum of
    # whole sublane tiles
    q = qi.reshape(n_tiles, tq, hi, di).transpose(0, 2, 1, 3) \
        .reshape(n_tiles * rows, di)
    w = wi.astype(jnp.float32).reshape(n_tiles, tq, hi).transpose(0, 2, 1) \
        .reshape(n_tiles * rows, 1)

    def row_map(i, j, lp_ref, blk_ref, tl_ref):
        return (i, 0)

    steps = n_tiles * max_nb
    with _mosaic_ctx():
        return pl.pallas_call(
            functools.partial(_dsa_index_prefill_kernel, n_heads=hi),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_tiles, n_blocks),
                in_specs=[
                    pl.BlockSpec((rows, di), row_map),
                    pl.BlockSpec((rows, 1), row_map),
                    pl.BlockSpec((1, 1, di, bs),
                                 lambda i, j, lp_ref, blk_ref, tl_ref: (
                                     lp_ref[0], blk_ref[i, j], 0, 0)),
                ],
                out_specs=pl.BlockSpec(
                    (tq, bs), lambda i, j, lp_ref, blk_ref, tl_ref: (i, j)),
            ),
            out_shape=jax.ShapeDtypeStruct((c, dsa_width(max_nb, bs)),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=MLA_VMEM_LIMIT),
            cost_estimate=_cost_estimate(
                flops=2 * rows * di * bs * steps,
                bytes_accessed=(di * bs * it * steps + c * hi * (di * it + 4)
                                + c * bs * 4 * max_nb),
                name="paged.dsa_index_prefill"),
            interpret=_interpret(),
        )(lp, blk, tiles, q, w, ipool)


def _dsa_index_decode_kernel(lp_ref, sc_ref, q_ref, w_ref, new_ref, k_ref,
                             o_ref, ko_ref, *, block_size, n_heads):
    j = pl.program_id(0)
    col = sc_ref[_WCOL, j]
    upd = sc_ref[_LAST, j] == np.int32(1)   # the new token's block IS the last
    di = q_ref.shape[2]

    def score(tile):
        o_ref[0] = _dsa_scores(q_ref[0], w_ref[0], tile, n_heads)

    @pl.when(upd)
    def _updated():
        lane = lax.broadcasted_iota(jnp.int32, (di, block_size), 1)
        tile = jnp.where(lane == col,
                         _column_tile(new_ref[0], block_size)[:di],
                         k_ref[0, 0].astype(jnp.float32)).astype(ko_ref.dtype)
        ko_ref[0, 0] = tile
        score(tile)

    @pl.when(jnp.logical_not(upd))
    def _raw():
        score(k_ref[0, 0])


def dsa_index_decode(qi, wi, new_key, ipool, walk, layer):
    """Fused index-key write + index scores for one decode layer: qi
    [B, HI, DI] (roped), wi [B, HI] f32 (scaled), new_key [B, DI] each row's
    new index key, ipool [Li, NP, DI, bs], ``walk`` the batch's
    ``mla_update_walk`` (the one the latent kernel walks). Every row
    writes its key IN PLACE (the pool aliases through the call) and scores
    its prefix including it. Returns (I [B, 1, T] f32 with T =
    ``dsa_width``; blocks past a row's own are not written, ipool)."""
    b, hi, di = qi.shape
    bs = ipool.shape[-1]
    it = jnp.dtype(ipool.dtype).itemsize
    sched, total = walk
    n_steps = sched.shape[1]
    max_nb = n_steps // b
    lp = jnp.asarray([layer], jnp.int32)
    dp = -(-di // 128) * 128
    new = jnp.pad(new_key, ((0, 0), (0, dp - di)))[:, None]
    w = wi.astype(jnp.float32)[:, :, None]

    def k_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_BLK, j], 0, 0)

    def q_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, 0)

    def upd_map(j, lp_ref, sc_ref):
        return (lp_ref[0], sc_ref[_WUBLK, j], 0, 0)

    def o_map(j, lp_ref, sc_ref):
        return (sc_ref[_SEQ, j], 0, sc_ref[_START, j] // bs)

    with _mosaic_ctx():
        out, ipool = pl.pallas_call(
            functools.partial(_dsa_index_decode_kernel, block_size=bs,
                              n_heads=hi),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(total,),
                in_specs=[
                    pl.BlockSpec((1, hi, di), q_map),
                    pl.BlockSpec((1, hi, 1), q_map),
                    pl.BlockSpec((1, 1, dp), q_map),
                    pl.BlockSpec((1, 1, di, bs), k_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, bs), o_map),
                    pl.BlockSpec((1, 1, di, bs), upd_map),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, 1, dsa_width(max_nb, bs)),
                                     jnp.float32),
                jax.ShapeDtypeStruct(ipool.shape, ipool.dtype),
            ],
            # operands count scalar prefetch first: 0=lp, 1=sched, 2=q,
            # 3=w, 4=new, 5=ipool
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=MLA_VMEM_LIMIT),
            cost_estimate=_cost_estimate(
                flops=2 * hi * di * bs * n_steps,
                bytes_accessed=((di * it + 4) * bs * n_steps
                                + 2 * b * di * bs * it),
                name="paged.dsa_index_decode"),
            interpret=_interpret(),
        )(lp, sched, qi, w, new, ipool)
    return out, ipool


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    key = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def dsa_select(scores, positions, k, dtype=jnp.bfloat16):
    """The ``min(position + 1, k)`` positions s <= position of largest score,
    a row: scores [R, T] f32 (anything past a row's position), positions [R]
    i32 -> [R, T] ``dtype``, 1 where selected. EXACT, and ``lax.top_k``'s
    set (among equal scores the lower position first): the k-th largest
    score is found by a search over the bits of the scores' order-preserving
    integer form (32 counting passes over the array, no sort), then among
    the scores equal to it the first few by position, by a search over the
    positions' bits; that second search runs only where some row's k-th
    score is tied."""
    r, t = scores.shape
    at = jnp.arange(t, dtype=jnp.int32)[None, :]
    valid = at <= positions[:, None]
    # valid keys are >= 1 (no float maps to 0 but -NaN's largest payload)
    key = jnp.where(valid, jnp.maximum(_sortable(scores), jnp.uint32(1)),
                    jnp.uint32(0))
    want = jnp.minimum(positions + 1, k).astype(jnp.int32)[:, None]

    def count(mask):
        return jnp.sum(mask, axis=1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, v):
        cand = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(key >= cand) >= want, cand, v)
    kth = lax.fori_loop(0, 32, value_bit, jnp.zeros((r, 1), jnp.uint32))
    above = key > kth
    tied = key == kth
    need = want - count(above)          # of the tied, the first ``need``

    def by_position():
        def pos_bit(i, p):
            cand = p | (jnp.int32(1) << (bits - 1 - i).astype(jnp.int32))
            return jnp.where(count(tied & (at < cand)) <= need, cand, p)
        bits = max(1, int(t).bit_length())
        upto = lax.fori_loop(0, bits, pos_bit, jnp.zeros((r, 1), jnp.int32))
        return above | (tied & (at < upto))

    sel = lax.cond(jnp.any(count(tied) != need), by_position,
                   lambda: above | tied)
    return (sel & valid).astype(dtype)
