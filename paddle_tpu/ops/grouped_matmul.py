"""Pallas grouped matmul (gmm) for dropless MoE expert compute.

Ref: the reference's capacity-bucketed expert matmuls
(incubate/distributed/models/moe) pad every expert to cf*T*k/E rows and
compute the padding — at cf=1.25 with 128-rounding that is ~25% dead MXU
work per MoE layer. MegaBlocks-style dropless replaces the buckets with
ONE ragged grouped GEMM over the expert-sorted token buffer:

    out[rows of group e] = lhs[rows of group e] @ rhs[e]

Group boundaries are TILE-ALIGNED by the caller (parallel/moe.py rounds
each expert's row count up to `tile_rows`), so every row tile belongs to
exactly one expert and the kernel runs one fixed grid of MXU row tiles,
reading the per-tile expert id / live / first / last flags out of SMEM
(scalar prefetch) — the same flat live-tile schedule planning the varlen
backward (ops/flash_varlen.py) uses. Padding is bounded by one row tile
per expert plus the tile-rounding of the total, NOT by a capacity
factor; tiles past the last live row skip their matmul entirely
(`pl.when(live)`), so dead-tail compute is a predicated no-op.

Three kernels, one schedule:
  _gmm_kernel      out  = lhs @ rhs[e]           grid (n_n, n_t), t minor
  _gmm_dx_kernel   dlhs = dout @ rhs[e].T        grid (n_k, n_t), t minor
  _gmm_dw_kernel   drhs[e] = sum_t lhs_t.T @ dout_t
                                                 grid (n_k, n_n, n_t)
t is the MINOR grid dim everywhere so consecutive steps walk tiles of
the same expert and Mosaic elides the rhs re-fetch (the block index is
unchanged); dW accumulates a group's tiles in VMEM scratch between its
first/last flags exactly like the varlen dKV accumulator.

The contraction dim is NOT split (full-K blocks): each grid step is one
dot, so no cross-step accumulator is needed in the forward/dX and the
per-row reduction order matches a plain XLA dot — the dropless MoE path
is BITWISE-equal to the dense einsum reference on CPU (test-asserted).
Block_n auto-shrinks until the rhs window fits the VMEM budget.
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

# default row tile: MXU-sized. Callers may shrink it for tiny tests.
TILE_ROWS = 128

# cap on one double-buffered rhs window (K x block_n): block_n halves
# until it fits so wide experts (K=4096) don't overrun scoped VMEM
_GMM_RHS_BUDGET = 8 * 1024 * 1024


def _round_up(n, m):
    return -(-n // m) * m


def _fit_block(dim, itemsize, k_rows, budget=_GMM_RHS_BUDGET):
    """Largest lane-dim block (<= dim, dividing dim, 128-min) whose
    double-buffered [k_rows, block] window fits the budget."""
    block = dim
    while block > 128 and 2 * k_rows * block * itemsize > budget:
        block //= 2
    while dim % block:
        block //= 2
    return max(block, 1)


def tile_schedule(counts, n_tiles, tile_rows=TILE_ROWS):
    """Per-tile flat schedule from per-expert row counts [E] (traced ok).

    Returns int32 arrays (tile_expert, live, first, last) of length
    ``n_tiles`` plus ``offsets`` [E+1] (tile-aligned row starts; the
    caller scatters pair rows to ``offsets[e] + queue_position``).
    Tiles past the last live row clamp their expert id to E-1 (same
    block re-presented -> rhs DMA elided) and carry live=0."""
    E = counts.shape[0]
    aligned = _round_up(counts, tile_rows)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(aligned).astype(jnp.int32)])          # [E+1]
    row0 = (jnp.arange(n_tiles, dtype=jnp.int32) * tile_rows)
    expert = jnp.clip(
        jnp.searchsorted(offsets, row0, side="right").astype(jnp.int32) - 1,
        0, E - 1)
    live = (row0 < offsets[E]).astype(jnp.int32)
    first = ((row0 == offsets[expert]) & (live == 1)).astype(jnp.int32)
    last = ((row0 + tile_rows == offsets[expert + 1])
            & (live == 1)).astype(jnp.int32)
    return (expert.astype(jnp.int32), live, first, last, offsets)


def chunk_schedule(counts, chunk_rows, tile_rows=TILE_ROWS):
    """Per-hop tile schedule for ONE ragged-a2a chunk (PR 10).

    ``counts`` [E_local] are the group sizes a single source rank packed
    into its ``chunk_rows``-row chunk with the same tile-aligned layout
    ``tile_schedule`` derives (cumsum of tile-rounded counts), so sender
    packing and receiver schedule agree by construction. Returns the
    4-tuple ``(tile_expert, live, first, last)`` ``grouped_matmul``
    consumes — one schedule per arrived chunk is what lets expert FFN
    start on hop h's rows while hop h+1's ppermute is still in flight."""
    assert chunk_rows % tile_rows == 0, (chunk_rows, tile_rows)
    return tile_schedule(counts, chunk_rows // tile_rows, tile_rows)[:4]


def _gmm_kernel(e_ref, lv_ref, f_ref, l_ref, x_ref, w_ref, o_ref, *,
                out_dtype):
    t = pl.program_id(1)

    @pl.when(lv_ref[t] == 1)
    def _dot():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_dtype)

    @pl.when(lv_ref[t] == 0)
    def _dead():
        # dead-tail rows are never gathered by the combine, but leaving
        # the block uninitialized would leak garbage into buffer-level
        # consumers (tests, debugging dumps): zero them
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_dx_kernel(e_ref, lv_ref, f_ref, l_ref, g_ref, w_ref, o_ref, *,
                   out_dtype):
    t = pl.program_id(1)

    @pl.when(lv_ref[t] == 1)
    def _dot():
        # dx_tile = dout_tile [tm, N] @ rhs[e][kblk, N].T
        o_ref[...] = jax.lax.dot_general(
            g_ref[...], w_ref[0],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_dtype)

    @pl.when(lv_ref[t] == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_dw_kernel(e_ref, lv_ref, f_ref, l_ref, x_ref, g_ref, o_ref,
                   acc_s, *, out_dtype):
    t = pl.program_id(2)

    @pl.when(f_ref[t] == 1)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(lv_ref[t] == 1)
    def _dot():
        acc_s[...] = acc_s[...] + jax.lax.dot_general(
            x_ref[...], g_ref[...],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(l_ref[t] == 1)
    def _flush():
        o_ref[0] = acc_s[...].astype(out_dtype)


def _sched_i32(sched):
    expert, live, first, last = sched
    return (jnp.asarray(expert, jnp.int32), jnp.asarray(live, jnp.int32),
            jnp.asarray(first, jnp.int32), jnp.asarray(last, jnp.int32))


def _gmm_fwd_call(lhs, rhs, sched, tile_rows):
    m, k = lhs.shape
    E, _, n = rhs.shape
    n_t = m // tile_rows
    out_dtype = jnp.promote_types(lhs.dtype, rhs.dtype)
    block_n = _fit_block(n, jnp.dtype(rhs.dtype).itemsize, k)
    it = jnp.dtype(lhs.dtype).itemsize
    kernel = functools.partial(_gmm_kernel, out_dtype=out_dtype)
    with _mosaic_ctx():
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n // block_n, n_t),
                in_specs=[
                    pl.BlockSpec((tile_rows, k),
                                 lambda nb, t, e, lv, f, l: (t, 0)),
                    pl.BlockSpec((1, k, block_n),
                                 lambda nb, t, e, lv, f, l: (e[t], 0, nb)),
                ],
                out_specs=pl.BlockSpec(
                    (tile_rows, block_n),
                    lambda nb, t, e, lv, f, l: (t, nb)),
            ),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            cost_estimate=_cost_estimate(
                flops=2 * m * k * n,
                bytes_accessed=(m * k + E * k * n) * it
                + m * n * jnp.dtype(out_dtype).itemsize,
                name="gmm.fwd"),
            interpret=_interpret(),
        )(*_sched_i32(sched), lhs, rhs)


def _gmm_dx_call(dout, rhs, sched, tile_rows, dx_dtype):
    m, n = dout.shape
    E, k, _ = rhs.shape
    n_t = m // tile_rows
    block_k = _fit_block(k, jnp.dtype(rhs.dtype).itemsize, n)
    it = jnp.dtype(dout.dtype).itemsize
    kernel = functools.partial(_gmm_dx_kernel, out_dtype=dx_dtype)
    with _mosaic_ctx():
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(k // block_k, n_t),
                in_specs=[
                    pl.BlockSpec((tile_rows, n),
                                 lambda kb, t, e, lv, f, l: (t, 0)),
                    pl.BlockSpec((1, block_k, n),
                                 lambda kb, t, e, lv, f, l: (e[t], kb, 0)),
                ],
                out_specs=pl.BlockSpec(
                    (tile_rows, block_k),
                    lambda kb, t, e, lv, f, l: (t, kb)),
            ),
            out_shape=jax.ShapeDtypeStruct((m, k), dx_dtype),
            cost_estimate=_cost_estimate(
                flops=2 * m * k * n,
                bytes_accessed=(m * n + E * k * n) * it
                + m * k * jnp.dtype(dx_dtype).itemsize,
                name="gmm.dx"),
            interpret=_interpret(),
        )(*_sched_i32(sched), dout, rhs)


def _gmm_dw_call(lhs, dout, sched, tile_rows, E, dw_dtype):
    m, k = lhs.shape
    n = dout.shape[1]
    n_t = m // tile_rows
    it = jnp.dtype(lhs.dtype).itemsize
    # a [block_k, block_n] block lives three times in scoped VMEM: the
    # f32 acc scratch and the double-buffered out window. Shrink
    # block_k, then block_n, until all three fit the budget (each extra
    # k/n block re-streams the whole token buffer, so prefer big
    # blocks). Pricing the scratch alone let K=2048 x N=1024 through at
    # 25M, which v5e's compiler refuses against its 16M scoped default.
    budget = 2 * _GMM_RHS_BUDGET
    per_elem = 4 + 2 * jnp.dtype(dw_dtype).itemsize
    block_k, block_n = k, n
    while block_k > 128 and block_k * block_n * per_elem > budget:
        block_k //= 2
    while block_n > 128 and block_k * block_n * per_elem > budget:
        block_n //= 2
    while k % block_k:
        block_k //= 2
    while n % block_n:
        block_n //= 2
    kernel = functools.partial(_gmm_dw_kernel, out_dtype=dw_dtype)
    with _mosaic_ctx():
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(k // block_k, n // block_n, n_t),
                in_specs=[
                    pl.BlockSpec((tile_rows, block_k),
                                 lambda kb, nb, t, e, lv, f, l: (t, kb)),
                    pl.BlockSpec((tile_rows, block_n),
                                 lambda kb, nb, t, e, lv, f, l: (t, nb)),
                ],
                out_specs=pl.BlockSpec(
                    (1, block_k, block_n),
                    lambda kb, nb, t, e, lv, f, l: (e[t], kb, nb)),
                scratch_shapes=[
                    pltpu.VMEM((block_k, block_n), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((E, k, n), dw_dtype),
            cost_estimate=_cost_estimate(
                flops=2 * m * k * n,
                bytes_accessed=m * (k + n) * it
                + E * k * n * jnp.dtype(dw_dtype).itemsize,
                name="gmm.dw"),
            interpret=_interpret(),
        )(*_sched_i32(sched), lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, sched, tile_rows=TILE_ROWS):
    """Ragged grouped GEMM: rows of ``lhs`` [M, K] in group e multiply
    ``rhs`` [E, K, N] -> out [M, N].

    ``sched`` = (tile_expert, live, first, last), int32 [M//tile_rows]
    arrays from ``tile_schedule`` — group boundaries must be aligned to
    ``tile_rows`` (the moe dispatch guarantees this) and M must be a
    multiple of ``tile_rows``. Rows past the last live tile come back
    zero. Differentiable in lhs and rhs (dX/dW run the same flat tile
    schedule); the schedule arrays get no gradient."""
    assert lhs.shape[0] % tile_rows == 0, (lhs.shape, tile_rows)
    return _gmm_fwd_call(lhs, rhs, sched, tile_rows)


def _grouped_matmul_fwd(lhs, rhs, sched, tile_rows):
    return grouped_matmul(lhs, rhs, sched, tile_rows), (lhs, rhs, sched)


def _grouped_matmul_bwd(tile_rows, res, g):
    lhs, rhs, sched = res
    E = rhs.shape[0]
    dlhs = _gmm_dx_call(g, rhs, sched, tile_rows, lhs.dtype)
    dw = _gmm_dw_call(lhs, g, sched, tile_rows, E, jnp.float32)
    # empty groups have no tiles -> their dW block is never presented to
    # the kernel and holds uninitialized memory: select (not multiply —
    # garbage could be NaN) zeros for them. `first` fires exactly once
    # per non-empty group.
    expert, live, first, last = sched
    has_rows = jnp.zeros((E,), jnp.int32).at[expert].add(first)
    dw = jnp.where(has_rows[:, None, None] > 0, dw,
                   jnp.zeros_like(dw)).astype(rhs.dtype)
    return dlhs, dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul_live(lhs, rhs, sched, n_live, tile_rows=TILE_ROWS):
    """Forward-only ``grouped_matmul`` over the LIVE tiles alone: the tile
    axis of the grid is the traced ``n_live`` (the schedule's live tiles come
    first), so dead tiles fetch neither rows nor expert weights, an expert
    with no row costs no weight traffic, and a call with no live tile moves
    nothing. Rows past the last live tile are left UNWRITTEN: the caller
    must never read them (serving combines by gathering live rows only)."""
    m, k = lhs.shape
    E, _, n = rhs.shape
    assert m % tile_rows == 0, (lhs.shape, tile_rows)
    out_dtype = jnp.promote_types(lhs.dtype, rhs.dtype)
    block_n = _fit_block(n, jnp.dtype(rhs.dtype).itemsize, k)
    it = jnp.dtype(lhs.dtype).itemsize
    kernel = functools.partial(_gmm_kernel, out_dtype=out_dtype)
    with _mosaic_ctx():
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n // block_n, jnp.asarray(n_live, jnp.int32)),
                in_specs=[
                    pl.BlockSpec((tile_rows, k),
                                 lambda nb, t, e, lv, f, l: (t, 0)),
                    pl.BlockSpec((1, k, block_n),
                                 lambda nb, t, e, lv, f, l: (e[t], 0, nb)),
                ],
                out_specs=pl.BlockSpec(
                    (tile_rows, block_n),
                    lambda nb, t, e, lv, f, l: (t, nb)),
            ),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            # priced at every tile live, as the static grid is
            cost_estimate=_cost_estimate(
                flops=2 * m * k * n,
                bytes_accessed=(m * k + E * k * n) * it
                + m * n * jnp.dtype(out_dtype).itemsize,
                name="gmm.fwd_live"),
            interpret=_interpret(),
        )(*_sched_i32(sched), lhs, rhs)
