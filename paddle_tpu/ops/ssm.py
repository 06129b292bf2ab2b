"""Mamba-2 state-space kernels of the serving path.

The recurrence, per head ``h`` of width ``P`` with a state ``S`` [P, N] and
the group ``g`` whose ``B`` and ``C`` [N] it reads (``NH / G`` heads share a
group)::

    S_t = exp(delta_t A_h) S_{t-1} + delta_t x_t (x) B_t
    y_t = S_t C_t                                  (the caller adds D_h x_t)

``delta`` comes in after its softplus, ``A`` negative. The state lives where
the engine keeps it: ONE float32 array ``[L, slots, NH, P, N]`` for all
layers, a sequence's slot on axis 1 (slot 0 is the null slot padding rows
point at). Both kernels read and write their slot of ``layer`` IN PLACE
through ``input_output_aliases``; nothing ever copies the array.

``ssd_chunk_scan``: a prefill chunk of one sequence in the state-space-
duality form over ``chunk``-token pieces: inside a piece
``((C B^T) * exp(L_t - L_s) * causal * delta_s) x`` with ``L`` the running
sum of ``delta A`` from the piece's start, across pieces
``exp(L_t) C_t S_in``, the state carried in VMEM from piece to piece.
``ssm_state_update``: one token for each row of a decode batch.

Beside each kernel its plain-XLA oracle (``ssd_chunk_scan_xla``,
``ssm_state_update_xla``), and the token-by-token recurrence
(``ssm_recurrence``) both are held to in ``tests/test_ssm_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common

_mosaic_ctx = _common.mosaic_trace_ctx
_cost_estimate = _common.cost_estimate
_interpret = _common.interpret_mode

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_META_LAYER, _META_SLOT, _META_START, _META_LIVE = range(4)


def _heads_per_block(nh: int, groups: int, most: int = 8) -> int:
    """Heads a grid step takes: the largest divisor of a group's heads up to
    ``most`` (a step then reads one group's B and C)."""
    per_group = nh // groups
    return max(d for d in range(1, min(most, per_group) + 1)
               if per_group % d == 0)


# -- prefill: the chunked scan ---------------------------------------------------

def _ssd_kernel(meta_ref, tail_ref, x_ref, xt_ref, b_ref, c_ref, col_ref,
                row_ref, s_ref, y_ref, so_ref, s_s, *, hb, p, q_len):
    i, q = pl.program_id(0), pl.program_id(1)
    first = q == 0
    fresh = meta_ref[_META_START] == 0

    @pl.when(jnp.logical_and(first, fresh))
    def _zero():
        s_s[...] = jnp.zeros(s_s.shape, F32)

    @pl.when(jnp.logical_and(first, jnp.logical_not(fresh)))
    def _load():
        s_s[...] = s_ref[0, 0]

    @pl.when(q * q_len >= meta_ref[_META_LIVE])
    def _dead():
        # a piece past the last live token: nothing decays, nothing is fed
        y_ref[...] = jnp.zeros(y_ref.shape, F32)

    @pl.when(q * q_len < meta_ref[_META_LIVE])
    def _piece():
        bq = b_ref[...].astype(F32)                         # [Q, N]
        cq = c_ref[...].astype(F32)
        g = lax.dot_general(cq, bq, (((1,), (1,)), ((), ())),
                            precision=_HI, preferred_element_type=F32)
        t = lax.broadcasted_iota(jnp.int32, g.shape, 0)
        s = lax.broadcasted_iota(jnp.int32, g.shape, 1)
        causal = t >= s
        for h in range(hb):
            lc = col_ref[0, :, h:h + 1]                     # [Q, 1]
            lr = row_ref[0, h:h + 1, :]                     # [1, Q]
            dtr = row_ref[0, hb + h:hb + h + 1, :]          # [1, Q]
            w = row_ref[0, 2 * hb + h:2 * hb + h + 1, :]    # [1, Q]
            # t >= s: L_t - L_s <= 0, so no exponent overflows
            decay = jnp.where(causal, jnp.exp(jnp.minimum(lc - lr, 0.0)),
                              jnp.float32(0.0))
            xh = x_ref[:, h * p:(h + 1) * p].astype(F32)    # [Q, P]
            s_in = s_s[h]                                   # [P, N]
            y = lax.dot_general(g * decay * dtr, xh,
                                (((1,), (0,)), ((), ())), precision=_HI,
                                preferred_element_type=F32)
            y_ref[:, h * p:(h + 1) * p] = y + jnp.exp(lc) * lax.dot_general(
                cq, s_in, (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=F32)
            xt = xt_ref[h * p:(h + 1) * p, :].astype(F32)   # [P, Q]
            s_s[h] = tail_ref[q, i * hb + h] * s_in + lax.dot_general(
                xt * w, bq, (((1,), (0,)), ((), ())), precision=_HI,
                preferred_element_type=F32)

    @pl.when(q == pl.num_programs(1) - 1)
    def _store():
        so_ref[0, 0] = s_s[...]


def _running_decay(dt, a, chunk):
    """L [C, NH] f32: the running sum of ``delta A`` from the start of each
    ``chunk``-token piece, the token's own term included."""
    c, nh = dt.shape
    da = (dt * a[None, :]).reshape(c // chunk, chunk, nh)
    return jnp.cumsum(da, axis=1).reshape(c, nh)


def ssd_chunk_scan(x, dt, a, bm, cm, state, layer, slot, start, n_live,
                   chunk: int = 128):
    """One prefill chunk of one sequence through a layer's recurrence.

    x [C, NH, P]; dt [C, NH] f32 (after the softplus), ZERO at and past
    ``n_live`` so that padding neither decays nor feeds the state; a [NH] f32
    (negative); bm, cm [C, G, N]; state [L, slots, NH, P, N] f32; ``layer``,
    ``slot``, ``start``, ``n_live`` i32 scalars. The slot's state comes in
    (zeros where ``start == 0``: a first chunk, also an evicted sequence's)
    and the state after the last live token goes back to it. ``C`` is a
    multiple of ``chunk``. Returns (y [C, NH, P] f32, state)."""
    c, nh, p = x.shape
    groups, n = bm.shape[1:]
    if c % chunk:
        raise ValueError(f"a chunk of {c} tokens is no multiple of the "
                         f"scan's {chunk}")
    hb = _heads_per_block(nh, groups)
    nhb, per_group = nh // hb, nh // groups
    nq = c // chunk
    big_l = _running_decay(dt, a, chunk)
    # what the state keeps of each token at its piece's end, and of the
    # state the piece began from: exp(L_end - L_s) delta_s, exp(L_end)
    l_end = big_l.reshape(nq, chunk, nh)[:, -1]             # [NQ, NH]
    w = jnp.exp(jnp.repeat(l_end, chunk, axis=0) - big_l) * dt
    by_block = lambda v: v.reshape(c, nhb, hb).transpose(1, 0, 2)
    cols = by_block(big_l)                                  # [NHB, C, hb]
    rows = jnp.concatenate([cols, by_block(dt), by_block(w)],
                           axis=-1).transpose(0, 2, 1)      # [NHB, 3 hb, C]
    x2 = x.reshape(c, nh * p)
    meta = jnp.stack([jnp.asarray(v, jnp.int32)
                      for v in (layer, slot, start, n_live)])
    group_of = lambda i: (i * hb) // per_group
    s_map = lambda i, q, m: (m[_META_LAYER], m[_META_SLOT], i, 0, 0)
    kernel = functools.partial(_ssd_kernel, hb=hb, p=p, q_len=chunk)
    with _mosaic_ctx():
        y, state = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(nhb, nq),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec((chunk, hb * p), lambda i, q, m: (q, i)),
                    pl.BlockSpec((hb * p, chunk), lambda i, q, m: (i, q)),
                    pl.BlockSpec((chunk, n),
                                 lambda i, q, m: (q, group_of(i))),
                    pl.BlockSpec((chunk, n),
                                 lambda i, q, m: (q, group_of(i))),
                    pl.BlockSpec((1, chunk, hb), lambda i, q, m: (i, q, 0)),
                    pl.BlockSpec((1, 3 * hb, chunk),
                                 lambda i, q, m: (i, 0, q)),
                    pl.BlockSpec((1, 1, hb, p, n), s_map),
                ],
                out_specs=[
                    pl.BlockSpec((chunk, hb * p), lambda i, q, m: (q, i)),
                    pl.BlockSpec((1, 1, hb, p, n), s_map),
                ],
                scratch_shapes=[pltpu.VMEM((hb, p, n), F32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((c, nh * p), F32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operands count the scalar prefetch first: 0 = meta, 8 = state
            input_output_aliases={8: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            cost_estimate=_cost_estimate(
                flops=ssd_scan_flops(c, nh, p, n, groups, chunk),
                transcendentals=c * nh * (chunk + 2),
                bytes_accessed=ssd_scan_bytes(
                    c, nh, p, n, groups, jnp.dtype(x.dtype).itemsize),
                name="ssm.scan"),
            interpret=_interpret(),
        )(meta, jnp.exp(l_end), x2, x2.T, bm.reshape(c, groups * n),
          cm.reshape(c, groups * n), cols, rows, state)
    return y.reshape(c, nh, p), state


def ssd_scan_flops(c, nh, p, n, groups, chunk=128) -> float:
    """Multiply-adds x 2 of one ``ssd_chunk_scan`` call over ``c`` live
    tokens: a piece's ``C B^T`` once a group, and per head the masked product
    with ``x``, ``C S`` and the state's ``x^T B``."""
    return 2.0 * c * (groups * chunk * n + nh * (chunk * p + 2 * p * n))


def ssd_scan_bytes(c, nh, p, n, groups, itemsize=2) -> float:
    """Bytes one ``ssd_chunk_scan`` call has to move for ``c`` tokens: x,
    B and C in, delta and its running sum, y out (f32), and the slot's
    state in and out."""
    return float(c * (nh * p + 2 * groups * n) * itemsize
                 + c * nh * 8 + c * nh * p * 4 + 2 * nh * p * n * 4)


def ssd_chunk_scan_xla(x, dt, a, bm, cm, s_in, chunk: int = 128):
    """The oracle of ``ssd_chunk_scan``: the same state-space-duality form
    in plain ``jax.numpy``, float32 at the highest matmul precision, on a
    state passed by value. s_in [NH, P, N]. Returns (y [C, NH, P], s_out)."""
    c, nh, p = x.shape
    groups = bm.shape[1]
    rep = nh // groups
    big_l = _running_decay(dt, a, chunk)
    split = lambda v: v.astype(F32).reshape((c // chunk, chunk) + v.shape[1:])
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def piece(s, xs):
        xq, dq, lq, bq, cq = xs
        bh, ch = jnp.repeat(bq, rep, axis=1), jnp.repeat(cq, rep, axis=1)
        g = jnp.einsum("thn,shn->hts", ch, bh, precision=_HI)
        diff = lq.T[:, :, None] - lq.T[:, None, :]              # [NH, t, s]
        decay = jnp.where(causal[None], jnp.exp(jnp.minimum(diff, 0.0)),
                          jnp.float32(0.0))
        m = g * decay * dq.T[:, None, :]
        y = jnp.einsum("hts,shp->thp", m, xq, precision=_HI)
        y += jnp.exp(lq)[:, :, None] * jnp.einsum(
            "thn,hpn->thp", ch, s, precision=_HI)
        w = jnp.exp(lq[-1][None] - lq) * dq                     # [t, NH]
        s = jnp.exp(lq[-1])[:, None, None] * s + jnp.einsum(
            "thp,thn->hpn", xq * w[:, :, None], bh, precision=_HI)
        return s, y

    s_out, y = lax.scan(piece, s_in.astype(F32),
                        tuple(split(v) for v in (x, dt, big_l, bm, cm)))
    return y.reshape(c, nh, p), s_out


def ssm_recurrence(x, dt, a, bm, cm, s_in):
    """The recurrence itself, token by token in float32 (what both kernels
    and their oracles are held to). Shapes as ``ssd_chunk_scan_xla``."""
    rep = x.shape[1] // bm.shape[1]

    def token(s, xs):
        xt, dt_t, bt, ct = xs
        bh, ch = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * xt)[:, :, None] * bh[:, None, :]
        return s, jnp.sum(s * ch[:, None, :], axis=-1)

    s_out, y = lax.scan(token, s_in.astype(F32),
                        tuple(v.astype(F32) for v in (x, dt, bm, cm)))
    return y, s_out


# -- decode: one token a row -----------------------------------------------------

def _update_kernel(meta_ref, da_ref, x_ref, b_ref, c_ref, s_ref, y_ref,
                   so_ref, *, hb):
    row, i = pl.program_id(0), pl.program_id(1)
    b_row = b_ref[0].astype(F32)                            # [1, N]
    c_row = c_ref[0].astype(F32)
    for h in range(hb):
        s_new = da_ref[row, i * hb + h] * s_ref[0, 0, h] \
            + x_ref[0, 0, :, h:h + 1] * b_row               # [P, N]
        so_ref[0, 0, h] = s_new
        y_ref[0, 0, :, h:h + 1] = jnp.sum(s_new * c_row, axis=1,
                                          keepdims=True)


def ssm_state_update(xdt, da, bm, cm, state, layer, slots):
    """One token for every row of a decode batch through a layer's
    recurrence, each row's slot read and written in place.

    xdt [B, NH, P] f32 (= delta x); da [B, NH] f32 (= exp(delta A)); bm, cm
    [B, G, N]; state [L, slots, NH, P, N] f32; ``layer`` an i32 scalar;
    slots [B] i32, padding rows at the null slot 0 (they all write it; its
    bytes mean nothing). Returns (y [B, NH, P] f32 = S_new C, state)."""
    b, nh, p = xdt.shape
    groups, n = bm.shape[1:]
    hb = _heads_per_block(nh, groups)
    nhb, per_group = nh // hb, nh // groups
    meta = jnp.concatenate([jnp.asarray(layer, jnp.int32)[None],
                            slots.astype(jnp.int32)])
    # a head's x as a column [P, 1] beside the state's [P, N]
    x_cols = xdt.astype(F32).reshape(b, nhb, hb, p).transpose(0, 1, 3, 2)
    group_of = lambda r, i: r * groups + (i * hb) // per_group
    s_map = lambda r, i, m: (m[0], m[1 + r], i, 0, 0)
    with _mosaic_ctx():
        y, state = pl.pallas_call(
            functools.partial(_update_kernel, hb=hb),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, nhb),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, 1, p, hb), lambda r, i, m: (r, i, 0, 0)),
                    pl.BlockSpec((1, 1, n),
                                 lambda r, i, m: (group_of(r, i), 0, 0)),
                    pl.BlockSpec((1, 1, n),
                                 lambda r, i, m: (group_of(r, i), 0, 0)),
                    pl.BlockSpec((1, 1, hb, p, n), s_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, p, hb), lambda r, i, m: (r, i, 0, 0)),
                    pl.BlockSpec((1, 1, hb, p, n), s_map),
                ],
            ),
            out_shape=[jax.ShapeDtypeStruct((b, nhb, p, hb), F32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operands count the scalar prefetch first: 0 = meta, 5 = state
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            cost_estimate=_cost_estimate(
                flops=5 * b * nh * p * n,
                transcendentals=0,
                bytes_accessed=ssm_update_bytes(
                    b, nh, p, n, groups, jnp.dtype(bm.dtype).itemsize),
                name="ssm.update"),
            interpret=_interpret(),
        )(meta, da.astype(F32), x_cols, bm.reshape(b * groups, 1, n),
          cm.reshape(b * groups, 1, n), state)
    return y.transpose(0, 1, 3, 2).reshape(b, nh, p), state


def ssm_update_bytes(b, nh, p, n, groups, itemsize=2) -> float:
    """Bytes one ``ssm_state_update`` call has to move for ``b`` rows: each
    row's state in and out (f32), and beside it x, its decay, B, C and y."""
    return float(b * (2 * nh * p * n * 4 + 2 * nh * p * 4 + nh * 4
                      + 2 * groups * n * itemsize))


def ssm_state_update_xla(xdt, da, bm, cm, s_rows):
    """The oracle of ``ssm_state_update`` on the rows' states passed by
    value: s_rows [B, NH, P, N]. Returns (y [B, NH, P], s_rows)."""
    rep = xdt.shape[1] // bm.shape[1]
    bh = jnp.repeat(bm.astype(F32), rep, axis=1)
    ch = jnp.repeat(cm.astype(F32), rep, axis=1)
    s = da.astype(F32)[:, :, None, None] * s_rows \
        + xdt.astype(F32)[..., None] * bh[:, :, None, :]
    return jnp.sum(s * ch[:, :, None, :], axis=-1), s
