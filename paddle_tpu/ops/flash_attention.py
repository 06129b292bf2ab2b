"""Flash attention as a Pallas TPU kernel.

Ref: paddle/phi/kernels/gpu/flash_attn_kernel.cu (the reference dlopens its
FlashAttention-2 fork). TPU-native rewrite, not a translation:

- forward: Pallas kernel, online-softmax over KV tiles held in VMEM, fp32
  accumulators, MXU matmuls with bf16 operands (preferred_element_type=f32).
  The [S, S] score matrix never exists in HBM. Emits per-row logsumexp.
- backward: two Pallas kernels using the saved logsumexp (standard FA2
  identities: dV = PᵀdO, dS = P∘(dP − rowsum(dO∘O)), dQ/dK from dS) —
  dK/dV over k-tiles x inner q loop, dQ over q-tiles x inner k loop, all
  tiles resident in VMEM. Ragged lengths via zero-pad + mask (see
  _flash_fwd / _flash_bwd_pallas docstrings).

Layout [B, S, H, D] (the reference's), GQA via KV-head repeat.
interpret=True under CPU so the same code runs in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# v5e-tuned: 1024x1024 tiles keep the MXU fed (2.7x over 128x128 measured);
# min() clamps both to the actual sequence length for small inputs.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# Scores are computed in the LOG2 domain: the callers fold scale·log2(e)
# into q, so the kernels' softmax uses exp2 directly. The VPU's exp is
# exp2 plus a multiply pass — folding the multiply into the [S, D] q
# prep deletes one full [BQ, BK] VPU pass per score tile. lse crosses
# the kernel boundary in the NATURAL-log domain (ring attention merges
# partial softmaxes with it).
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453


from .. import envs
from ._common import cost_estimate as _cost_estimate
from ._common import interpret_mode as _interpret
from ._common import mosaic_trace_ctx as _mosaic_ctx


def _fit_block(block, n):
    """Largest useful block <= `block` for length n, 128-aligned (Mosaic
    requires lane-tile-aligned vector loads; min(block, n) could yield e.g.
    300 which fails to legalize)."""
    return min(block, -(-n // 128) * 128)


def _attn_cost(bh, sp, skp, d, itemsize, causal, matmuls, extra_bytes=0,
               name=None):
    """pl.CostEstimate for a dense-attention kernel: `matmuls` [Sq, Sk]·D
    contractions over the (clamped-to-half under causal) score area, one
    exp per score, and the q/k/v/o-sized HBM traffic. ``name`` is the
    site's stable kernel name for ``kernel_cost_table`` attribution."""
    cf = 0.5 if causal else 1.0
    return _cost_estimate(
        flops=matmuls * 2 * bh * sp * skp * d * cf,
        transcendentals=bh * sp * skp * cf,
        bytes_accessed=bh * (2 * sp + 2 * skp) * d * itemsize + extra_bytes,
        name=name)


def _pad_rows(x, multiple):
    """Zero-pad axis 1 up to a multiple; returns (padded, original_len)."""
    n = x.shape[1]
    rem = (-n) % multiple
    if rem:
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, rem)
        x = jnp.pad(x, pad)
    return x, n


def _mask_scores(s, row0, col0, causal, row_limit=None, col_limit=None):
    """Trace-time-composed mask for one [R, C] score tile: causal
    (rows >= cols) and/or row/col validity limits (padding tails). Limits
    passed as None are elided from the trace entirely — a non-causal
    unpadded tile pays zero mask work. Shared by all six kernels (resident
    and streaming, fwd and bwd) so the boundary conditions cannot drift."""
    import numpy as np
    if not causal and row_limit is None and col_limit is None:
        return s
    r, c = s.shape
    ok = None
    cols = (col0 + lax.broadcasted_iota(jnp.int32, (r, c), 1)
            if (causal or col_limit is not None) else None)
    rows = (row0 + lax.broadcasted_iota(jnp.int32, (r, c), 0)
            if (causal or row_limit is not None) else None)
    if col_limit is not None:
        ok = cols < np.int32(col_limit)
    if row_limit is not None:
        t = rows < np.int32(row_limit)
        ok = t if ok is None else ok & t
    if causal:
        t = rows >= cols
        ok = t if ok is None else ok & t
    # strong f32 scalar: a weak Python literal re-canonicalizes to f64
    # when a consumer jit lowers under the package-global x64 (the MLIR
    # verifier rejects it — see the decode/paged strong-typing note)
    return jnp.where(ok, s, jnp.float32(-1e30))


def _tri_mask_const(block_q, block_k):
    """Additive lower-triangular mask tile ([BQ, BK] f32, 0 below/on the
    diagonal, -1e30 above). For self-attention with equal blocks, every
    causal-masked tile IS the diagonal tile, and its mask is identical
    across tiles — so a single precomputed tile turns the per-tile
    iota+compare+select (4-5 VPU passes, measured to cost causal D=64
    attention nearly all of its 2x FLOP advantage) into one add."""
    # int32: the package turns x64 on, and an i64 [BQ, BK] compare is
    # emulated on the TPU's 32-bit vector unit
    r = jnp.arange(block_q, dtype=jnp.int32)[:, None]
    c = jnp.arange(block_k, dtype=jnp.int32)[None, :]
    return jnp.where(r >= c, jnp.float32(0.0), jnp.float32(-1e30))


def _resident_loop_bounds(qi, bq_i, bk_i, seq_k, block_k, causal, mask_kv,
                          lo):
    """Shared masked/unmasked loop-split bounds for the resident forward
    kernels (ONE copy so the causal/kv-padding boundary conditions cannot
    drift between the online and fixed-base variants): returns (nblocks,
    first_masked) with first_masked clamped to at least ``lo`` (the fixed-
    base kernel consumes block 0 outside the loops)."""
    import numpy as np
    nblocks = np.int32(seq_k // block_k)
    if causal:
        # only blocks whose start <= last query position of this tile
        last_q = (qi + np.int32(1)) * bq_i - np.int32(1)
        nblocks = jnp.minimum(nblocks, last_q // bk_i + np.int32(1))
    # first block index that needs any masking: the causal diagonal
    # (rows >= cols can fail once j*bk > qi*bq) and/or the padded tail.
    first_masked = nblocks
    if causal:
        first_masked = jnp.minimum(first_masked, (qi * bq_i) // bk_i)
    if mask_kv:
        first_masked = jnp.minimum(first_masked, nblocks - np.int32(1))
    first_masked = jnp.maximum(first_masked, np.int32(lo))
    return nblocks, first_masked


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k, causal,
                seq_k, kv_len, use_tri=False):
    """seq_k is the PADDED key length (multiple of block_k); kv_len the true
    one — key positions >= kv_len are masked out so padding never attends.

    The softmax scale is FOLDED INTO Q by the caller (q arrives pre-scaled):
    the per-tile `s * scale` was a full [BQ, BK] f32 VPU pass per tile, a
    measurable share of a kernel that is softmax-(VPU-)bound.

    The KV loop is split into an unmasked region (blocks fully below the
    causal diagonal and clear of padding) and a masked tail: the mask iota/
    where work is VPU-side and the kernel is softmax-(VPU-)bound at small D,
    so skipping it on interior blocks is a real win. With use_tri (equal
    blocks, no kv padding) the masked region is exactly the diagonal tile
    and applies the precomputed additive mask — see _tri_mask_const."""
    import numpy as np
    if use_tri:
        tri_ref, o_ref, lse_ref = rest
    else:
        (o_ref, lse_ref), tri_ref = rest, None
    bk_i = np.int32(block_k)  # i32 casts are belt-and-braces; the trace runs
    # under mosaic_trace_ctx (x64 disabled) — see _common.mosaic_trace_ctx
    qi = pl.program_id(1)
    # keep q/k in their storage dtype (bf16) for the dot — the MXU runs
    # bf16 x bf16 -> f32 at full rate, while f32 x f32 is ~8x slower; the
    # fp32 scale is applied to the f32 accumulator after the matmul.
    q = q_ref[0]                                      # [BQ, D]
    bq, d = q.shape
    bq_i = np.int32(bq)
    m = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    mask_kv = kv_len != seq_k
    nblocks, first_masked = _resident_loop_bounds(
        qi, bq_i, bk_i, seq_k, block_k, causal, mask_kv, 0)

    def body(j, carry, *, masked):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk_i, block_k), :]
        v = v_ref[0, pl.ds(j * bk_i, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if masked:
            if use_tri:
                s = s + tri_ref[...]
            else:
                s = _mask_scores(s, qi * bq_i, j * bk_i, causal,
                                 col_limit=kv_len if mask_kv else None)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp2(m - m_new)
        p = jnp.exp2(s - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal or mask_kv:
        carry = lax.fori_loop(np.int32(0), first_masked,
                              functools.partial(body, masked=False),
                              (m, l, acc))
        m, l, acc = lax.fori_loop(first_masked, nblocks,
                                  functools.partial(body, masked=True), carry)
    else:
        m, l, acc = lax.fori_loop(np.int32(0), nblocks,
                                  functools.partial(body, masked=False),
                                  (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # 2-D store ([1, BQ]); Mosaic fails to legalize 1-D vector stores.
    lse_ref[0] = ((m + jnp.log2(jnp.maximum(l, 1e-30))) * _LN2).T


def _fwd_kernel_fixed_base(q_ref, k_ref, v_ref, *rest, block_k, causal,
                           seq_k, kv_len, use_tri=False):
    """FIXED-BASE variant of _fwd_kernel (r5): block 0's row max anchors
    the exponent base for the whole row, so later blocks' p never wait
    on the current block's reduction and acc never rescales — the
    online-max data path (not exp2/sum) was measured as the entire
    0.633-vs-0.821 eff gap on the streaming kernel. Numerics: later
    blocks' p = exp2(s - base) may exceed 1; f32 holds 2^127 of
    headroom, so results are exact unless a row's true max exceeds
    block 0's by >~100 log2 units (no realistic attention; the failure
    is a LOUD inf/nan, never silent). Selected only when the extra
    s0/p0 live ranges fit scoped VMEM (see _flash_fwd)."""
    import numpy as np
    if use_tri:
        tri_ref, o_ref, lse_ref = rest
    else:
        (o_ref, lse_ref), tri_ref = rest, None
    bk_i = np.int32(block_k)
    qi = pl.program_id(1)
    q = q_ref[0]                                      # [BQ, D]
    bq, d = q.shape
    bq_i = np.int32(bq)

    mask_kv = kv_len != seq_k
    nblocks, first_masked = _resident_loop_bounds(
        qi, bq_i, bk_i, seq_k, block_k, causal, mask_kv, 1)

    # block 0 anchors the base; masked unconditionally (no-op for
    # qi > 0 causal rows, keeps the base finite when block 0 IS the
    # diagonal or kv_len < block_k). Block 0 always has a live column.
    k0 = k_ref[0, pl.ds(0, block_k), :]
    v0 = v_ref[0, pl.ds(0, block_k), :]
    s0 = jnp.dot(q, k0.T, preferred_element_type=jnp.float32)
    s0 = _mask_scores(s0, qi * bq_i, 0, causal,
                      col_limit=kv_len if mask_kv else None)
    base = s0.max(axis=-1, keepdims=True)
    p0 = jnp.exp2(s0 - base)
    l = p0.sum(axis=-1, keepdims=True)
    acc = jnp.dot(p0.astype(v0.dtype), v0,
                  preferred_element_type=jnp.float32)

    def body(j, carry, *, masked):
        l, acc = carry
        k = k_ref[0, pl.ds(j * bk_i, block_k), :]
        v = v_ref[0, pl.ds(j * bk_i, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if masked:
            if use_tri:
                s = s + tri_ref[...]
            else:
                s = _mask_scores(s, qi * bq_i, j * bk_i, causal,
                                 col_limit=kv_len if mask_kv else None)
        p = jnp.exp2(s - base)
        l_new = l + p.sum(axis=-1, keepdims=True)
        acc_new = acc + jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32)
        return l_new, acc_new

    if causal or mask_kv:
        carry = lax.fori_loop(np.int32(1), first_masked,
                              functools.partial(body, masked=False),
                              (l, acc))
        l, acc = lax.fori_loop(first_masked, nblocks,
                               functools.partial(body, masked=True), carry)
    else:
        l, acc = lax.fori_loop(np.int32(1), nblocks,
                               functools.partial(body, masked=False),
                               (l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = ((base + jnp.log2(jnp.maximum(l, 1e-30))) * _LN2).T


# Escape hatch (ADVICE r5): the fixed-base scheme anchors every row's
# exponent base on block/tile 0's max, which overflows (LOUD inf/nan, never
# silent) if a later block's true row max exceeds it by >~100 log2 units.
# Callers with such heavy-tailed logits set PADDLE_TPU_FLASH_SOFTMAX=online
# to force the unconditionally-stable online-softmax recurrence in every
# kernel that has a fixed-base variant (resident forward, streaming
# forward, decode slabs). Read per call so tests can flip it via
# monkeypatched env.
ENV_FLASH_SOFTMAX = "PADDLE_TPU_FLASH_SOFTMAX"


def softmax_mode() -> str:
    """'auto' (fixed-base wherever its VMEM budget fits) or 'online'."""
    return envs.get(ENV_FLASH_SOFTMAX)


# scoped-VMEM budget for selecting the fixed-base resident kernel: its
# extra s0/p0 live ranges cost ~2 more [BQ, BK] f32 buffers than the
# online kernel (measured: flagship 1024^2 blocks hit 16.02M > 16M)
_FB_RESIDENT_BUDGET = 13 * 1024 * 1024


def _fb_resident_fits(skp, d, bq, bk, itemsize):
    kv = 2 * skp * d * itemsize * 2          # k+v, double-buffered
    sp = 4 * bq * bk * 4                     # s0/p0 + loop s/p, f32
    io = 2 * bq * d * itemsize * 2           # q + o
    tri = bq * bk * 4
    return kv + sp + io + tri < _FB_RESIDENT_BUDGET


def _resident_kernel_choice(skp, d, bq, bk, itemsize):
    """The resident forward kernel _flash_fwd will run: fixed-base when the
    escape hatch is off and its scoped-VMEM stack fits, else online."""
    if softmax_mode() == "online":
        return _fwd_kernel
    return (_fwd_kernel_fixed_base
            if _fb_resident_fits(skp, d, bq, bk, itemsize) else _fwd_kernel)


# whole-KV-in-VMEM ceiling: above this the forward streams KV tiles through
# a third grid dimension instead. Empirical (v5e, 16MB scoped vmem): the
# resident kernel's scoped stack is ~2x(K+V) (double buffering) + ~1.3MB, so
# K+V beyond ~3MB (S=8192 at D=128 bf16 measured 17.33M > 16M) must stream.
STREAM_KV_BYTES = 3 * 2 ** 20


def _fwd_kernel_stream(q_ref, k_ref, v_ref, *rest, block_k, causal, kv_len,
                       seq_k, n_k, use_tri=False, online=False):
    """Streaming variant: grid (BH, n_q, n_k); one KV tile per step, online
    stats in VMEM scratch persisted across the innermost (sequential) k
    steps. Removes the whole-KV VMEM residency ceiling (S beyond ~12k at
    D=128). Perf notes (profiled on-device at S=16k, D=128, 1024x1024
    tiles, from device spans; see bench.py long_seq):

    - seq_k is the PADDED key length, a Python int: when kv_len == seq_k
      (no padding) the tail compare is elided at trace time, and a
      non-causal unpadded call runs with no mask work at all.
    - use_tri (equal blocks, no kv padding): the only tiles the causal
      mask BITES are the ki == qi diagonal tiles, so the iota+compare+
      select (multiple VPU passes on EVERY live tile of a VPU-bound
      kernel) collapses to one fused multiply-add of a precomputed
      additive tri tile by a per-step scalar flag. An earlier lax.cond
      boundary/interior split measured 0.34 eff vs 0.55 for the plain
      where() — Mosaic branches defeat the pipeline; the scalar-flag
      multiply keeps the body branch-free.
    - fully-above-diagonal causal tiles are never DMA'd: the caller clamps
      the k/v BlockSpec index to the last needed tile, so Mosaic sees an
      unchanged block index and skips the copy (see _kv_clamp_map;
      profiled 0.55 -> 0.60 eff).
    - finalize at a dynamic last-needed index measured slightly SLOWER
      than writing at n_k - 1; keep the static finalize."""
    import numpy as np
    if use_tri:
        tri_ref, o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        (o_ref, lse_ref, m_s, l_s, acc_s), tri_ref = rest, None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq = q_ref.shape[1]
    bq_i, bk_i = np.int32(bq), np.int32(block_k)

    start = ki * bk_i
    mask_kv = kv_len != seq_k
    needed = start < np.int32(kv_len)
    if causal:
        last_q = (qi + np.int32(1)) * bq_i - np.int32(1)
        needed = jnp.logical_and(needed, start <= last_q)

    # FIXED-BASE softmax (r5, see _fwd_kernel): tile 0's row max anchors
    # the exponent base for all later tiles, so p never waits on the
    # current tile's reduction and acc never rescales (measured 0.633 ->
    # 0.82 eff at S=32k; the exp2+sum are free, the online-max data
    # path was the whole gap). Tile 0 always has a live column. With
    # online=True (PADDLE_TPU_FLASH_SOFTMAX=online) m_s instead carries
    # the running row max and l/acc rescale each tile — the
    # unconditionally-stable recurrence for heavy-tailed logits.
    @pl.when(ki == 0)
    def _first():
        q = q_ref[0]
        s = jnp.dot(q, k_ref[0].T, preferred_element_type=jnp.float32)
        # mask unconditionally: no-op for qi > 0 causal rows, keeps the
        # base finite on the qi == 0 diagonal / short-kv tiles
        s = _mask_scores(s, qi * bq_i, 0, causal,
                         col_limit=kv_len if mask_kv else None)
        base = s.max(axis=-1, keepdims=True)
        p = jnp.exp2(s - base)
        m_s[...] = jnp.broadcast_to(base, m_s.shape)
        l_s[...] = jnp.broadcast_to(p.sum(axis=-1, keepdims=True),
                                    l_s.shape)
        acc_s[...] = jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                             preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(needed, ki > 0))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if use_tri:
            # equal blocks: diagonal tile iff ki == qi (bq == bk)
            diag = (ki == qi).astype(jnp.float32)
            s = s + tri_ref[...] * diag
        else:
            s = _mask_scores(s, qi * bq_i, start, causal,
                             col_limit=kv_len if mask_kv else None)
        if online:
            m_prev = m_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.exp2(s - m_new)
            m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
            l_s[...] = l_s[...] * alpha + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        else:
            base = m_s[:, :1]
            p = jnp.exp2(s - base)
            l_s[...] = l_s[...] + jnp.broadcast_to(
                p.sum(axis=-1, keepdims=True), l_s.shape)
            acc_s[...] = acc_s[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ki == np.int32(n_k - 1))
    def _finalize():
        m = m_s[:, :1]
        l = l_s[:, :1]
        o_ref[0] = (acc_s[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = ((m + jnp.log2(jnp.maximum(l, 1e-30))) * _LN2).T


def _kv_clamp_map(block_q, block_k, causal):
    """k/v BlockSpec index map for (bh, n_q, n_k) streaming grids: under
    causal, clamp the k tile index to the last tile this q tile attends to,
    so fully-above-diagonal steps present an UNCHANGED block index and
    Mosaic's pipeline skips their DMA entirely (the compute is already
    gated in-kernel). ~2x bandwidth saved on causal streams."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def _map(b, i, j):
        jmax = ((i + 1) * block_q - 1) // block_k
        return (b, jnp.minimum(j, jmax), 0)

    return _map


def _q_clamp_map(block_q, block_k, causal, stat=False):
    """q-side (and lse/delta when stat=True) BlockSpec index map for
    (bh, n_k, n_q) streaming dK/dV grids: under causal, clamp the q tile
    index UP to the first tile at/below the diagonal for this k tile, so
    fully-above-diagonal steps re-present the same block index and skip
    their DMA (dual of _kv_clamp_map)."""
    if not causal:
        return ((lambda b, j, i: (b, 0, i)) if stat
                else (lambda b, j, i: (b, i, 0)))

    def _map(b, j, i):
        imin = (j * block_k) // block_q
        i = jnp.maximum(i, imin)
        return (b, 0, i) if stat else (b, i, 0)

    return _map


def _flash_fwd_stream(qp, kp, vp, causal, block_q, block_k, sk,
                      out_dtype):
    bh, sp, d = qp.shape
    skp = kp.shape[1]
    n_k = skp // block_k
    use_tri = causal and sk == skp and block_q == block_k
    kernel = functools.partial(_fwd_kernel_stream, block_k=block_k,
                               causal=causal, kv_len=sk,
                               seq_k=skp, n_k=n_k, use_tri=use_tri,
                               online=softmax_mode() == "online")
    kv_map = _kv_clamp_map(block_q, block_k, causal)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
    ]
    args = [qp, kp, vp]
    if use_tri:
        in_specs.append(pl.BlockSpec((block_q, block_k),
                                     lambda b, i, j: (0, 0)))
        args.append(_tri_mask_const(block_q, block_k))
    with _mosaic_ctx():
        return pl.pallas_call(
            kernel,
            grid=(bh, sp // block_q, n_k),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qp.shape, out_dtype),
                jax.ShapeDtypeStruct((bh, 1, sp), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            cost_estimate=_attn_cost(bh, sp, skp, d, qp.dtype.itemsize,
                                     causal, matmuls=2,
                                     name="flash.fwd_stream"),
            interpret=_interpret(),
        )(*args)


# Scoped-VMEM window of the resident forward. At 1024x1024 tiles the
# double-buffered f32 tri-mask operand alone is 8M and the s/p score
# temporaries another 8M, so the call sits on the compiler's 16M default:
# v5e's compiler (libtpu 0.0.34) counts 16.02M for it inside a 7B-wide
# train step and refuses. The default is a guardrail, not the hardware
# (128M on v5e), and the backward calls below already state 48-80M; the
# tiles stay at the measured-best 1024 and the limit is stated instead.
_FWD_RESIDENT_VMEM_LIMIT = 32 * 1024 * 1024


def _small_d_blocks(d, block_q, block_k):
    """At D<=64 the kernel is at the MXU's half-rate (K=64) ceiling and
    512x512 tiles measure ~10% faster than 1024x1024 (smaller tiles keep
    the VPU softmax overlapped); only shrink caller DEFAULTS, never an
    explicit smaller choice."""
    if d <= 64:
        return min(block_q, 512), min(block_k, 512)
    return block_q, block_k


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    """q, k, v: [BH, S, D] (same head count). Returns (o, lse).

    Ragged sequence lengths are handled by zero-padding to block multiples
    (manual `pl.ds` slices clamp out-of-bounds starts, which would silently
    re-read earlier rows) and masking padded key positions."""
    bh, s, d = q.shape
    sk = k.shape[1]
    block_q, block_k = _small_d_blocks(d, block_q, block_k)
    block_q = _fit_block(block_q, s)
    block_k = _fit_block(block_k, sk)
    # fold the softmax scale AND the exp->exp2 conversion into q once
    # ([S, D] elementwise) instead of per score tile ([BQ, BK] x n_tiles);
    # scale=None marks q as ALREADY pre-scaled (the custom-vjp path saves
    # q̃ in its residuals so the backward reuses it)
    if scale is not None:
        q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    qp, _ = _pad_rows(q, block_q)
    kp, _ = _pad_rows(k, block_k)
    vp, _ = _pad_rows(v, block_k)
    sp, skp = qp.shape[1], kp.shape[1]
    if 2 * skp * d * k.dtype.itemsize > STREAM_KV_BYTES:
        o, lse = _flash_fwd_stream(qp, kp, vp, causal, block_q,
                                   block_k, sk, q.dtype)
        return o[:, :s], lse.reshape(bh, sp)[:, :s]
    grid = (bh, sp // block_q)
    use_tri = causal and sk == skp and block_q == block_k
    kern_fn = _resident_kernel_choice(skp, d, block_q, block_k,
                                      q.dtype.itemsize)
    kernel = functools.partial(kern_fn, block_k=block_k, causal=causal,
                               seq_k=skp, kv_len=sk,
                               use_tri=use_tri)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, skp, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, skp, d), lambda b, i: (b, 0, 0)),
    ]
    args = [qp, kp, vp]
    if use_tri:
        in_specs.append(pl.BlockSpec((block_q, block_k), lambda b, i: (0, 0)))
        args.append(_tri_mask_const(block_q, block_k))
    with _mosaic_ctx():
        o, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qp.shape, q.dtype),
                jax.ShapeDtypeStruct((bh, 1, sp), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FWD_RESIDENT_VMEM_LIMIT),
            cost_estimate=_attn_cost(bh, sp, skp, d, q.dtype.itemsize,
                                     causal, matmuls=2,
                                     name="flash.fwd"),
            interpret=_interpret(),
        )(*args)
    return o[:, :s], lse.reshape(bh, sp)[:, :s]


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, block_q, causal, seq_q, q_len,
                    use_tri=False):
    """dK/dV: grid (bh, k_blocks); inner loop over q tiles >= the diagonal.

    q arrives PRE-SCALED (q̃ = scale·q, folded by the caller): with
    ds̃ = P∘(dP−δ) (no scale), dK = scale·ds̃ᵀ·q = ds̃ᵀ·q̃ exactly — both
    per-tile scale multiplies vanish. dV = PᵀdO is scale-free anyway.

    seq_q is the padded query length (block_q multiple); q rows >= q_len are
    zero padding and get masked so exp(0 - lse_pad) can't contribute.
    use_tri: see _tri_mask_const."""
    import numpy as np
    if use_tri:
        tri_ref, dk_ref, dv_ref = rest
    else:
        (dk_ref, dv_ref), tri_ref = rest, None
    ki = pl.program_id(1)
    k = k_ref[0]                                  # [BK, D] storage dtype
    v = v_ref[0]
    bk, d = k.shape
    bq_i = np.int32(block_q)
    bk_i = np.int32(bk)
    acc_dk = jnp.zeros((bk, d), jnp.float32)
    acc_dv = jnp.zeros((bk, d), jnp.float32)
    mask_q = q_len != seq_q
    nq = np.int32(seq_q // block_q)
    start = (ki * bk_i) // bq_i if causal else np.int32(0)

    def body(i, carry, *, masked):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * bq_i, block_q), :]        # [BQ, D]
        dob = do_ref[0, pl.ds(i * bq_i, block_q), :]
        lseb = lse_ref[0, 0, pl.ds(i * bq_i, block_q)]    # [BQ] f32
        deltab = delta_ref[0, 0, pl.ds(i * bq_i, block_q)]
        s = jnp.dot(qb, k.T, preferred_element_type=jnp.float32)
        if masked:
            if use_tri:
                s = s + tri_ref[...]
            else:
                s = _mask_scores(s, i * bq_i, ki * bk_i, causal,
                                 row_limit=q_len if mask_q else None)
        p = jnp.exp2(s - lseb[:, None])                    # [BQ, BK] f32
        p_lo = p.astype(v.dtype)
        dv = dv + jnp.dot(p_lo.T, dob, preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - deltab[:, None])).astype(v.dtype)
        dk = dk + jnp.dot(ds.T, qb, preferred_element_type=jnp.float32)
        return dk, dv

    if causal or mask_q:
        # q tiles straddling the causal diagonal need the mask; tiles fully
        # below it don't; the last tile needs it again when q is padded.
        if causal:
            diag_end = -((ki * bk_i + bk_i) // -bq_i)     # ceil-div
            diag_end = jnp.clip(diag_end, start, nq)
        else:
            diag_end = start
        un_end = jnp.maximum(diag_end, nq - np.int32(1)) if mask_q else nq
        carry = lax.fori_loop(start, diag_end,
                              functools.partial(body, masked=True),
                              (acc_dk, acc_dv))
        carry = lax.fori_loop(diag_end, un_end,
                              functools.partial(body, masked=False), carry)
        acc_dk, acc_dv = lax.fori_loop(un_end, nq,
                                       functools.partial(body, masked=True),
                                       carry)
    else:
        acc_dk, acc_dv = lax.fori_loop(start, nq,
                                       functools.partial(body, masked=False),
                                       (acc_dk, acc_dv))
    # q̃ carries an extra log2e (log2-domain scores); undo it on dK only
    dk_ref[0] = (acc_dk * _LN2).astype(dk_ref.dtype)
    dv_ref[0] = acc_dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, block_k, causal, scale, seq_k, kv_len,
                   use_tri=False):
    """dQ: grid (bh, q_blocks); inner loop over k tiles <= the diagonal.
    q arrives pre-scaled (see _bwd_dkv_kernel): dQ = scale·(ds̃·K), with
    the single scale multiply applied to the [BQ, D] accumulator at
    finalize instead of per [BQ, BK] score tile.
    seq_k is padded; key positions >= kv_len are masked out.
    use_tri: see _tri_mask_const."""
    import numpy as np
    if use_tri:
        tri_ref, dq_ref = rest
    else:
        (dq_ref,), tri_ref = rest, None
    qi = pl.program_id(1)
    qb = q_ref[0]                                 # [BQ, D]
    dob = do_ref[0]
    bq, d = qb.shape
    bq_i = np.int32(bq)
    bk_i = np.int32(block_k)
    lseb = lse_ref[0, 0, :]                       # [BQ]
    deltab = delta_ref[0, 0, :]
    acc = jnp.zeros((bq, d), jnp.float32)
    mask_kv = kv_len != seq_k
    nblocks = np.int32(seq_k // block_k)
    if causal:
        last_q = (qi + np.int32(1)) * bq_i - np.int32(1)
        nblocks = jnp.minimum(nblocks, last_q // bk_i + np.int32(1))

    def body(j, acc, *, masked):
        kb = k_ref[0, pl.ds(j * bk_i, block_k), :]
        vb = v_ref[0, pl.ds(j * bk_i, block_k), :]
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32)
        if masked:
            if use_tri:
                s = s + tri_ref[...]
            else:
                s = _mask_scores(s, qi * bq_i, j * bk_i, causal,
                                 col_limit=kv_len if mask_kv else None)
        p = jnp.exp2(s - lseb[:, None])
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - deltab[:, None])).astype(kb.dtype)
        return acc + jnp.dot(ds, kb, preferred_element_type=jnp.float32)

    if causal or mask_kv:
        first_masked = nblocks
        if causal:
            first_masked = jnp.minimum(first_masked, (qi * bq_i) // bk_i)
        if mask_kv:
            first_masked = jnp.minimum(first_masked, nblocks - np.int32(1))
        first_masked = jnp.maximum(first_masked, np.int32(0))
        acc = lax.fori_loop(np.int32(0), first_masked,
                            functools.partial(body, masked=False), acc)
        acc = lax.fori_loop(first_masked, nblocks,
                            functools.partial(body, masked=True), acc)
    else:
        acc = lax.fori_loop(np.int32(0), nblocks,
                            functools.partial(body, masked=False), acc)
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                      q_prescaled=False):
    """Pallas FA2 backward: tiles stay in VMEM (the jnp formulation streamed
    [S, BK] intermediates through HBM — bandwidth-bound).

    Ragged lengths: inputs are zero-padded to block multiples with padded
    positions masked in the kernels (see _flash_fwd). Known limit: each
    kernel stages the full opposing sequence (q/do resp. k/v) in VMEM per
    grid step, so VMEM bounds the practical single-shard sequence length
    (~16k at d=64 on v5e); longer contexts belong on the ring-attention
    path which shards the sequence."""
    bh, s, d = q.shape
    sk = k.shape[1]
    block_q, block_k = _small_d_blocks(d, block_q, block_k)
    block_q = _fit_block(block_q, s)
    block_k = _fit_block(block_k, sk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qp, _ = _pad_rows(q, block_q)
    dop, _ = _pad_rows(do, block_q)
    kp, _ = _pad_rows(k, block_k)
    vp, _ = _pad_rows(v, block_k)
    sp, skp = qp.shape[1], kp.shape[1]
    lse3, _ = _pad_rows(lse.reshape(bh, s, 1), block_q)
    delta3, _ = _pad_rows(delta.reshape(bh, s, 1), block_q)
    lse3 = lse3.reshape(bh, 1, sp)
    delta3 = delta3.reshape(bh, 1, sp)

    dq, dk, dv = _bwd_pallas_calls(qp, kp, vp, dop, lse3, delta3, causal,
                                   scale, block_q, block_k, q_len=s,
                                   kv_len=sk, q_prescaled=q_prescaled)
    return dq[:, :s], dk[:, :sk], dv[:, :sk]




def _bwd_fused_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             dqp_ref, dk_ref, dv_ref, dk_s, dv_s, dq_s, *,
                             block_q, block_k, causal, q_len, seq_q,
                             n_q, n_sub, col_tile0=0):
    """Fused streaming backward: ONE pass per (k-tile, q-tile) computes all
    five FA2 matmuls (S=QKᵀ, dP=dO·Vᵀ, dV=PᵀdO, dQ+=dS·K, dK+=dSᵀQ).

    The previous split (dK/dV kernel + dQ kernel) recomputed S and dP in
    both kernels — 7 matmuls per tile pair, capping backward efficiency
    at 5/7 of forward (measured r3: bwd 0.42-0.43 vs fwd 0.60-0.64).

    Grid (bh, n_kdma, n_q, n_sub): the k/v DMA block (bkdma = n_sub
    compute tiles) amortizes one fetch over the whole inner sweep, while
    each compute sub-tile is its own grid step so causal liveness gates
    at COMPUTE granularity (an unrolled in-kernel sub loop wasted a full
    dead sub-tile on every diagonal DMA block, ~5% at S=32k, and its n_sub
    live intermediates blew VMEM past bkdma=2048).

    dK/dV accumulate in VMEM scratch (slot = sub index) across the inner
    (q, sub) sweep. dQ accumulates over the OUTER kv dim, which scratch
    cannot span — each (kv-block, q-tile) window accumulates sub
    contributions in f32 scratch and flushes once, at the last LIVE sub,
    to a per-kv-block partial (grid-indexed output, the splash-attention
    pattern); the caller reduces partials with a liveness-masked sum (dead
    (j, i) slots are never written — their q-side index maps clamp to the
    first live tile, so they cost neither DMA nor flush)."""
    import numpy as np
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    si = pl.program_id(3)
    bq_i, bk_i = np.int32(block_q), np.int32(block_k)
    ns_i = np.int32(n_sub)
    # ABSOLUTE compute-tile column index (col_tile0 = this kv chunk's
    # offset when the caller chunks long sequences)
    ct = np.int32(col_tile0) + ki * ns_i + si
    mask_q = q_len != seq_q

    @pl.when(jnp.logical_and(qi == 0, si == 0))
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    if causal:
        needed = (qi + 1) * bq_i > ct * bk_i
        # last live sub of this (kv-block, q-tile) window: flush dq there
        si_last = jnp.clip(
            ((qi + 1) * bq_i - 1) // bk_i - np.int32(col_tile0)
            - ki * ns_i, np.int32(0), ns_i - 1)
    else:
        needed = si == si
        si_last = ns_i - 1

    @pl.when(needed)
    def _compute():
        qb = q_ref[0]
        dob = do_ref[0]
        lseb = lse_ref[0, 0, :]
        deltab = delta_ref[0, 0, :]
        k = k_ref[0, pl.ds(si * bk_i, block_k), :]
        v = v_ref[0, pl.ds(si * bk_i, block_k), :]
        s = jnp.dot(qb, k.T, preferred_element_type=jnp.float32)
        # iota mask, not a precomputed tri tile: the bwd kernel is
        # MXU-bound (VPU has slack) and the 4MB tri constant pushed the
        # bkdma=4096 configuration over the 16M scoped-VMEM limit
        s = _mask_scores(s, qi * bq_i, ct * bk_i, causal,
                         row_limit=q_len if mask_q else None)
        p = jnp.exp2(s - lseb[:, None])
        p_lo = p.astype(v.dtype)
        sl = pl.ds(si * bk_i, block_k)
        dv_s[sl, :] = dv_s[sl, :] + jnp.dot(
            p_lo.T, dob, preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - deltab[:, None])).astype(v.dtype)
        dk_s[sl, :] = dk_s[sl, :] + jnp.dot(
            ds.T, qb, preferred_element_type=jnp.float32)
        contrib = jnp.dot(ds, k, preferred_element_type=jnp.float32)
        acc = jnp.where(si == 0, contrib, dq_s[...] + contrib)
        dq_s[...] = acc

        @pl.when(si == si_last)
        def _flush_dq():
            dqp_ref[0, 0] = acc.astype(dqp_ref.dtype)

    @pl.when(jnp.logical_and(qi == np.int32(n_q - 1), si == ns_i - 1))
    def _finalize():
        # q̃ carries an extra log2e (log2-domain scores); undo it on dK
        dk_ref[0] = (dk_s[...] * _LN2).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# k/v DMA block of the fused backward = this multiple of the compute tile
# (bounded by VMEM: dk/dv scratch 2·bkdma·D f32 + double-buffered k/v
# DMA windows; one sub-tile of matmul intermediates regardless of mult)
_BWD_KV_DMA_MULT = 8


# upper bound on dq-partial copies per pallas_call: the partial buffer is
# n_k x full-dq, which would grow quadratically with S — beyond this many
# kv DMA blocks the kv dimension is chunked at the XLA level instead
# (fixed partial footprint per chunk, dq accumulated across chunks)
_BWD_MAX_DQ_PARTIALS = 16


def _bwd_fused_stream_call(qp, kp, vp, dop, lse3, delta3, causal, scale,
                           block_q, block_k, q_len):
    """Fused backward: dq reduced from per-kv-DMA-block partials by a
    liveness-masked XLA sum, kv dimension chunked so the partial buffer
    stays bounded (<= _BWD_MAX_DQ_PARTIALS full-dq copies per chunk
    regardless of S)."""
    bh, sp, d = qp.shape
    skp = kp.shape[1]
    bkdma = block_k * _BWD_KV_DMA_MULT
    while skp % bkdma:
        bkdma -= block_k
    rows_per_chunk = _BWD_MAX_DQ_PARTIALS * bkdma
    if skp <= rows_per_chunk:
        dq32, dk, dv = _bwd_fused_stream_chunk(
            qp, kp, vp, dop, lse3, delta3, causal, block_q, block_k,
            q_len, bkdma, col_tile0=0)
        return (dq32 * scale).astype(qp.dtype), dk, dv
    dq32 = None
    dks, dvs = [], []
    for c0 in range(0, skp, rows_per_chunk):
        kc = kp[:, c0:c0 + rows_per_chunk]
        vc = vp[:, c0:c0 + rows_per_chunk]
        dqc, dkc, dvc = _bwd_fused_stream_chunk(
            qp, kc, vc, dop, lse3, delta3, causal, block_q, block_k,
            q_len, bkdma, col_tile0=c0 // block_k)
        dq32 = dqc if dq32 is None else dq32 + dqc
        dks.append(dkc)
        dvs.append(dvc)
    return ((dq32 * scale).astype(qp.dtype),
            jnp.concatenate(dks, axis=1), jnp.concatenate(dvs, axis=1))


def _bwd_fused_stream_chunk(qp, kp, vp, dop, lse3, delta3, causal,
                            block_q, block_k, q_len, bkdma, col_tile0):
    """One fused-backward pallas_call over a kv slice starting at absolute
    column tile `col_tile0`: grid (bh, n_kdma, n_q, n_sub); returns
    (dq_chunk f32 unscaled, dk_chunk, dv_chunk)."""
    bh, sp, d = qp.shape
    skp = kp.shape[1]
    n_q = sp // block_q
    n_k = skp // bkdma
    n_sub = bkdma // block_k
    kernel = functools.partial(_bwd_fused_kernel_stream, block_q=block_q,
                               block_k=block_k, causal=causal,
                               q_len=q_len, seq_q=sp, n_q=n_q,
                               n_sub=n_sub, col_tile0=col_tile0)
    col0_rows = col_tile0 * block_k

    if causal:
        def _iclamp(j, i):
            return jnp.maximum(i, (col0_rows + j * bkdma) // block_q)
    else:
        def _iclamp(j, i):
            return i
    q_map = lambda b, j, i, s_: (b, _iclamp(j, i), 0)
    stat_map = lambda b, j, i, s_: (b, 0, _iclamp(j, i))
    dqp_map = lambda b, j, i, s_: (j, b, _iclamp(j, i), 0)
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),                   # q
        pl.BlockSpec((1, bkdma, d), lambda b, j, i, s_: (b, j, 0)),
        pl.BlockSpec((1, bkdma, d), lambda b, j, i, s_: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), q_map),                   # do
        pl.BlockSpec((1, 1, block_q), stat_map),                # lse
        pl.BlockSpec((1, 1, block_q), stat_map),                # delta
    ]
    args = [qp, kp, vp, dop, lse3, delta3]
    with _mosaic_ctx():
        dqp, dk, dv = pl.pallas_call(
            kernel,
            grid=(bh, n_k, n_q, n_sub),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), dqp_map),
                pl.BlockSpec((1, bkdma, d), lambda b, j, i, s_: (b, j, 0)),
                pl.BlockSpec((1, bkdma, d), lambda b, j, i, s_: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_k, bh, sp, d), qp.dtype),
                jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                jax.ShapeDtypeStruct(vp.shape, vp.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bkdma, d), jnp.float32),
                pltpu.VMEM((bkdma, d), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            # the 16M scoped-VMEM default is a compiler guardrail, not the
            # hardware (v5e has 128M): bkdma=4096 needs ~19M of windows +
            # scratch and halves the dq-partial traffic vs bkdma=2048
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=48 * 1024 * 1024),
            cost_estimate=_attn_cost(
                bh, sp, skp, d, qp.dtype.itemsize, causal, matmuls=5,
                extra_bytes=n_k * bh * sp * d * qp.dtype.itemsize,
                name="flash.bwd_fused_stream"),
            interpret=_interpret(),
        )(*args)
    # Σ_j ds̃·K (scale applied by the caller after cross-chunk
    # accumulation; q was pre-scaled — see _bwd_dkv_kernel docstring).
    # Under causal clamping the dead (j, i) partial slots were never
    # written (garbage): mask them out of the sum — the iota/compare
    # fuses into the reduce.
    if causal:
        row_tile = lax.broadcasted_iota(jnp.int32, (n_k, 1, sp, 1), 2) \
            // block_q
        imin = ((col0_rows + jnp.arange(n_k, dtype=jnp.int32) * bkdma)
                // block_q).reshape(n_k, 1, 1, 1)
        dqp = jnp.where(row_tile >= imin, dqp.astype(jnp.float32),
                        jnp.float32(0.0))
        dq = jnp.sum(dqp, axis=0)
    else:
        dq = jnp.sum(dqp, axis=0, dtype=jnp.float32)
    return dq, dk, dv


# Escape hatch for the default fused flat-schedule backward (r7): 'auto'
# runs the one-pass k-major kernel whenever its scratch fits the budget
# (below); 'split' forces the legacy dispatch — the two resident kernels
# (or the dq-partials streaming pass over the residency ceiling). The
# split resident pair is the bitwise-pinned reference the parity tests
# compare against. Read per call so tests can flip it via monkeypatched
# env (house pattern: ValueError names the variable).
ENV_FLASH_BWD = "PADDLE_TPU_FLASH_BWD"


def dense_bwd_mode() -> str:
    """'auto' (fused flat pass when its scratch fits) or 'split' (legacy
    two-kernel/dq-partials dispatch)."""
    return envs.get(ENV_FLASH_BWD)


def _dense_bwd_lo(n_q, n_k, causal, block_q, block_k):
    """Per-k-tile first live q-tile index (numpy, trace-time static): under
    causal, k tile j only receives gradient from q tiles at/past its own
    diagonal — i >= (j·bk)//bq, exactly the transpose of the forward's
    live set (j·bk <= (i+1)·bq − 1). K tiles past the last q row clamp to
    a single all-masked pair: its p is exactly 0, so dk/dv finalize to
    the zeros the split kernels produce and dq gains nothing, but the
    out blocks are still written (never garbage)."""
    import numpy as np
    if not causal:
        return np.zeros(n_k, dtype=np.int64)
    j = np.arange(n_k, dtype=np.int64)
    return np.minimum((j * block_k) // block_q, n_q - 1)


def _dense_bwd_schedule(n_q, n_k, causal, block_q, block_k):
    """K-major flat schedule over the live (k-tile, q-tile) pairs of a
    DENSE backward — the static-shape analogue of flash_varlen's
    _flat_schedule (no cu; bounds are closed-form, so the arrays are
    concrete at trace time). Returns int32 (ki, qi, first, last) scalar-
    prefetch arrays and n_flat; every step is live."""
    import numpy as np
    lo = _dense_bwd_lo(n_q, n_k, causal, block_q, block_k)
    spans = n_q - lo
    cum = np.concatenate([[0], np.cumsum(spans)])
    n_flat = int(cum[-1])
    s = np.arange(n_flat, dtype=np.int64)
    ki = np.searchsorted(cum, s, side="right") - 1
    qi = lo[ki] + (s - cum[ki])
    first = (s == cum[ki]).astype(np.int32)
    last = (s == cum[ki + 1] - 1).astype(np.int32)
    # int32: the package runs with x64 on, and int64 scalar-prefetch
    # operands break Mosaic's SMEM lowering
    return (jnp.asarray(ki, jnp.int32), jnp.asarray(qi, jnp.int32),
            jnp.asarray(first, jnp.int32), jnp.asarray(last, jnp.int32),
            n_flat)


def _bwd_fused_flat_kernel(ki_ref, qi_ref, first_ref, last_ref,
                           q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dq_ref, dk_ref, dv_ref, dk_s, dv_s, dq_s, *,
                           block_q, block_k, causal, scale, q_len, seq_q,
                           kv_len, seq_k):
    """Fused dK/dV/dQ in ONE pass per (k-tile, q-tile) pair: FLAT grid
    (bh, n_flat) in k-major order — the dense port of flash_varlen's
    _bwd_fused_kernel_varlen. Each pair fetches q/do/lse/delta and k/v
    ONCE and runs all five FA2 matmuls (S=QKᵀ, dP=dO·Vᵀ, dV=PᵀdO,
    dK+=dS̃ᵀQ̃, dQ+=dS̃·K) — the split two-kernel scheme fetched every
    block twice and ran seven (S and dP recomputed in the dq kernel),
    capping backward efficiency at 5/7 of forward.

    dK/dV accumulate in scratch across a k tile's consecutive steps
    (first/last flags). dQ accumulates in a PERSISTENT full-length
    scratch (dq_s, [seq_q, d] f32, zeroed at step 0 of each bh): a q
    tile's steps are NOT consecutive in k-major order, so the running
    partial is re-written to the dq out block on every step — the grid
    is sequential, so the final write-back of each presented block (the
    tile's LAST visit) carries the complete sum. Within one q tile the
    k contributions arrive in increasing j and within one k tile the q
    contributions in increasing i — the SAME f32 accumulation orders as
    the split kernels' inner loops, and _mask_scores' -1e30 overwrite
    on always-masked tiles is a p == 0 no-op — so the fused pass is
    bitwise-equal to the split pair at equal block sizes (pinned in
    tests). q arrives pre-scaled (see _bwd_dkv_kernel): the deferred
    ·scale rides each dq write-back, ·ln2 undoes q̃'s log2e on dK."""
    import numpy as np
    s_idx = pl.program_id(1)
    bq_i, bk_i = np.int32(block_q), np.int32(block_k)
    mask_q = q_len != seq_q
    mask_kv = kv_len != seq_k

    @pl.when(s_idx == 0)
    def _init_dq():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    @pl.when(first_ref[s_idx] == 1)
    def _init_dkv():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    qi = qi_ref[s_idx]
    ki = ki_ref[s_idx]
    qb = q_ref[0]
    kb = k_ref[0]
    vb = v_ref[0]
    dob = do_ref[0]
    lseb = lse_ref[0, 0, :]
    deltab = delta_ref[0, 0, :]
    s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32)
    # iota mask on every step (no masked/unmasked split): the bwd is
    # MXU-bound — the VPU has slack — and interior tiles' where() is a
    # bitwise no-op (see _bwd_fused_kernel_stream)
    s = _mask_scores(s, qi * bq_i, ki * bk_i, causal,
                     row_limit=q_len if mask_q else None,
                     col_limit=kv_len if mask_kv else None)
    p = jnp.exp2(s - lseb[:, None])
    p_lo = p.astype(vb.dtype)
    dv_s[...] = dv_s[...] + jnp.dot(p_lo.T, dob,
                                    preferred_element_type=jnp.float32)
    dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
    ds = (p * (dp - deltab[:, None])).astype(vb.dtype)
    dk_s[...] = dk_s[...] + jnp.dot(ds.T, qb,
                                    preferred_element_type=jnp.float32)
    row = qi * bq_i
    dq_new = dq_s[pl.ds(row, block_q), :] + jnp.dot(
        ds, kb, preferred_element_type=jnp.float32)
    dq_s[pl.ds(row, block_q), :] = dq_new
    dq_ref[0] = (dq_new * scale).astype(dq_ref.dtype)

    @pl.when(last_ref[s_idx] == 1)
    def _flush_dkv():
        # q̃ carries an extra log2e (log2-domain scores); undo it on dK
        dk_ref[0] = (dk_s[...] * _LN2).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# Scoped-VMEM plan for the fused flat backward (same budget split as the
# varlen port it mirrors): the persistent [seq_q, d] f32 dQ accumulator is
# the big consumer, so block sizes are fitted per SHAPE and the Mosaic
# scoped-VMEM window is raised past the 16M guardrail accordingly.
_FLAT_BWD_VMEM_BUDGET = 52 * 1024 * 1024
_FLAT_BWD_VMEM_LIMIT = 80 * 1024 * 1024


def _bwd_flat_vmem_bytes(bq, bk, sp, d, itemsize):
    """Estimated scoped-VMEM footprint of one fused-flat grid step: f32
    scratch (persistent dq + dk/dv accumulators) plus the 6 live input
    windows and 3 out blocks (double-buffered) and the f32 score-tile
    temporaries."""
    scratch = 4 * (sp * d + 2 * bk * d)
    blocks = (2 * bq * d * itemsize      # q, do
              + 2 * bk * d * itemsize    # k, v
              + 2 * bq * 4               # lse, delta
              + bq * d * itemsize        # dq
              + 2 * bk * d * itemsize)   # dk, dv
    temps = 4 * bq * bk * 4              # s/p/dp/ds tiles
    return scratch + 2 * blocks + temps


def _shrink_block(b, n):
    """Next-smaller 128-aligned divisor of n below b (n is 128-aligned)."""
    b -= 128
    while b > 128 and n % b:
        b -= 128
    return max(b, 128)


def _fit_bwd_flat_blocks(block_q, block_k, sp, skp, d, itemsize):
    """_fit_block_t-style fitter (see decode_attention) for the fused flat
    backward: shrink the larger block side until the grid step fits the
    scoped-VMEM budget — hd >= 128 at big tiles would otherwise overrun
    scoped VMEM. Returns (block_q, block_k) or None when even 128x128
    does not fit (the [sp, d] dq scratch alone is over budget — very
    long sequences stay on the dq-partials streaming pass)."""
    bq, bk = block_q, block_k
    while _bwd_flat_vmem_bytes(bq, bk, sp, d, itemsize) \
            > _FLAT_BWD_VMEM_BUDGET:
        if bq <= 128 and bk <= 128:
            return None
        if bq >= bk and bq > 128:
            bq = _shrink_block(bq, sp)
        else:
            bk = _shrink_block(bk, skp)
    return bq, bk


def _bwd_fused_flat_call(qp, kp, vp, dop, lse3, delta3, causal, scale,
                         block_q, block_k, q_len, kv_len):
    """One fused-flat pallas_call over the whole padded backward: grid
    (bh, n_flat) with the (ki, qi, first, last) schedule scalar-prefetched.
    Each q/k/v/do block is fetched exactly once (the flat order revisits
    no pair), vs twice for the split pair — at S=32k this halves the HBM
    read traffic and removes the dq-partials reduction kernel, the lever
    behind the r05 bwd_eff=0.599 -> >=0.7 target."""
    bh, sp, d = qp.shape
    skp = kp.shape[1]
    it = qp.dtype.itemsize
    n_q, n_k = sp // block_q, skp // block_k
    ki_a, qi_a, first_a, last_a, n_flat = _dense_bwd_schedule(
        n_q, n_k, causal, block_q, block_k)
    kernel = functools.partial(_bwd_fused_flat_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale,
                               q_len=q_len, seq_q=sp, kv_len=kv_len,
                               seq_k=skp)
    with _mosaic_ctx():
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(bh, n_flat),
                in_specs=[
                    pl.BlockSpec((1, block_q, d),
                                 lambda b, s, ki, qi, f, l: (b, qi[s], 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda b, s, ki, qi, f, l: (b, ki[s], 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda b, s, ki, qi, f, l: (b, ki[s], 0)),
                    pl.BlockSpec((1, block_q, d),
                                 lambda b, s, ki, qi, f, l: (b, qi[s], 0)),
                    pl.BlockSpec((1, 1, block_q),
                                 lambda b, s, ki, qi, f, l: (b, 0, qi[s])),
                    pl.BlockSpec((1, 1, block_q),
                                 lambda b, s, ki, qi, f, l: (b, 0, qi[s])),
                ],
                out_specs=[
                    pl.BlockSpec((1, block_q, d),
                                 lambda b, s, ki, qi, f, l: (b, qi[s], 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda b, s, ki, qi, f, l: (b, ki[s], 0)),
                    pl.BlockSpec((1, block_k, d),
                                 lambda b, s, ki, qi, f, l: (b, ki[s], 0)),
                ],
                scratch_shapes=[
                    pltpu.VMEM((block_k, d), jnp.float32),
                    pltpu.VMEM((block_k, d), jnp.float32),
                    pltpu.VMEM((sp, d), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(qp.shape, qp.dtype),
                jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                jax.ShapeDtypeStruct(vp.shape, vp.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FLAT_BWD_VMEM_LIMIT),
            cost_estimate=_cost_estimate(
                flops=10 * bh * n_flat * block_q * block_k * d,
                transcendentals=bh * n_flat * block_q * block_k,
                bytes_accessed=(bh * n_flat
                                * (2 * block_q + 2 * block_k) * d * it
                                + bh * (sp + 2 * skp) * d * it),
                name="flash.bwd_fused_flat"),
            interpret=_interpret(),
        )(ki_a, qi_a, first_a, last_a, qp, kp, vp, dop, lse3, delta3)
    return dq, dk, dv


def dense_bwd_schedule_stats(bh, sq, sk, d, dtype, causal,
                             block_q=DEFAULT_BLOCK_Q,
                             block_k=DEFAULT_BLOCK_K):
    """Which backward path _bwd_pallas_calls would run for this shape and
    its flat-schedule geometry — static (no tracing); recorded in
    BENCH_DETAIL next to the bwd_eff rungs."""
    item = jnp.dtype(dtype).itemsize
    block_q, block_k = _small_d_blocks(d, block_q, block_k)
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    sp = -(-sq // block_q) * block_q
    skp = -(-sk // block_k) * block_k
    stats = {"mode": dense_bwd_mode(), "bh": bh, "seq_q": sq, "seq_k": sk,
             "head_dim": d}
    fit = (_fit_bwd_flat_blocks(block_q, block_k, sp, skp, d, item)
           if stats["mode"] == "auto" else None)
    if fit is not None:
        bq, bk = fit
        n_q, n_k = sp // bq, skp // bk
        lo = _dense_bwd_lo(n_q, n_k, causal, bq, bk)
        n_flat = int(n_q * n_k - lo.sum())
        stats.update(path="fused_flat", block_q=bq, block_k=bk,
                     n_flat=n_flat, dead_pairs=n_q * n_k - n_flat,
                     fetches_per_block_pair=1, matmuls_per_pair=5,
                     dq_scratch_bytes=4 * sp * d)
    elif (2 * sp * d * item > STREAM_KV_BYTES
          or 2 * skp * d * item > STREAM_KV_BYTES):
        stats.update(path="fused_stream", block_q=block_q, block_k=block_k,
                     fetches_per_block_pair=1, matmuls_per_pair=5)
    else:
        stats.update(path="split_resident", block_q=block_q,
                     block_k=block_k, fetches_per_block_pair=2,
                     matmuls_per_pair=7)
    return stats


def _bwd_pallas_calls(qp, kp, vp, dop, lse3, delta3, causal, scale, block_q,
                      block_k, q_len, kv_len, q_prescaled=False):
    """Backward pallas_calls on already-padded [BH, Sp, D] operands.
    lse3/delta3: [BH, 1, Sp] f32. Returns padded (dq, dk, dv).

    The softmax scale is folded into q here (see _bwd_dkv_kernel): the
    kernels see q̃ = scale·q and compute dK = ds̃ᵀq̃ exactly; dQ applies
    the single deferred scale to its accumulator.

    Dispatch (r7): the fused FLAT k-major pass (_bwd_fused_flat_call) is
    the default whenever its scratch fits the fitted blocks; past that
    (very long S) the dq-partials streaming pass takes over; the split
    resident pair (dK/dV over k tiles, dQ over q tiles, whole opposing
    side in VMEM) remains as the bitwise-pinned PADDLE_TPU_FLASH_BWD=
    split fallback and the sub-residency leg of that mode."""
    bh, sp, d = qp.shape
    skp = kp.shape[1]
    item = kp.dtype.itemsize
    # log2-domain scores (see module constants): q̃ = scale·log2e·q, lse
    # converted to the log2 domain; the kernels' dK therefore comes out
    # log2e too large and is corrected by ·ln2 at finalize
    if not q_prescaled:
        qp = (qp.astype(jnp.float32) * (scale * _LOG2E)).astype(qp.dtype)
    lse3 = lse3 * _LOG2E
    if dense_bwd_mode() == "auto":
        # DEFAULT (r7): one fused k-major pass, each q/k/v/do block fetched
        # once feeding all five matmuls; bitwise-equal to the split pair at
        # equal blocks. Skipped only when even 128x128 tiles can't fit the
        # persistent [sp, d] dq scratch (very long S falls through to the
        # dq-partials streaming pass) or PADDLE_TPU_FLASH_BWD=split.
        fit = _fit_bwd_flat_blocks(block_q, block_k, sp, skp, d, item)
        if fit is not None:
            return _bwd_fused_flat_call(qp, kp, vp, dop, lse3, delta3,
                                        causal, scale, fit[0], fit[1],
                                        q_len, kv_len)
    if (2 * sp * d * item > STREAM_KV_BYTES
            or 2 * skp * d * item > STREAM_KV_BYTES):
        # the fused kernel streams both sides and does 5 matmuls per tile
        # pair (the old split kernels did 7 — see _bwd_fused_kernel_stream)
        return _bwd_fused_stream_call(qp, kp, vp, dop, lse3, delta3,
                                      causal, scale, block_q, block_k,
                                      q_len)
    dk = dv = None
    dq = None
    use_tri = causal and block_q == block_k
    tri = _tri_mask_const(block_q, block_k) if use_tri else None
    with _mosaic_ctx():
        if dk is None:
            tri_kv = use_tri and q_len == sp
            kv_grid = (bh, skp // block_k)
            in_specs = [
                pl.BlockSpec((1, sp, d), lambda b, j: (b, 0, 0)),     # q
                pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, sp, d), lambda b, j: (b, 0, 0)),     # do
                pl.BlockSpec((1, 1, sp), lambda b, j: (b, 0, 0)),     # lse
                pl.BlockSpec((1, 1, sp), lambda b, j: (b, 0, 0)),   # delta
            ]
            args = [qp, kp, vp, dop, lse3, delta3]
            if tri_kv:
                in_specs.append(pl.BlockSpec((block_q, block_k),
                                             lambda b, j: (0, 0)))
                args.append(tri)
            dk, dv = pl.pallas_call(
                functools.partial(_bwd_dkv_kernel, block_q=block_q,
                                  causal=causal, seq_q=sp,
                                  q_len=q_len, use_tri=tri_kv),
                grid=kv_grid,
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                    jax.ShapeDtypeStruct(vp.shape, vp.dtype),
                ],
                cost_estimate=_attn_cost(bh, sp, skp, d, item, causal,
                                         matmuls=4,
                                         name="flash.bwd_dkv"),
                interpret=_interpret(),
            )(*args)

        if dq is None:
            tri_q = use_tri and kv_len == skp
            q_grid = (bh, sp // block_q)
            in_specs = [
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, skp, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, skp, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            ]
            args = [qp, kp, vp, dop, lse3, delta3]
            if tri_q:
                in_specs.append(pl.BlockSpec((block_q, block_k),
                                             lambda b, i: (0, 0)))
                args.append(tri)
            dq = pl.pallas_call(
                functools.partial(_bwd_dq_kernel, block_k=block_k,
                                  causal=causal, scale=scale, seq_k=skp,
                                  kv_len=kv_len, use_tri=tri_q),
                grid=q_grid,
                in_specs=in_specs,
                out_specs=pl.BlockSpec((1, block_q, d),
                                       lambda b, i: (b, i, 0)),
                out_shape=jax.ShapeDtypeStruct(qp.shape, qp.dtype),
                cost_estimate=_attn_cost(bh, sp, skp, d, item, causal,
                                         matmuls=3,
                                         name="flash.bwd_dq"),
                interpret=_interpret(),
            )(*args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, scale, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return o


def _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k):
    # pre-scale once and save q̃ in the residuals: the backward's own
    # q-prep (another [BH, S, D] multiply + HBM round trip) is skipped
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    o, lse = _flash_fwd(qs, k, v, causal, None, block_q, block_k)
    return o, (qs, k, v, o, lse)


def _flash_attention_bwd(causal, scale, block_q, block_k, res, do):
    qs, k, v, o, lse = res
    return _flash_bwd_pallas(qs, k, v, o, lse, do, causal, scale, block_q,
                             block_k, q_prescaled=True)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention_bshd(q, k, v, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Public entry. q: [B, S, H, D]; k/v: [B, S, Hkv, D] (GQA repeats kv)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    def to_bh(x, seq):
        return x.transpose(0, 2, 1, 3).reshape(b * h, seq, d)

    o = _flash_attention(to_bh(q, s), to_bh(k, sk), to_bh(v, sk),
                         causal, float(scale), block_q, block_k)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# block-level entry points for ring attention (parallel/ring_attention.py):
# per-KV-block flash with the (o, lse) partials exposed so the caller can
# merge partial softmaxes across sequence shards, and the FA2 backward with
# caller-provided GLOBAL lse/delta (the identities hold per block when the
# statistics are global).
# ---------------------------------------------------------------------------

def flash_block_fwd(q, k, v, causal, scale, block_q=DEFAULT_BLOCK_Q,
                    block_k=DEFAULT_BLOCK_K):
    """q/k/v: [BH, S, D]. Returns (o [BH, S, D], lse [BH, S] f32)."""
    return _flash_fwd(q, k, v, causal, float(scale), block_q, block_k)


def flash_block_bwd(q, k, v, do, lse, delta, causal, scale,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """FA2 backward for one KV block with global statistics.

    q/do: [BH, Sq, D]; k/v: [BH, Sk, D]; lse/delta: [BH, Sq] f32 computed
    over the FULL (all-block) attention. Sq/Sk must be 128-aligned (ring
    shards are; enforced here rather than padded because padding q rows
    with lse=0 would make exp(0-lse) contribute garbage to dk/dv).
    Returns (dq, dk, dv)."""
    bh, s, d = q.shape
    sk = k.shape[1]
    if s % 128 or sk % 128:
        raise ValueError(f"flash_block_bwd needs 128-aligned lengths, got "
                         f"q={s}, k={sk}")

    def fit_divisor(block, n):
        # largest 128-multiple <= block that divides n (n is 128-aligned)
        b = min(block, n)
        while n % b:
            b -= 128
        return b

    block_q = fit_divisor(block_q, s)
    block_k = fit_divisor(block_k, sk)
    lse3 = lse.reshape(bh, 1, s)
    delta3 = delta.reshape(bh, 1, s)
    return _bwd_pallas_calls(q, k, v, do, lse3, delta3, causal, float(scale),
                             block_q, block_k, q_len=s, kv_len=sk)
