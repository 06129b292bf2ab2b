"""Falcon-H1 on the serving path: a Mamba-2 mixer beside grouped-query
attention in every block.

A block (``x`` the stream, every layer alike)::

    h  = RMSNorm_in(x)
    x <- x + Mixer(h * ssm_in_multiplier) * ssm_out_multiplier
           + Attn(h * attention_in_multiplier) * attention_out_multiplier
    x <- x + MLP(RMSNorm_ff(x))

The two mixers run on ONE normed input and are summed. Attention is Llama's
(grouped queries, rope in the half-rotation layout over the whole head,
``head_dim`` a key of its own and not ``hidden / heads``) with the keys
scaled by ``key_multiplier`` before they reach the pool; its paged kernels
and the ``_paged_*`` pieces are ``models/llama.py``'s. The mixer is Mamba-2
(``ops/ssm.py`` states the recurrence): ``p = (W_in u) * mup`` with ``mup``
the five ``ssm_multipliers`` spread over ``[z | x | B | C | dt]``, a
depthwise causal convolution of ``mamba_d_conv`` taps and SiLU over
``[x | B | C]``, ``delta = softplus(dt + dt_bias)`` (no clamp), ``A =
-exp(A_log)``, the scan, ``y + D x``, then ``RMSNorm_grouped(y * silu(z))``
(``mamba_norm_before_gate`` false; the mean square over each of the
``mamba_n_groups`` groups of channels) and ``W_out``. The MLP is SwiGLU with
``mlp_multipliers`` on the gate and on the output. ``embedding_multiplier``
scales the embedding, ``lm_head_multiplier`` the logits.

What a sequence keeps between steps is of two kinds, and the engine holds
them apart (``inference/engine.py``): its KV columns in blocks of the paged
pools, and ONE slot of recurrent state: per layer the float32 ``[NH, P, N]``
matrix of the scan and the last ``mamba_d_conv - 1`` inputs of the
convolution. The state is float32 because it is a sum over every token the
sequence has seen; the convolution's columns are three activations, kept in
the model's dtype. The steps take a slot a row beside the block table
(padding rows at the null slot 0) and update both kinds in place.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.rms_norm import fused_rms_norm
from ..ops.rope import build_rope_cache
from ..ops.sampling import sampled, with_board
from ..ops.ssm import ssd_chunk_scan, ssm_state_update
from .llama import (_chunk_window, _mat, _paged_attend_chunk,
                    _paged_attend_rows, _paged_embed, _paged_qkv)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published keys of a ``falcon_h1`` config.json that shape the
    model; frozen and hashable, so it keys the jitted programs as it is."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x "
                             "mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide into their groups")

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads


def falcon_h1_tiny(**over) -> FalconH1Config:
    """A float32 toy for the CPU tests: every mechanism, no width."""
    return FalconH1Config(**dict(dict(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=5, num_key_value_heads=1,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_n_groups=2, mamba_d_state=32, mamba_chunk_size=16,
        embedding_multiplier=2.0, lm_head_multiplier=0.5,
        attention_in_multiplier=1.0, attention_out_multiplier=0.75,
        key_multiplier=0.5, ssm_in_multiplier=1.25, ssm_out_multiplier=0.5,
        ssm_multipliers=(0.7, 1.3, 0.9, 1.1, 1.2),
        mlp_multipliers=(0.8, 0.6), dtype=jnp.float32), **over))


def param_shapes(c: FalconH1Config) -> Dict[str, Any]:
    """The parameter tree as shapes: the layers' leaves stacked on axis 0;
    attention and MLP under Llama's names (its ``_paged_qkv`` reads them),
    the mixer's under ``ssm_``."""
    n, h, i = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    q, kv = c.num_attention_heads * c.head_dim, \
        c.num_key_value_heads * c.head_dim
    return {
        "embed": (c.vocab_size, h),
        "layers": {
            "input_norm": (n, h),
            "q_proj": (n, h, q), "k_proj": (n, h, kv), "v_proj": (n, h, kv),
            "o_proj": (n, q, h),
            "ssm_in_proj": (n, h, c.in_proj_dim),
            "ssm_conv_w": (n, c.conv_dim, c.mamba_d_conv),
            "ssm_conv_b": (n, c.conv_dim),
            "ssm_dt_bias": (n, c.mamba_n_heads),
            "ssm_A_log": (n, c.mamba_n_heads),
            "ssm_D": (n, c.mamba_n_heads),
            "ssm_norm": (n, c.mamba_d_ssm),
            "ssm_out_proj": (n, c.mamba_d_ssm, h),
            "post_norm": (n, h),
            "gate_proj": (n, h, i), "up_proj": (n, h, i),
            "down_proj": (n, i, h),
        },
        "final_norm": (h,),
        "lm_head": (h, c.vocab_size),
    }


def init_falcon_h1_params(c: FalconH1Config, seed: int = 0):
    """Seeded weights for the tests, with the published Mamba-2 starts where
    a normal x 0.02 would leave the recurrence idle: ``A`` uniform in
    [1, 16], ``delta``'s bias the inverse softplus of a log-uniform [0.001,
    0.1], ``D`` one, the convolution's weights at a standard deviation of
    0.5, norm scales one."""
    rng = np.random.RandomState(seed)
    n, nh = c.num_hidden_layers, c.mamba_n_heads

    def make(path, shape):
        name = path[-1].key
        if name.endswith("norm") or name == "ssm_D":
            a = np.ones(shape)
        elif name == "ssm_A_log":
            a = np.log(rng.uniform(1.0, 16.0, (n, nh)))
        elif name == "ssm_dt_bias":
            dt = np.exp(rng.uniform(math.log(1e-3), math.log(0.1), (n, nh)))
            a = dt + np.log(-np.expm1(-dt))
        elif name == "ssm_conv_w":
            a = rng.randn(*shape) * 0.5
        elif name == "ssm_conv_b":
            a = np.zeros(shape)
        else:
            a = rng.randn(*shape) / math.sqrt(shape[-2])
        return jnp.asarray(a, c.dtype)
    return jax.tree_util.tree_map_with_path(
        make, param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))


def init_paged_kv_pool(c: FalconH1Config, num_blocks: int, block_size: int):
    """Llama's pools at this model's attention widths: k and v
    [L, num_blocks, KV*HD, block_size]."""
    shape = (c.num_hidden_layers, num_blocks,
             c.num_key_value_heads * c.head_dim, block_size)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


def init_state(c: FalconH1Config, num_slots: int):
    """(the scan's state [L, slots, NH, P, N] f32, the convolution's last
    inputs [L, slots, d_conv - 1, conv_dim] in the model's dtype): a
    sequence's slot on axis 1, slot 0 the null slot."""
    return (jnp.zeros((c.num_hidden_layers, num_slots, c.mamba_n_heads,
                       c.mamba_d_head, c.mamba_d_state), F32),
            jnp.zeros((c.num_hidden_layers, num_slots, c.mamba_d_conv - 1,
                       c.conv_dim), c.dtype))


# -- the mixer -------------------------------------------------------------------

def _mup(c: FalconH1Config):
    """``ssm_multipliers`` spread over the input projection's segments
    ``[z | x | B | C | dt]``, [in_proj_dim] f32."""
    gn = c.mamba_n_groups * c.mamba_d_state
    widths = (c.mamba_d_ssm, c.mamba_d_ssm, gn, gn, c.mamba_n_heads)
    return jnp.asarray(np.repeat(np.asarray(c.ssm_multipliers, np.float32),
                                 widths))


def _mixer_project(p, u, c: FalconH1Config):
    """Rows u [T, H] (normed, scaled) -> (z [T, d_ssm], the convolution's
    input xBC [T, conv_dim], dt [T, NH] before its bias), in the model's
    dtype."""
    with jax.named_scope("ssm.project"):
        proj = (_mat(u, p["ssm_in_proj"]) * _mup(c).astype(u.dtype))
        return jnp.split(proj, [c.mamba_d_ssm, c.mamba_d_ssm + c.conv_dim],
                         axis=-1)


def _conv(p, window, c: FalconH1Config):
    """The depthwise causal convolution and its SiLU: ``window`` [...,
    T + d_conv - 1, conv_dim] holds each row's d_conv - 1 predecessors
    before it; returns [..., T, conv_dim] f32."""
    k = c.mamba_d_conv
    t = window.shape[-2] - (k - 1)
    w = p["ssm_conv_w"].astype(F32)
    acc = p["ssm_conv_b"].astype(F32)
    for j in range(k):
        acc = acc + w[:, j] * lax.slice_in_dim(window, j, j + t,
                                               axis=-2).astype(F32)
    return jax.nn.silu(acc)


def _split_conv(xbc, c: FalconH1Config):
    """The convolution's output [T, conv_dim] as (x [T, NH, P], B and C
    [T, G, N])."""
    t, gn = xbc.shape[0], c.mamba_n_groups * c.mamba_d_state
    x, bm, cm = jnp.split(xbc, [c.mamba_d_ssm, c.mamba_d_ssm + gn], axis=-1)
    heads = (t, c.mamba_n_groups, c.mamba_d_state)
    return (x.reshape(t, c.mamba_n_heads, c.mamba_d_head),
            bm.reshape(heads), cm.reshape(heads))


def _delta(p, dt, c):
    """(delta = softplus(dt + dt_bias) [T, NH] f32, A [NH] f32 negative)."""
    return (jax.nn.softplus(dt.astype(F32) + p["ssm_dt_bias"].astype(F32)),
            -jnp.exp(p["ssm_A_log"].astype(F32)))


def _mixer_out(p, y, x, z, c: FalconH1Config):
    """What follows the scan: ``y + D x``, the gate, the grouped norm,
    ``W_out``. y [T, NH, P] f32, x [T, NH, P], z [T, d_ssm]."""
    t = y.shape[0]
    y = y + p["ssm_D"].astype(F32)[None, :, None] * x.astype(F32)
    y = y.reshape(t, c.mamba_d_ssm) * jax.nn.silu(z.astype(F32))
    g = y.reshape(t, c.mamba_n_groups, -1)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                      + c.rms_norm_eps)
    y = g.reshape(t, c.mamba_d_ssm) * p["ssm_norm"].astype(F32)
    return _mat(y.astype(c.dtype), p["ssm_out_proj"])


def _scan_chunk(p, xbc, dt, state, layer, slot, start, n_live, c):
    """The convolution and the scan of one prefill chunk of the sequence in
    ``slot`` (xbc [C, conv_dim], dt [C, NH] as ``_mixer_project`` gives
    them): the slot's state and convolution columns come in (zeros where
    ``start == 0``, inside the program: an evicted sequence needs no
    host-side clear) and leave as they are after the chunk's last live
    token. Returns (y [C, NH, P] f32, x [C, NH, P], state)."""
    ssm, conv = state
    k1 = c.mamba_d_conv - 1
    with jax.named_scope("ssm.conv"):
        at = (layer, slot, jnp.int32(0), jnp.int32(0))
        prev = lax.dynamic_slice(conv, at, (1, 1, k1, c.conv_dim))[0, 0]
        prev = jnp.where(start == 0, jnp.zeros_like(prev), prev)
        window = jnp.concatenate([prev, xbc.astype(conv.dtype)])
        # the last three live inputs: rows n_live .. n_live + 2 of the window
        conv = lax.dynamic_update_slice(
            conv, lax.dynamic_slice_in_dim(window, n_live, k1)[None, None],
            at)
        x, bm, cm = _split_conv(_conv(p, window, c).astype(c.dtype), c)
    delta, a = _delta(p, dt, c)
    live = jnp.arange(xbc.shape[0], dtype=jnp.int32)[:, None] < n_live
    with jax.named_scope("ssm.scan"):
        y, ssm = ssd_chunk_scan(
            x, jnp.where(live, delta, jnp.float32(0.0)), a, bm, cm, ssm,
            layer, slot, start, n_live,
            chunk=math.gcd(xbc.shape[0], c.mamba_chunk_size))
    return y, x, (ssm, conv)


def _update_rows(p, xbc, dt, state, layer, slots, c):
    """The convolution and the recurrence of a decode batch, one token a
    row (xbc [B, conv_dim], dt [B, NH]), each row's slot advanced in place
    (padding rows scribble on the null slot 0). Returns (y [B, NH, P] f32,
    x [B, NH, P], state)."""
    ssm, conv = state
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate(
            [conv[layer, slots], xbc.astype(conv.dtype)[:, None]], axis=1)
        conv = conv.at[layer, slots].set(window[:, 1:])
        x, bm, cm = _split_conv(_conv(p, window, c)[:, 0].astype(c.dtype), c)
    delta, a = _delta(p, dt, c)
    with jax.named_scope("ssm.update"):
        y, ssm = ssm_state_update(
            delta[:, :, None] * x.astype(F32), jnp.exp(delta * a[None]), bm,
            cm, ssm, layer, slots)
    return y, x, (ssm, conv)


def _mixer(p, h, state, c: FalconH1Config, parts):
    """The mixer over the normed rows h [T, H]: one input projection, gate,
    norm and output projection for all of them; between those, each of
    ``parts`` ((rows, core): a prefix of ``rows`` rows and the recurrence
    that takes them, ``core(xbc, dt, state) -> (y, x, state)``) in turn."""
    z, xbc, dt = _mixer_project(
        p, (h * c.ssm_in_multiplier).astype(c.dtype), c)
    ys, xs, at = [], [], 0
    for rows, core in parts:
        y, x, state = core(xbc[at:at + rows], dt[at:at + rows], state)
        ys.append(y), xs.append(x)
        at += rows
    return _mixer_out(p, jnp.concatenate(ys), jnp.concatenate(xs), z,
                      c), state


# -- the block and the steps -----------------------------------------------------

def _attn_qkv(p, h, cos, sin, c):
    """Llama's projections of the normed rows, the keys scaled as the pool
    holds them."""
    q, k, v = _paged_qkv(p, (h * c.attention_in_multiplier).astype(c.dtype),
                         cos, sin, c)
    return q, (k * c.key_multiplier).astype(c.dtype), v


def _block_tail(p, x, mixed, attended, c: FalconH1Config):
    """The residual of the two mixers, then the MLP and its residual; rows
    of any leading shape."""
    with jax.named_scope("attn.gqa"):
        attn = _mat(attended, p["o_proj"])
    x = x + (mixed * c.ssm_out_multiplier
             + attn * c.attention_out_multiplier).astype(c.dtype)
    with jax.named_scope("ffn.dense"):
        y = fused_rms_norm(x, p["post_norm"], c.rms_norm_eps)
        gate, out = c.mlp_multipliers
        gated = _mat(y, p["up_proj"]) * jax.nn.silu(
            _mat(y, p["gate_proj"]) * gate)
        return x + (_mat(gated.astype(c.dtype), p["down_proj"])
                    * out).astype(c.dtype)


def _embed(params, ids, c):
    return (_paged_embed(params, ids, c, None)
            * c.embedding_multiplier).astype(c.dtype)


def _logits(params, x, c: FalconH1Config):
    with jax.named_scope("lm_head"):
        y = fused_rms_norm(x, params["final_norm"], c.rms_norm_eps)
        return jnp.matmul(y, params["lm_head"],
                          preferred_element_type=F32) * c.lm_head_multiplier


def _scan_layers(layer_step, x, pools, state, params):
    """``layer_step(x, pools, state, p, layer) -> (x, pools, state)`` over
    the stacked layers, both caches as donated carries."""
    xs = (params["layers"],
          jnp.arange(pools[0].shape[0], dtype=jnp.int32))
    return lax.scan(lambda carry, a: (layer_step(*carry, *a), None),
                    (x, tuple(pools), tuple(state)), xs)[0]


def _state_counts(c, rows, tokens):
    """What the steps return after the caches, [2] i32: slots advanced x
    layers, tokens scanned x layers."""
    n = jnp.int32(c.num_hidden_layers)
    return jnp.stack([n * rows, n * tokens]).astype(jnp.int32)


def falcon_h1_paged_decode_step(params, pools, state, tables, positions, ids,
                                slots, c: FalconH1Config):
    """One token for every row of a decode batch: ``pools`` (k, v) and
    ``tables``, ``positions``, ``ids`` as ``llama_paged_decode_step`` takes
    them; ``state`` (``init_state``) and slots [B] i32, each row's slot of
    it (padding rows: slot 0, block 0, position 0). Returns (logits [B,
    vocab] f32, *pools, *state, counts)."""
    from ..ops.paged_attention import paged_update_walk
    x = _embed(params, ids, c)                                   # [B, H]
    cos, sin = build_rope_cache(ids.shape[0], c.head_dim, base=c.rope_theta,
                                position_ids=positions[:, None])
    walk = paged_update_walk(tables, positions, pools[0].shape[-1])

    def layer_step(x, pools, state, p, layer):
        h = fused_rms_norm(x, p["input_norm"], c.rms_norm_eps)
        mixed, state = _mixer(p, h, state, c, [(
            h.shape[0], lambda xbc, dt, st: _update_rows(
                p, xbc, dt, st, layer, slots, c))])
        with jax.named_scope("attn.gqa"):
            q, k, v = _attn_qkv(p, h[:, None], cos, sin, c)
            ao, pools = _paged_attend_rows(q[:, 0], k[:, 0], v[:, 0], pools,
                                           walk, layer, c)
        return _block_tail(p, x, mixed, ao, c), pools, state

    x, pools, state = _scan_layers(layer_step, x, pools, state, params)
    counts = _state_counts(c, jnp.sum(slots != 0), 0)
    return (_logits(params, x, c), *pools, *state, counts)


def falcon_h1_paged_prefill_chunk(params, pools, state, table_row, start, ids,
                                  n_live, slot, c: FalconH1Config):
    """One chunk of one prompt: ``table_row``, ``start``, ids [C], ``n_live``
    as ``llama_paged_prefill_chunk`` takes them, ``slot`` the sequence's.
    Returns (the last live token's logits [vocab] f32, *pools, *state,
    counts)."""
    n_c = ids.shape[0]
    x = _embed(params, ids, c)                                   # [C, H]
    pidx = start + jnp.arange(n_c, dtype=jnp.int32)
    cos, sin = build_rope_cache(n_c, c.head_dim, base=c.rope_theta,
                                position_ids=pidx)
    where = _chunk_window(table_row, start, n_live, n_c, pools[0].shape[-1])

    def layer_step(x, pools, state, p, layer):
        h = fused_rms_norm(x, p["input_norm"], c.rms_norm_eps)
        mixed, state = _mixer(p, h, state, c, [(
            n_c, lambda xbc, dt, st: _scan_chunk(
                p, xbc, dt, st, layer, slot, start, n_live, c))])
        with jax.named_scope("attn.gqa"):
            q, k, v = _attn_qkv(p, h[None], cos, sin, c)
            ao, pools = _paged_attend_chunk(q[0], k[0], v[0], pools,
                                            table_row, start, n_live, layer,
                                            where)
        return _block_tail(p, x, mixed, ao, c), pools, state

    x, pools, state = _scan_layers(layer_step, x, pools, state, params)
    last = lax.dynamic_slice_in_dim(x, n_live - 1, 1, 0)
    counts = _state_counts(c, 1, n_live)
    return (_logits(params, last, c)[0], *pools, *state, counts)


def falcon_h1_paged_prefill_chunk_with_decode(
        params, pools, state, table_row, start, ids, n_live, tables,
        positions, row_ids, slot, slots, c: FalconH1Config):
    """A prefill chunk with the decode batch riding it, for an iteration
    that has both: ONE layer scan over the chunk's C rows and the batch's R
    rows (inputs as the two steps above take them: the chunk's, then the
    batch's, then the chunk's slot and the rows' slots), so the weights
    stream once. Embedding, norms, every projection, the gate and its norm,
    the MLP and the head run on all rows together; between the projections
    the rows part, each to its own recurrence (the chunk's scan, the batch's
    update: disjoint slots) and its own attention (the batch's fused paged
    update first, so that the chunk's attention is the pools' last reader in
    a layer and nothing copies them: ``llama_paged_prefill_chunk_with_decode``).

    Returns (the chunk's last-live-token logits [vocab] f32, the batch's
    logits [R, vocab] f32, *pools, *state, counts)."""
    from ..ops.paged_attention import paged_update_walk
    n_c, n_r = ids.shape[0], row_ids.shape[0]
    x = _embed(params, jnp.concatenate([ids, row_ids]), c)      # [C + R, H]
    pidx = jnp.concatenate([start + jnp.arange(n_c, dtype=jnp.int32),
                            positions])
    cos, sin = build_rope_cache(pidx.shape[0], c.head_dim, base=c.rope_theta,
                                position_ids=pidx)
    where = _chunk_window(table_row, start, n_live, n_c, pools[0].shape[-1])
    walk = paged_update_walk(tables, positions, pools[0].shape[-1])

    def layer_step(x, pools, state, p, layer):
        h = fused_rms_norm(x, p["input_norm"], c.rms_norm_eps)
        mixed, state = _mixer(p, h, state, c, [
            (n_c, lambda xbc, dt, st: _scan_chunk(
                p, xbc, dt, st, layer, slot, start, n_live, c)),
            (n_r, lambda xbc, dt, st: _update_rows(
                p, xbc, dt, st, layer, slots, c))])
        with jax.named_scope("attn.gqa"):
            q, k, v = (t[0] for t in _attn_qkv(p, h[None], cos, sin, c))
            ao_rows, pools = _paged_attend_rows(
                q[n_c:], k[n_c:], v[n_c:], pools, walk, layer, c)
            ao_chunk, pools = _paged_attend_chunk(
                q[:n_c], k[:n_c], v[:n_c], pools, table_row, start, n_live,
                layer, where)
        return _block_tail(p, x, mixed, jnp.concatenate([ao_chunk, ao_rows]),
                           c), pools, state

    x, pools, state = _scan_layers(layer_step, x, pools, state, params)
    heads = jnp.concatenate([lax.dynamic_slice_in_dim(x, n_live - 1, 1, 0),
                             x[n_c:]])
    logits = _logits(params, heads, c)
    counts = _state_counts(c, 1 + jnp.sum(slots != 0), n_live)
    return (logits[0], logits[1:], *pools, *state, counts)


# kind -> (the step, the jitted program's name: what the benchmark's metrics
# match in a device trace; the chunk that carries the batch goes by the
# chunk's name first, so what reads ``paged_prefill_chunk_h1`` reads both;
# where the rows' tokens sit among the inputs after the cache and the state:
# what the serving program's board replaces, ``ops/sampling.py``
# ``with_board``)
_PAGED_STEPS = {
    "prefill": (falcon_h1_paged_prefill_chunk, "paged_prefill_chunk_h1",
                None),
    "decode": (falcon_h1_paged_decode_step, "paged_decode_step_h1", 2),
    "prefill+decode": (falcon_h1_paged_prefill_chunk_with_decode,
                       "paged_prefill_chunk_h1_with_decode", 6),
}
_N_POOLS, _N_STATE = 2, 2


@functools.lru_cache(maxsize=24)
def _jitted_paged_step(kind: str, c: FalconH1Config, board: bool = False):
    """The jitted program of ``kind``: ``fn(params, *pools, *state, *inputs)
    -> (token, finite, *pools, *state, counts)`` with both caches donated
    and the greedy head (``ops/sampling.py``) on the step's logits
    (``prefill+decode``: the chunk's pair, then the rows'). With ``board``
    the serving program the engine launches: it also takes the previous
    program's token board and returns its own last (``ops/sampling.py``
    ``with_board``)."""
    step, name, toks_at = _PAGED_STEPS[kind]
    n = _N_POOLS + _N_STATE

    def fn(params, *args):
        return sampled(step(params, args[:_N_POOLS], args[_N_POOLS:n],
                            *args[n:], c), len(kind.split("+")))
    if board:
        fn = with_board(kind, fn, n, toks_at)
    fn.__name__ = name
    return jax.jit(fn, donate_argnums=tuple(range(1, 1 + n)))


class FalconH1Serving:
    """What ``InferenceEngine`` asks of a model (``llama.LlamaServing``
    states the contract), for a model with recurrent state: beside the
    block-indexed cache of ``init_cache`` it answers ``init_state`` (arrays
    indexed by SLOT on axis 1, slot 0 reserved), and its programs take the
    state after the cache and the rows' slots (the chunk's slot; for
    ``prefill+decode`` the chunk's, then the rows') as their last inputs:
    ``fn(params, *cache, *state, <inputs>, slots) -> (*heads, *cache,
    *state, counts)``."""

    # span argument -> registry counter (paddle_tpu_serve_<name>)
    work = {"state_rows": "ssm_state_rows_total",
            "scan_tokens": "ssm_scan_tokens_total"}

    @staticmethod
    def refuse(*, mp, kv_dtype, speculative, draft, prefix_cache) -> None:
        """Out of scope for a model with state, refused rather than
        half-done (ROADMAP.md M6 has each)."""
        for on, what in (
                (mp > 1, "ServeConfig.mp > 1 (the state and the scan are "
                         "not sharded)"),
                (kv_dtype != "auto", "kv_dtype='int8' (no int8 pools or "
                                     "state)"),
                (speculative or draft,
                 "speculative decoding and a draft model (a rejected "
                 "proposal would have to roll the state back)"),
                (prefix_cache, "prefix_cache (a prefix hit skips tokens "
                               "whose state nobody kept)")):
            if on:
                raise NotImplementedError(
                    f"Falcon-H1 serving does not support {what}")

    @staticmethod
    def freeze(config: FalconH1Config) -> FalconH1Config:
        return config           # frozen and hashable as it is

    @staticmethod
    def prepare(params, config):
        return params           # the programs read the leaves as stored

    @staticmethod
    def init_cache(config, num_blocks, block_size, kv_dtype):
        return init_paged_kv_pool(config, num_blocks, block_size)

    @staticmethod
    def init_state(config, num_slots, serve):
        return init_state(config, num_slots)

    @staticmethod
    def step_fn(kind, frozen, quant, mesh, board=True):
        return _jitted_paged_step(kind, frozen, board) \
            if kind in _PAGED_STEPS else None

    @staticmethod
    def counted(kind, counts, *ctx):
        """The span arguments of one step from ``counts`` as the program
        returned it ([2] i32, see ``_state_counts``)."""
        rows, tokens = (int(v) for v in np.asarray(counts[0]))  # noqa: PTA006 -- read inside the wait the step's tokens already pay
        return {"state_rows": rows, "scan_tokens": tokens}
