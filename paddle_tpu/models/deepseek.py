"""DeepSeek-V3's decoder on the serving path: multi-head latent attention
(MLA) over ONE paged latent pool, one leading dense SwiGLU layer, expert
layers with sigmoid group-limited routing that hold a stated share of the
routed experts, YaRN rope. Serving only (``InferenceEngine``): one chip,
bf16, greedy, chunked prefill and batched decode.

Equations (pre-norm, RMSNorm, untied head; per layer x <- x + Attn(norm(x)),
x <- x + FFN(norm(x))):

MLA. c_q = RMSNorm(y W_qa); [q_nope | q_pe] = c_q W_qb per head;
[c_kv | k_pe] = y W_kva; c_kv <- RMSNorm(c_kv); k_pe, q_pe <- RoPE (one key
for all heads). The cache holds, per token and layer, c_kv after its norm and
k_pe after its rope: ``kv_lora_rank + qk_rope_head_dim`` values, nothing
else. The program attends in the ABSORBED form: q' = q_nope W_kvb^K (per
head, nope -> rank), score = (q' . c_kv + q_pe . k_pe) * scale, o_lat =
P c_kv, o = o_lat W_kvb^V, the same function as expanding [k_nope | v] =
c_kv W_kvb first (a test says so). scale = (nope + rope)^-1/2 * mscale^2,
mscale = 0.1 * mscale_all_dim * ln(factor) + 1.

RoPE is YaRN on the rope dimensions: inverse frequencies blended between
interpolated (1 / (factor * base^(2i/d))) and original by the linear ramp
over the correction range of (beta_fast, beta_slow); cos and sin carry
mscale / mscale_all_dim. DEPARTURE: the rope dimensions pair in the
half-rotation layout (i with i + d/2) where the published code pairs adjacent
elements; with seeded weights the two are one model up to a permutation of
W_qb's and W_kva's rope columns.

Expert layer. s = sigmoid(y W_g) in float32 over ALL ``n_routed_experts``;
selection on s' = s + bias: a group's score is the sum of its two largest
s', the ``topk_group`` best groups stay, the ``num_experts_per_tok`` largest
s' inside them are taken (DEPARTURE: the others are masked to -inf, where the
published code fills 0.0; they differ only if a kept s' is negative); weights
w_i = s_i / sum_j s_j * routed_scaling_factor over all selected (the s
without the bias). FFN(y) = sum_i w_i E_i(y) + E_shared(y). THIS CHIP'S
SHARE: the sum runs over the selected experts that are held here
(``expert_offset`` .. ``expert_offset + n_local_experts``) plus the shared
expert; what the absent experts would add is left out and the partial result
goes on. No code stands in for the absent chips or their exchange.
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

from ..ops.grouped_matmul import grouped_matmul_live, tile_schedule
from ..ops.rms_norm import fused_rms_norm
from ..ops.rope import apply_rope
from ..ops.sampling import sampled
from .llama import _chunk_window, _pin_pool_layout, _pool_write_chunk

_LOG2E = 1.4426950408889634
INDEX_NORM_EPS = 1e-6       # the LayerNorm on index keys


@dataclasses.dataclass(frozen=True)
class DeepSeekConfig:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432          # the dense layers' SwiGLU
    moe_intermediate_size: int = 2048       # one expert's
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256             # what the router scores
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # this chip's share of the routed experts: ids offset .. offset + local
    expert_offset: int = 0
    n_local_experts: int = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # learned sparse attention (GLM-5.2's): ``indexer_types`` names every
    # layer ``full`` (it holds an indexer and selects) or ``shared`` (it
    # attends what the nearest ``full`` layer before it selected); empty =
    # no indexer anywhere, every layer attends its whole context
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: Tuple[str, ...] = ()
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = self.indexer_types
        if kinds and (len(kinds) != self.num_hidden_layers
                      or kinds[0] != "full"
                      or set(kinds) - {"full", "shared"}):
            raise ValueError(
                f"indexer_types names each of the {self.num_hidden_layers} "
                f"layers 'full' or 'shared', the first 'full': {kinds}")

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_index_layers(self) -> int:
        return self.indexer_types.count("full")

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0 \
            if self.rope_factor > 1 else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def deepseek_tiny(**over) -> DeepSeekConfig:
    """A size the CPU runs in seconds, every kind of layer present: one
    dense and two expert layers, 8 of 32 experts held, 4 groups."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=32,
                num_experts_per_tok=4, n_group=4, topk_group=2,
                expert_offset=0, n_local_experts=8, rope_original_max=64)
    return DeepSeekConfig(**dict(base, **over))


def glm_dsa_tiny(**over) -> DeepSeekConfig:
    """GLM-5.2's kind of model at a size the CPU runs in seconds: one group,
    plain rope, value heads wider than the nope part, an indexer that keeps
    16 positions, layers of both kinds (one dense ``full`` layer, then
    expert layers ``shared``, ``full``, ``shared``)."""
    base = dict(num_hidden_layers=4, v_head_dim=24, n_group=1, topk_group=1,
                rope_factor=1.0, rope_theta=8e6, index_n_heads=4,
                index_head_dim=16, index_topk=16,
                indexer_types=("full", "shared", "full", "shared"))
    return deepseek_tiny(**dict(base, **over))


def indexer_shapes(c: DeepSeekConfig) -> Dict[str, tuple]:
    """One indexer: queries from the latent queries, ONE key head from the
    layer's input through a LayerNorm (scale ``k_norm``, bias ``k_bias``),
    a weight a head from the layer's input."""
    hi, di = c.index_n_heads, c.index_head_dim
    return {"wq_b": (c.q_lora_rank, hi * di), "wk": (c.hidden_size, di),
            "k_norm": (di,), "k_bias": (di,),
            "weights_proj": (c.hidden_size, hi)}


def attn_shapes(c: DeepSeekConfig) -> Dict[str, tuple]:
    nh, h = c.num_attention_heads, c.hidden_size
    return {
        "input_norm": (h,), "q_a": (h, c.q_lora_rank),
        "q_a_norm": (c.q_lora_rank,),
        "q_b": (c.q_lora_rank,
                nh * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
        "kv_a": (h, c.latent_width), "kv_a_norm": (c.kv_lora_rank,),
        "kv_b": (c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim)),
        "o_proj": (nh * c.v_head_dim, h), "post_norm": (h,),
    }


def param_shapes(c: DeepSeekConfig) -> Dict[str, Any]:
    """The tree ``InferenceEngine`` takes: the leading dense layers as a
    list, the expert layers stacked on axis 0 (two stacks: they do not fit
    one ``(n, ...)`` leaf). Norm scales end in ``norm``; ``router_bias`` is
    the selection's correction bias. Where the model has indexers, a
    ``full`` dense layer holds its own under ``indexer`` and the ``full``
    expert layers' are stacked under ``moe.indexer`` in their order (a
    ``shared`` layer holds none)."""
    h, i, e = c.hidden_size, c.intermediate_size, c.moe_intermediate_size
    n, el = c.n_moe_layers, c.n_local_experts
    nd = c.first_k_dense_replace
    attn = attn_shapes(c)
    sh = c.n_shared_experts * e
    full = [k == "full" for k in c.indexer_types]
    dense = [dict(attn, gate_proj=(h, i), up_proj=(h, i), down_proj=(i, h))
             for _ in range(nd)]
    for layer, is_full in zip(dense, full):
        if is_full:
            layer["indexer"] = indexer_shapes(c)
    moe = dict(
        {k: (n,) + s for k, s in attn.items()},
        router=(n, h, c.n_routed_experts),
        router_bias=(n, c.n_routed_experts),
        experts={"gate": (n, el, h, e), "up": (n, el, h, e),
                 "down": (n, el, e, h)},
        shared={"gate": (n, h, sh), "up": (n, h, sh),
                "down": (n, sh, h)})
    if any(full[nd:]):
        moe["indexer"] = {k: (sum(full[nd:]),) + s
                          for k, s in indexer_shapes(c).items()}
    return {"embed": (c.vocab_size, h), "dense": dense, "moe": moe,
            "final_norm": (h,), "lm_head": (h, c.vocab_size)}


def init_deepseek_params(c: DeepSeekConfig, seed: int = 0, std: float = 0.02):
    """Seeded weights in the tree of :func:`param_shapes`: matrices and the
    correction bias normal x ``std``, norm scales one."""
    shapes = param_shapes(c)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = str(getattr(path[-1], "key", ""))
        out.append(jnp.ones(shape, c.dtype) if name.endswith("norm")
                   else (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(c.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def init_latent_pool(c: DeepSeekConfig, num_blocks: int, block_size: int):
    """The latent pool [L, NP, kv_lora_rank + qk_rope_head_dim, block_size],
    time in lanes, block 0 the null block: the whole cache of a model
    without indexers, the first of two arrays of one with them."""
    return jnp.zeros((c.num_hidden_layers, num_blocks, c.latent_width,
                      block_size), c.dtype)


def init_index_pool(c: DeepSeekConfig, num_blocks: int, block_size: int):
    """The index keys' pool [Li, NP, index_head_dim, block_size]: one layer
    for each ``full`` layer (a ``shared`` layer caches no key of its own),
    one key head, time in lanes, under the latent pool's block table."""
    return jnp.zeros((c.n_index_layers, num_blocks, c.index_head_dim,
                      block_size), c.dtype)


# -- rope ------------------------------------------------------------------------

def yarn_inv_freq(c: DeepSeekConfig):
    """[rope/2] inverse frequencies: interpolated below the correction
    range, original above it, a linear ramp between."""
    d, base = c.qk_rope_head_dim, c.rope_theta
    pos_freqs = base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    extra, inter = 1.0 / pos_freqs, 1.0 / (c.rope_factor * pos_freqs)
    if c.rope_factor <= 1:
        return extra

    def correction_dim(rotations):
        return d * math.log(c.rope_original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(c.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(c.rope_beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_cos_sin(c: DeepSeekConfig, positions):
    """cos, sin [..., rope/2] f32 at integer ``positions``."""
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(c)

    def get_mscale(m):
        return 0.1 * m * math.log(c.rope_factor) + 1.0 \
            if c.rope_factor > 1 else 1.0
    ms = get_mscale(c.rope_mscale) / get_mscale(c.rope_mscale_all_dim)
    return jnp.cos(ang) * ms, jnp.sin(ang) * ms


# -- attention -------------------------------------------------------------------

def latent_queries(p, x, c: DeepSeekConfig):
    """c_q [T, q_lora_rank]: what the heads' queries, and an indexer's, are
    projected from."""
    return fused_rms_norm(x @ p["q_a"], p["q_a_norm"], c.rms_norm_eps)


def mla_project(p, x, cos, sin, c: DeepSeekConfig, cq=None):
    """x [T, H] (normed) at the positions of cos/sin [T, rope/2] ->
    (q_nope [T, NH, nope], q_pe [T, NH, rope] roped, latent [T, W]: the
    normed compressed KV, then the roped shared key). ``cq``: the layer's
    ``latent_queries`` where the caller made them already."""
    t = x.shape[0]
    nh, dn, dr = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
    if cq is None:
        cq = latent_queries(p, x, c)
    q = (cq @ p["q_b"]).reshape(t, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv = x @ p["kv_a"]
    ckv = fused_rms_norm(kv[:, :c.kv_lora_rank], p["kv_a_norm"],
                         c.rms_norm_eps)
    k_pe = apply_rope(kv[None, :, None, c.kv_lora_rank:], cos, sin)[0, :, 0]
    q_pe = apply_rope(q_pe[None], cos, sin)[0]
    return q_nope, q_pe, jnp.concatenate([ckv, k_pe], axis=-1)


def _kv_b_heads(p, c: DeepSeekConfig):
    """W_kvb by head: (W^K [rank, NH, nope], W^V [rank, NH, v])."""
    w = p["kv_b"].reshape(c.kv_lora_rank, c.num_attention_heads,
                          c.qk_nope_head_dim + c.v_head_dim)
    return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def absorbed_queries(p, q_nope, q_pe, c: DeepSeekConfig):
    """[T, NH, W] queries against the latent: q_nope through W_kvb^K, then
    q_pe; PRE-SCALED by scale * log2(e) for the kernels' exp2 softmax."""
    wk, _ = _kv_b_heads(p, c)
    q_abs = jnp.einsum("thd,chd->thc", q_nope, wk,
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_abs, q_pe.astype(jnp.float32)], axis=-1)
    return (q * (c.softmax_scale * _LOG2E)).astype(c.dtype)


def latent_out(p, o_lat, c: DeepSeekConfig):
    """o_lat [T, NH, rank] -> the attention's output [T, H] f32: through
    W_kvb^V per head, then W_o."""
    _, wv = _kv_b_heads(p, c)
    o = jnp.einsum("thc,chd->thd", o_lat.astype(c.dtype), wv,
                   preferred_element_type=jnp.float32).astype(c.dtype)
    return jnp.dot(o.reshape(o.shape[0], -1), p["o_proj"],
                   preferred_element_type=jnp.float32)


def _queries_and_latent(p, x, cq, cos, sin, pool, c: DeepSeekConfig):
    """What a paged step's attention starts from: (the absorbed queries
    [T, NH, W], the rows' latent columns [T, W] in the pool's dtype)."""
    with jax.named_scope("mla.project"):
        q_nope, q_pe, lat = mla_project(p, x, cos, sin, c, cq)
        return absorbed_queries(p, q_nope, q_pe, c), lat.astype(pool.dtype)


def indexer_project(ip, x, cq, cos, sin, c: DeepSeekConfig):
    """One indexer's inputs for rows x [T, H] (normed) with latent queries
    cq [T, q_lora_rank]: (qI [T, HI, DI] roped, w [T, HI] f32 scaled by
    HI^-1/2 * DI^-1/2, kI [T, DI] f32: LayerNorm(x W_k), roped). Rope turns
    the FIRST ``qk_rope_head_dim`` of the DI dimensions, in the pairing and
    at the frequencies of the attention's."""
    t = x.shape[0]
    hi, di, dr = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
    qi = (cq @ ip["wq_b"]).reshape(t, hi, di)
    qi = jnp.concatenate(
        [apply_rope(qi[None, ..., :dr], cos, sin)[0], qi[..., dr:]], axis=-1)
    k = jnp.dot(x, ip["wk"], preferred_element_type=jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                      + INDEX_NORM_EPS) * ip["k_norm"].astype(jnp.float32) \
        + ip["k_bias"].astype(jnp.float32)
    k = jnp.concatenate(
        [apply_rope(k[None, :, None, :dr], cos, sin)[0, :, 0], k[:, dr:]],
        axis=-1)
    w = jnp.dot(x, ip["weights_proj"], preferred_element_type=jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    return qi, w, k


def mla_expanded(p, x, cos, sin, c: DeepSeekConfig):
    """The EXPANDED form over one whole sequence x [T, H] (normed), causal,
    in plain jnp: what the absorbed kernels must equal. Not on the serving
    path (tests and the builder's measurement only)."""
    t = x.shape[0]
    q_nope, q_pe, lat = mla_project(p, x, cos, sin, c)
    wk, wv = _kv_b_heads(p, c)
    ckv, k_pe = lat[:, :c.kv_lora_rank], lat[:, c.kv_lora_rank:]
    k_nope = jnp.einsum("tc,chd->thd", ckv, wk)
    v = jnp.einsum("tc,chd->thd", ckv, wv)
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("qhd,kd->hqk", q_pe, k_pe,
                      preferred_element_type=jnp.float32)) * c.softmax_scale
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr.astype(c.dtype), v,
                   preferred_element_type=jnp.float32).astype(c.dtype)
    return jnp.dot(o.reshape(t, -1), p["o_proj"],
                   preferred_element_type=jnp.float32)


# -- feed-forward ----------------------------------------------------------------

def rms_norm(x, w, eps):
    """RMSNorm of the float32 residual stream, in float32 (plain jnp: XLA
    fuses it; ``fused_rms_norm``'s windows are sized for bf16 rows)."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def swiglu(x, gate, up, down):
    """x [T, H] in the weights' dtype -> [T, H] f32 (the last product's
    accumulator, unrounded)."""
    return jnp.dot(jax.nn.silu(x @ gate) * (x @ up), down,
                   preferred_element_type=jnp.float32)


def route(y, router, bias, c: DeepSeekConfig):
    """y [T, H] -> (idx [T, k] i32 over ALL routed experts, w [T, k] f32):
    sigmoid scores in float32, selection on score + bias limited to the
    best groups, weights from the scores without the bias, normalised over
    all selected and scaled."""
    s = jax.nn.sigmoid(jnp.dot(y.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    sel = s + bias.astype(jnp.float32)
    _, idx = lax.top_k(_best_groups(sel, c), c.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * c.routed_scaling_factor
    return idx.astype(jnp.int32), w


def _best_groups(sel, c: DeepSeekConfig):
    """sel [T, E] -> the same with the experts outside the ``topk_group``
    best groups at -inf (a group's score: the sum of its two largest). One
    group (GLM-5.2's) holds every expert, so this is the identity."""
    t, e = sel.shape
    g = sel.reshape(t, c.n_group, e // c.n_group)
    # the two largest of a group as two maxima (``lax.top_k(g, 2)`` sorts
    # every group, with the groups in lanes at a row count off the lane
    # tile: 0.18 ms a layer at 576 rows); the same two numbers, ties too
    first = jnp.argmax(g, axis=-1, keepdims=True)
    second = jnp.where(jnp.arange(g.shape[-1]) == first, -jnp.inf, g)
    group_score = g.max(-1) + second.max(-1)                   # [T, G]
    _, best = lax.top_k(group_score, c.topk_group)
    keep = jnp.zeros((t, c.n_group), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    return jnp.where(keep[:, :, None], g, -jnp.inf).reshape(t, e)


def _expert_tile(t: int) -> int:
    """Row tile of the grouped matmul: the MXU's for a chunk, one packed
    bf16 sublane tile for a decode batch (an expert's few rows pad to it)."""
    return 128 if t >= 128 else 16


def moe_ffn(y, live, p, experts, slot, c: DeepSeekConfig):
    """y [T, H] f32, the normed residual stream: the router reads it as it
    is, the experts after rounding to the weights' dtype; live [T] bool
    (padding tokens route nowhere); p the layer's
    router, bias and shared expert; ``experts`` the stacked routed experts
    of ALL expert layers ({gate, up: [n, El, H, I], down: [n, El, I, H]})
    and ``slot`` this layer's index in them (the stack goes to the grouped
    matmul whole, so no layer's experts are sliced out and copied).
    Returns (FFN(y) [T, H] f32, counts [4] i32: (live token, expert) pairs,
    those routed to experts held here, held experts with at least one row,
    rows of the busiest)."""
    t, h = y.shape
    k, el = c.num_experts_per_tok, c.n_local_experts
    tile = _expert_tile(t)
    with jax.named_scope("moe.route"):
        idx, w = route(y, p["router"], p["router_bias"], c)
        y = y.astype(c.dtype)
        local = idx - c.expert_offset
        held = (local >= 0) & (local < el) & live[:, None]     # [T, k]
        e_flat = jnp.where(held, local, el).reshape(-1)         # [P]
        onehot = (e_flat[:, None] == jnp.arange(el)[None, :]).astype(
            jnp.int32)                                          # [P, El]
        counts = onehot.sum(0)
        m = -(-(t * k) // tile) * tile + el * tile
        n_tiles = m // tile
        tile_e, live_t, first, last, offsets = tile_schedule(
            counts, n_tiles, tile)
        e_safe = jnp.minimum(e_flat, el - 1)
        within = jnp.take_along_axis(jnp.cumsum(onehot, axis=0),
                                     e_safe[:, None], axis=1)[:, 0] - 1
        dest = jnp.where(held.reshape(-1), offsets[e_safe] + within, m)
        row_tok = jnp.zeros((m,), jnp.int32).at[dest].set(
            jnp.repeat(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
        sched = (tile_e + slot * el, live_t, first, last)
        n_live = offsets[el] // tile
    with jax.named_scope("moe.experts"):
        stack = lambda a: a.reshape((-1,) + a.shape[2:])
        x = jnp.take(y, row_tok, axis=0)
        g = grouped_matmul_live(x, stack(experts["gate"]), sched, n_live,
                                tile)
        u = grouped_matmul_live(x, stack(experts["up"]), sched, n_live, tile)
        d = grouped_matmul_live((jax.nn.silu(g) * u).astype(y.dtype),
                                stack(experts["down"]), sched, n_live, tile)
        # rows no tile wrote are garbage: select, never multiply by zero
        rows = jnp.take(d, jnp.minimum(dest, m - 1), axis=0).reshape(t, k, h)
        out = jnp.sum(jnp.where(held[:, :, None],
                                rows.astype(jnp.float32) * w[:, :, None],
                                0.0), axis=1)
    with jax.named_scope("moe.shared"):
        sh = p["shared"]
        shared = swiglu(y, sh["gate"], sh["up"], sh["down"])
    stats = jnp.stack([live.sum() * k, counts.sum(), (counts > 0).sum(),
                       counts.max()])
    return out + shared, stats.astype(jnp.int32)


# -- the paged steps -------------------------------------------------------------

def _runs(kinds):
    """Consecutive layers of one kind: [(kind, lo, hi)]."""
    out, lo = [], 0
    for i in range(1, len(kinds) + 1):
        if i == len(kinds) or kinds[i] != kinds[lo]:
            out.append((kinds[lo], lo, i))
            lo = i
    return out


def _layers(params, c: DeepSeekConfig, attend, h, pools, live, index=None):
    """Every layer over the residual stream h [T, H], held in FLOAT32 (a
    sub-layer's inputs are rounded to the weights' dtype, its last product's
    float32 accumulator is added unrounded: a bf16 stream's rounding decides
    the router's near-ties the other way more often than the float32
    reference's, and with a share of the experts held a flipped choice is not
    made up by the others), with the cache ``pools`` (a tuple: the latent
    pool, then the index keys' where the model has indexers) as a carry: the
    leading dense layers one by one, the expert layers in scans.
    ``attend(p, x, cq, pools, layer, sel) -> (attention output [T, H],
    pools)``. A model without indexers is one scan over alike layers, ``sel``
    None. With them the layers are of TWO kinds and the expert layers go in
    runs of one kind, a scan each: a ``full`` layer first calls ``index(ip,
    x, cq, pools, slot) -> (pools, sel)`` with its indexer's weights ``ip``
    and its layer ``slot`` of the index pool (it writes the rows' index
    keys there and selects), a ``shared`` layer runs no indexer and attends
    the ``sel`` it is handed, which rides the scans' carry from the nearest
    ``full`` layer before it. Returns (h, pools, counts [n_moe, 4] i32)."""
    nd = len(params["dense"])
    kinds = c.indexer_types or ("plain",) * c.num_hidden_layers

    def attn(p, ip, h, pools, layer, slot, sel):
        x = rms_norm(h, p["input_norm"], c.rms_norm_eps).astype(c.dtype)
        with jax.named_scope("mla.project"):
            cq = latent_queries(p, x, c)
        if ip is not None:
            pools, sel = index(ip, x, cq, pools, slot)
        a, pools = attend(p, x, cq, pools, layer, sel)
        h = h + a
        return h, rms_norm(h, p["post_norm"], c.rms_norm_eps), pools, sel

    sel, n_full = None, 0
    for i, p in enumerate(params["dense"]):
        ip = p.get("indexer")
        h, y, pools, sel = attn(p, ip, h, pools, jnp.int32(i),
                                jnp.int32(n_full), sel)
        n_full += ip is not None
        with jax.named_scope("ffn.dense"):
            h = h + swiglu(y.astype(c.dtype), p["gate_proj"], p["up_proj"],
                           p["down_proj"])
    moe = params["moe"]
    experts = moe["experts"]
    scanned = {k: v for k, v in moe.items() if k not in ("experts", "indexer")}
    n = experts["gate"].shape[0]
    at = lambda tree, i: jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)

    n_full_dense, counts = n_full, []

    def run(kind, lo, first_slot):
        def moe_layer(carry, xs):
            h, pools, sel = carry
            i, p = xs
            if p is None:       # a run inside the stack reads its layer
                p = at(scanned, i)
            ip, slot = None, None
            if kind == "full":
                slot = first_slot + (i - lo)
                ip = at(moe["indexer"], slot - n_full_dense)
            h, y, pools, sel = attn(p, ip, h, pools, i + nd, slot, sel)
            f, stats = moe_ffn(y, live, p, experts, i, c)
            return (h + f, pools, sel), stats
        return moe_layer

    for kind, lo, hi in _runs(kinds[nd:]):
        whole = (lo, hi) == (0, n)
        (h, pools, sel), stats = lax.scan(
            run(kind, lo, n_full), (h, pools, sel),
            (jnp.arange(lo, hi, dtype=jnp.int32), scanned if whole else None))
        n_full += (hi - lo) * (kind == "full")
        counts.append(stats)
    return h, pools, jnp.concatenate(counts) if len(counts) > 1 else counts[0]


def _logits(params, h, c: DeepSeekConfig):
    with jax.named_scope("lm_head"):
        x = rms_norm(h, params["final_norm"], c.rms_norm_eps).astype(c.dtype)
        return jnp.dot(x, params["lm_head"],
                       preferred_element_type=jnp.float32)


def _attend_scope(sel):
    """A layer's attention kernel by what it attends: ``dsa.attend`` under
    a selection, ``mla.attend`` over the whole context."""
    return jax.named_scope("mla.attend" if sel is None else "dsa.attend")


def _dsa_counts(c: DeepSeekConfig, reach):
    """What the steps of a model with indexers return after ``counts``:
    ([3] i32: (row, key) pairs its indexers scored, tokens attended to and
    tokens in reach, each summed over the layers it holds for), from
    ``reach`` [R] i32, the positions at or before each live row (0 for a
    padding row). Nothing for a model without indexers."""
    if not c.indexer_types:
        return ()
    total = jnp.sum(reach)
    picked = jnp.sum(jnp.minimum(reach, c.index_topk))
    n = c.num_hidden_layers
    return (jnp.stack([c.n_index_layers * total, n * picked,
                       n * total]).astype(jnp.int32),)


def _index_rows(ip, x, cq, cos, sin, ipool, walk, positions, slot,
                c: DeepSeekConfig):
    """A decode batch's rows through one indexer: each writes its index key
    (its own new token is a candidate), scores its prefix and selects.
    Returns (ipool, sel [B, 1, T])."""
    from ..ops.paged_attention import dsa_index_decode, dsa_select
    with jax.named_scope("dsa.index"):
        qi, w, k = indexer_project(ip, x, cq, cos, sin, c)
        scores, ipool = dsa_index_decode(qi, w, k.astype(ipool.dtype), ipool,
                                         walk, slot)
    with jax.named_scope("dsa.select"):
        sel = dsa_select(scores[:, 0], positions, c.index_topk, c.dtype)
    return ipool, sel[:, None]


def _index_chunk(ip, x, cq, cos, sin, ipool, where, table_row, start, n_live,
                 slot, c: DeepSeekConfig):
    """A prefill chunk's rows through one indexer: the chunk's index keys
    land in the sequence's blocks, every row scores the sequence's keys up
    to its own and selects. Returns (ipool, sel [C, T])."""
    from ..ops.paged_attention import dsa_index_prefill, dsa_select
    wbid, fresh, window = where
    with jax.named_scope("dsa.index"):
        qi, w, k = indexer_project(ip, x, cq, cos, sin, c)
        ipool = _pool_write_chunk(_pin_pool_layout(ipool), slot, wbid, fresh,
                                  window(k.astype(ipool.dtype)))
        scores = dsa_index_prefill(qi, w, ipool, table_row, start, n_live,
                                   slot)
    with jax.named_scope("dsa.select"):
        sel = dsa_select(
            scores, start + jnp.arange(x.shape[0], dtype=jnp.int32),
            c.index_topk, c.dtype)
    return ipool, sel


def deepseek_paged_decode_step(params, pools, tables, positions, ids,
                               c: DeepSeekConfig):
    """One decode step over the paged cache ``pools`` (``init_cache``'s
    tuple): ids [B], tables [B, max_nb], positions [B] = the slot each row's
    new token takes. Padding rows point their tables at the null block 0
    (position 0) and route to no expert. Returns (logits [B, vocab] f32,
    *pools, counts[, ``_dsa_counts``])."""
    from ..ops.paged_attention import mla_paged_decode, mla_update_walk
    h = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    cos, sin = yarn_cos_sin(c, positions)
    live = tables[:, 0] > 0
    walk = mla_update_walk(tables, positions, pools[0].shape[-1])

    def attend(p, x, cq, pools, layer, sel):
        q, lat = _queries_and_latent(p, x, cq, cos, sin, pools[0], c)
        with _attend_scope(sel):
            o_lat, pool = mla_paged_decode(q, lat, pools[0], walk, layer,
                                           rank=c.kv_lora_rank, select=sel)
        with jax.named_scope("mla.project"):
            return latent_out(p, o_lat, c), (pool,) + pools[1:]

    def index(ip, x, cq, pools, slot):
        ipool, sel = _index_rows(ip, x, cq, cos, sin, pools[1], walk,
                                 positions, slot, c)
        return (pools[0], ipool), sel

    h, pools, counts = _layers(params, c, attend, h, tuple(pools), live,
                               index)
    return (_logits(params, h, c), *pools, counts,
            *_dsa_counts(c, jnp.where(live, positions + 1, 0)))


def deepseek_paged_prefill_chunk(params, pools, table_row, start, ids, n_live,
                                 c: DeepSeekConfig):
    """One chunked-prefill slice of ONE sequence: ids [C] padded to the
    chunk, ``n_live`` real tokens, ``start`` tokens already cached. Writes
    the chunk's latent columns (and, in a ``full`` layer, its index keys)
    into the sequence's blocks (padding lands in the null block), attends
    the live context through the block table and returns (logits [vocab] f32
    of the last real token, *pools, counts[, ``_dsa_counts``])."""
    from ..ops.paged_attention import mla_paged_prefill
    C = ids.shape[0]
    h = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    cos, sin = yarn_cos_sin(c, start + jnp.arange(C, dtype=jnp.int32))
    live = jnp.arange(C) < n_live
    where = _chunk_window(table_row, start, n_live, C, pools[0].shape[-1])
    wbid, fresh, window = where

    def attend(p, x, cq, pools, layer, sel):
        q, lat = _queries_and_latent(p, x, cq, cos, sin, pools[0], c)
        with _attend_scope(sel):
            pool = _pool_write_chunk(_pin_pool_layout(pools[0]), layer, wbid,
                                     fresh, window(lat))
            o_lat = mla_paged_prefill(q, pool, table_row, start, n_live,
                                      layer, rank=c.kv_lora_rank, select=sel)
        with jax.named_scope("mla.project"):
            return latent_out(p, o_lat, c), (pool,) + pools[1:]

    def index(ip, x, cq, pools, slot):
        ipool, sel = _index_chunk(ip, x, cq, cos, sin, pools[1], where,
                                  table_row, start, n_live, slot, c)
        return (pools[0], ipool), sel

    h, pools, counts = _layers(params, c, attend, h, tuple(pools), live,
                               index)
    h_last = lax.dynamic_slice_in_dim(h, n_live - 1, 1, 0)
    reach = jnp.where(live, start + 1 + jnp.arange(C, dtype=jnp.int32), 0)
    return (_logits(params, h_last, c)[0], *pools, counts,
            *_dsa_counts(c, reach))


def deepseek_paged_prefill_chunk_with_decode(params, pools, table_row, start,
                                             ids, n_live, tables, positions,
                                             row_ids, c: DeepSeekConfig):
    """A prefill chunk with the decode batch riding it, for an iteration
    that has both: ONE pass of the layers over the chunk's C rows
    (``table_row``, ``start``, ``ids``, ``n_live`` as
    ``deepseek_paged_prefill_chunk`` takes them) and the batch's B rows
    (``tables``, ``positions``, ``row_ids`` as ``deepseek_paged_decode_step``
    takes them; padding rows at the null block 0, position 0), so the
    weights stream once for both: the attention's, the dense FFN's, the
    shared expert's, the head's, and each expert's that either part hits
    (the rows' pairs sit in the row tiles the chunk opens anyway). Between
    ``mla_project`` and ``latent_out`` the rows part, each to the absorbed
    queries and the attention of its own step, roped by its own positions;
    so they do through an indexer: the chunk's rows and the batch's each
    score, and select from, their own sequence's keys.
    The parts touch disjoint blocks: a sequence is in prefill or running,
    never both.
    The batch's update goes first, so the chunk's attention is the pool's
    last reader in a layer and nothing copies it.

    Returns (the chunk's last-live-token logits [vocab] f32, the batch's
    logits [B, vocab] f32, *pools, counts: once, for both parts[,
    ``_dsa_counts``])."""
    from ..ops.paged_attention import (mla_paged_decode, mla_paged_prefill,
                                       mla_update_walk)
    C = ids.shape[0]
    h = jnp.take(params["embed"], jnp.concatenate([ids, row_ids]),
                 axis=0).astype(jnp.float32)
    chunk_pos = start + jnp.arange(C, dtype=jnp.int32)
    cos, sin = yarn_cos_sin(c, jnp.concatenate([chunk_pos, positions]))
    live = jnp.concatenate([jnp.arange(C) < n_live, tables[:, 0] > 0])
    where = _chunk_window(table_row, start, n_live, C, pools[0].shape[-1])
    wbid, fresh, window = where
    walk = mla_update_walk(tables, positions, pools[0].shape[-1])

    def attend(p, x, cq, pools, layer, sel):
        # projected on all rows together; absorbed a part at a time, so each
        # kernel's queries are written where it reads them (a slice of one
        # [C + B, NH, W] array would be a copy of it in every layer)
        sel_chunk, sel_rows = sel if sel is not None else (None, None)
        with jax.named_scope("mla.project"):
            q_nope, q_pe, lat = mla_project(p, x, cos, sin, c, cq)
            q_chunk = absorbed_queries(p, q_nope[:C], q_pe[:C], c)
            q_rows = absorbed_queries(p, q_nope[C:], q_pe[C:], c)
            lat = lat.astype(pools[0].dtype)
        with _attend_scope(sel):
            o_rows, pool = mla_paged_decode(q_rows, lat[C:], pools[0], walk,
                                            layer, rank=c.kv_lora_rank,
                                            select=sel_rows)
            pool = _pool_write_chunk(_pin_pool_layout(pool), layer, wbid,
                                     fresh, window(lat[:C]))
            o_chunk = mla_paged_prefill(q_chunk, pool, table_row, start,
                                        n_live, layer, rank=c.kv_lora_rank,
                                        select=sel_chunk)
        with jax.named_scope("mla.project"):
            o_lat = jnp.concatenate([o_chunk, o_rows.astype(o_chunk.dtype)])
            return latent_out(p, o_lat, c), (pool,) + pools[1:]

    def index(ip, x, cq, pools, slot):
        ipool, sel_rows = _index_rows(
            ip, x[C:], cq[C:], cos[C:], sin[C:], pools[1], walk, positions,
            slot, c)
        ipool, sel_chunk = _index_chunk(
            ip, x[:C], cq[:C], cos[:C], sin[:C], ipool, where, table_row,
            start, n_live, slot, c)
        return (pools[0], ipool), (sel_chunk, sel_rows)

    h, pools, counts = _layers(params, c, attend, h, tuple(pools), live,
                               index)
    heads = jnp.concatenate(
        [lax.dynamic_slice_in_dim(h, n_live - 1, 1, 0), h[C:]])
    logits = _logits(params, heads, c)
    reach = jnp.where(live, jnp.concatenate([chunk_pos, positions]) + 1, 0)
    return (logits[0], logits[1:], *pools, counts, *_dsa_counts(c, reach))


# -- what InferenceEngine asks of a model ----------------------------------------

# kind -> (the step, the jitted program's name: what the benchmark's metrics
# match in a device trace; the chunk that carries the batch goes by the
# chunk's name first, so what reads ``paged_prefill_chunk_mla`` reads both)
_PAGED_STEPS = {
    "prefill": (deepseek_paged_prefill_chunk, "paged_prefill_chunk_mla"),
    "decode": (deepseek_paged_decode_step, "paged_decode_step_mla"),
    "prefill+decode": (deepseek_paged_prefill_chunk_with_decode,
                       "paged_prefill_chunk_mla_with_decode"),
}


@functools.lru_cache(maxsize=24)
def _jitted_paged_step(kind: str, c: DeepSeekConfig):
    """The jitted program of ``kind`` for the frozen config:
    ``fn(params, *pools, *inputs)`` with the cache's arrays donated (one
    latent pool; with indexers, the index keys' pool after it). The step's
    logits do not leave it: the greedy head (``ops/sampling.py``) runs here,
    so ``decode`` returns (tokens [B] i32, finite [B] bool, *pools,
    counts...), ``prefill`` (token [], finite [], ...) and
    ``prefill+decode`` the chunk's pair, then the rows'."""
    step, name = _PAGED_STEPS[kind]
    n = 2 if c.indexer_types else 1

    def fn(params, *args):
        return sampled(step(params, args[:n], *args[n:], c),
                       len(kind.split("+")))
    fn.__name__ = name
    return jax.jit(fn, donate_argnums=tuple(range(1, 1 + n)))


# ``chipbench/families/deepseek.py`` ``aot_programs`` asks for these by name
_jitted_paged_decode = functools.partial(_jitted_paged_step, "decode")
_jitted_paged_prefill = functools.partial(_jitted_paged_step, "prefill")


class DeepSeekServing:
    """What ``InferenceEngine`` asks of a model (``llama.LlamaServing`` is
    Llama's): the frozen config, the cache, the three jitted programs (a
    chunk, a decode step, a chunk that carries the decode batch), which
    return a token and a finite flag a row where their steps return logits
    (``LlamaServing`` states the contract) and ``counts`` after the cache,
    and the registry counters those feed.
    The cache is ONE latent pool; a model with indexers
    (``DeepSeekConfig.indexer_types``: GLM-5.2's learned sparse attention)
    keeps the index keys of its ``full`` layers in a SECOND pool of its own
    depth and width under the same block table, written by the same
    programs, and its steps return their selection's counts after
    ``counts``. What a layer attends to follows from the config and the
    context alone: no option chooses."""

    # span argument -> registry counter (paddle_tpu_serve_<name>)
    work = {"pairs": "moe_pairs_total",
            "local_pairs": "moe_local_pairs_total",
            "experts_hit": "moe_expert_hits_total",
            "busiest_rows": "moe_busiest_rows_total",
            "mla_decode_ctx": "mla_decode_ctx_tokens_total",
            "mla_prefill_ctx": "mla_prefill_ctx_tokens_total",
            # a model with indexers: (row, key) pairs its indexers scored,
            # tokens attended to and tokens in reach, summed over layers
            "dsa_index_pairs": "dsa_index_pairs_total",
            "dsa_selected": "dsa_selected_tokens_total",
            "dsa_ctx": "dsa_ctx_tokens_total"}

    @staticmethod
    def refuse(*, mp, kv_dtype, speculative, draft, prefix_cache=False
               ) -> None:
        """Out of scope for this model, refused rather than half-done (the
        prefix cache is not: nothing here depends on it);
        with indexers as without (a selection across chips, int8 or fp8
        index keys and a drafter that shares the selection are ROADMAP.md
        M5's)."""
        for on, what in ((mp > 1, "ServeConfig.mp > 1 (tensor-parallel "
                                   "serving shards kv heads; the latent "
                                   "pool and the index keys' have none)"),
                         (kv_dtype != "auto", "kv_dtype='int8' (no int8 "
                                              "latent or index-key pool)"),
                         (speculative or draft, "speculative decoding and "
                                                "a draft model")):
            if on:
                raise NotImplementedError(
                    f"DeepSeek serving does not support {what}")

    @staticmethod
    def freeze(config: DeepSeekConfig) -> DeepSeekConfig:
        return config           # frozen and hashable as it is

    @staticmethod
    def init_cache(config, num_blocks, block_size, kv_dtype):
        pools = (init_latent_pool(config, num_blocks, block_size),)
        if config.indexer_types:
            pools += (init_index_pool(config, num_blocks, block_size),)
        return pools

    @staticmethod
    def step_fn(kind, frozen, quant, mesh):
        """The jitted program of ``kind`` (``prefill``, ``decode``,
        ``prefill+decode``: a chunk with the decode batch riding it); None
        for a kind not offered (``verify``)."""
        return _jitted_paged_step(kind, frozen) if kind in _PAGED_STEPS \
            else None

    @staticmethod
    def counted(kind, counts, *ctx):
        """The span arguments of one step: ``counts`` as the jitted step
        returned them ([n_moe, 4] i32: (live token, expert) pairs, those
        routed to experts held here, held experts hit, the busiest's rows;
        summed here over the expert layers) and, for each part of ``kind``
        in its order (``prefill+decode``: the chunk's, then the rows'),
        ``ctx``: the latent columns each sequence's attention had to read,
        per layer. A program of both parts counts its pairs as the two
        programs together would; ``experts_hit`` and ``busiest_rows`` are of
        the union of its rows (an expert both parts hit streams, and counts,
        once)."""
        c = np.asarray(counts[0])  # noqa: PTA006 -- read inside the wait the step's tokens already pay
        pairs, local, hit, busiest = (int(x) for x in c.sum(axis=0))
        out = {"pairs": pairs, "local_pairs": local, "experts_hit": hit,
               "busiest_rows": busiest}
        for part, cols in zip(kind.split("+"), ctx, strict=True):
            out[f"mla_{part}_ctx"] = int(sum(cols))
        if len(counts) > 1:
            d = np.asarray(counts[1])  # noqa: PTA006 -- as above
            out.update(dsa_index_pairs=int(d[0]), dsa_selected=int(d[1]),
                       dsa_ctx=int(d[2]))
        return out
