"""The DeepSeek-V3 family, on the serving path: what
``paddle_tpu.models.deepseek`` runs through ``InferenceEngine``. The same
six answers ``families/llama.py`` gives; ``m`` is a configuration's dict
with the published key names.

Equations (the program and this file's reference both compute them):
pre-norm decoder, RMSNorm, untied head; per layer x <- x + Attn(norm(x)),
x <- x + FFN(norm(x)).

Latent attention (MLA): c_q = RMSNorm(y W_qa); [q_nope | q_pe] = c_q W_qb
per head; [c_kv | k_pe] = y W_kva; c_kv <- RMSNorm(c_kv); k_pe and q_pe
roped, one key shared by all heads; [k_nope | v] = c_kv W_kvb per head;
score = (q_nope . k_nope + q_pe . k_pe) * scale, causal softmax, o = P v,
out = concat(o) W_o; scale = (nope + rope)^-1/2 * mscale^2, mscale = 0.1 *
mscale_all_dim * ln(factor) + 1. The reference attends in this EXPANDED
form; the program in the absorbed one over its latent cache.

RoPE is YaRN on the rope dimensions: inverse frequencies blended between
interpolated and original by the linear ramp over the correction range of
(beta_fast, beta_slow); cos and sin carry mscale / mscale_all_dim.
DEPARTURE, in the program and here alike: rope dimensions pair in the
half-rotation layout (i with i + d/2) where the published code pairs
adjacent elements: one model up to a permutation of W_qb's and W_kva's rope
columns, which seeded weights do not tell apart.

Expert layer: s = sigmoid(y W_g) in float32 over all
``published.n_routed_experts``; selection on s' = s + bias: a group's
score is the sum of its two largest s', the ``topk_group`` best groups
stay, the ``num_experts_per_tok`` largest s' inside them are taken
(DEPARTURE: the rest are masked to -inf where the published code fills 0.0;
the two differ only where a kept s' is negative); w_i = s_i / sum_j s_j *
routed_scaling_factor over all selected, from the s without the bias.
FFN(y) = sum_i w_i E_i(y) + E_shared(y), E(y) = W_down(silu(W_gate y) *
W_up y). THE CHIP'S SHARE: the configuration's ``n_routed_experts`` is how
many routed experts are held (ids 0 ..), the router scores all of
``published.n_routed_experts``, weights are normalised over all selected,
and the sum runs over the selected experts that are held, plus the shared
expert; the rest is left out, here as in the program. ``vocab_size`` is the
slice held: ids, logits and argmax are over it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import flops, reference
from chipbench.reference import F32, HI, _mm, _round, rms_norm
from chipbench.weights import Leaf, is_leaf


def routed_experts(m) -> int:
    """How many experts the router scores: the published count."""
    return m.get("published", {}).get("n_routed_experts",
                                      m["n_routed_experts"])


# -- 1. the program's entry ------------------------------------------------------

def deepseek_config(m: dict):
    """The program's own configuration object from the published keys."""
    from paddle_tpu.models.deepseek import DeepSeekConfig
    rs = m["rope_scaling"]
    return DeepSeekConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        first_k_dense_replace=m["first_k_dense_replace"],
        num_attention_heads=m["num_attention_heads"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        n_routed_experts=routed_experts(m),
        n_shared_experts=m["n_shared_experts"],
        num_experts_per_tok=m["num_experts_per_tok"], n_group=m["n_group"],
        topk_group=m["topk_group"],
        routed_scaling_factor=m["routed_scaling_factor"],
        expert_offset=0, n_local_experts=m["n_routed_experts"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]), rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        dtype=jnp.dtype(m.get("torch_dtype", "bfloat16")))


def train_step(m: dict, t: dict):
    raise SystemExit("chipbench: the deepseek family has no training path "
                     "yet (ROADMAP.md M2): no train cell can run it")


first_moment = train_step


def serve_engine(weights, m: dict, e: dict):
    """The program's own serving engine for a ``serve_open`` mix's
    ``engine`` block; the window calls ``submit()`` and ``step()`` itself."""
    from paddle_tpu.inference import InferenceEngine, ServeConfig
    return InferenceEngine(
        weights, deepseek_config(m),
        ServeConfig(block_size=e["block_size"], num_blocks=e["num_blocks"],
                    max_batch=e["max_batch"],
                    prefill_chunk=e["prefill_chunk"],
                    max_seq_len=e["max_seq_len"]))


# -- 2. the weights' tree --------------------------------------------------------

def leaves(m) -> dict:
    """The tree ``InferenceEngine`` takes for this model
    (``paddle_tpu.models.deepseek.param_shapes``): the leading dense layers
    in a list, the expert layers stacked on axis 0. Norm scales start at one;
    the router's correction bias starts from the seed like a matrix, so that
    the term does something."""
    from paddle_tpu.models.deepseek import param_shapes

    def leaf(path, shape):
        name = str(getattr(path[-1], "key", ""))
        return Leaf(tuple(shape), "one" if name.endswith("norm") else "normal")
    return jax.tree_util.tree_map_with_path(
        leaf, param_shapes(deepseek_config(m)),
        is_leaf=lambda x: isinstance(x, tuple))


# -- 3. the plain reference ------------------------------------------------------

def yarn_inv_freq(m):
    rs, d, base = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]
    pos = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    extra, inter = 1.0 / pos, 1.0 / (rs["factor"] * pos)

    def correction_dim(rotations):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(inter * ramp + extra * (1 - ramp), F32)


def _mscale(factor, m_):
    return 0.1 * m_ * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(m) -> float:
    rs = m["rope_scaling"]
    ms = _mscale(rs["factor"], rs["mscale_all_dim"])
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * ms * ms


def yarn_rope(x, positions, m):
    """x [S, heads, D] at ``positions`` [S]: half-rotation pairs."""
    rs = m["rope_scaling"]
    ang = positions.astype(F32)[:, None] * yarn_inv_freq(m)[None, :]
    ms = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * ms)[:, None, :], (jnp.sin(ang) * ms)[:, None, :]
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


HEAD_BLOCK = 8          # heads the reference attends at a time
ROW_BLOCK = 1024        # query rows, and FFN rows, at a time


def _blocks(n: int, block: int) -> int:
    """How many equal blocks of at most ``block`` divide ``n``."""
    nb = max(1, -(-n // block))
    while n % nb:
        nb += 1
    return nb


def attn_sublayer(p, x, m, mode):
    """x [S, H] -> x + MLA(norm(x)), expanded form, ``HEAD_BLOCK`` heads
    and ``ROW_BLOCK`` query rows at a time so that the float32 scores of a
    17,408-token forward stay under a gigabyte."""
    s, _ = x.shape
    nh, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"])
    rank, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    pos = jnp.arange(s)
    y = rms_norm(x, p["input_norm"], eps)
    cq = rms_norm(_mm(y, p["q_a"], mode), p["q_a_norm"], eps)
    kv = _mm(y, p["kv_a"], mode)
    ckv = rms_norm(kv[:, :rank], p["kv_a_norm"], eps)
    k_pe = yarn_rope(kv[:, None, rank:], pos, m)[:, 0]          # [S, dr]
    hb = nh // _blocks(nh, HEAD_BLOCK)
    nb = nh // hb
    q_b = p["q_b"].reshape(-1, nb, hb * (dn + dr)).transpose(1, 0, 2)
    kv_b = p["kv_b"].reshape(rank, nb, hb * (dn + dv)).transpose(1, 0, 2)
    o_w = p["o_proj"].reshape(nb, hb * dv, -1)
    nr = _blocks(s, ROW_BLOCK)
    scale = softmax_scale(m)
    ckv_r, kpe_r = _round(ckv, mode), _round(k_pe, mode)

    def heads(out, w):
        wq, wkv, wo = w
        q = _mm(cq, wq, mode).reshape(s, hb, dn + dr)
        q_nope, q_pe = q[..., :dn], yarn_rope(q[..., dn:], pos, m)
        kvh = jnp.matmul(ckv_r, _round(wkv.astype(F32), mode),
                         precision=HI).reshape(s, hb, dn + dv)
        k_nope, v = _round(kvh[..., :dn], mode), _round(kvh[..., dn:], mode)

        @jax.checkpoint
        def rows(args):
            qn, qp, row0 = args
            sc = (jnp.einsum("shd,thd->hst", _round(qn, mode), k_nope,
                             precision=HI)
                  + jnp.einsum("shd,td->hst", _round(qp, mode), kpe_r,
                               precision=HI)) * scale
            r = row0 + jnp.arange(qn.shape[0])
            mask = r[:, None] >= jnp.arange(s)[None, :]
            pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hst,thd->shd", _round(pr, mode), v,
                              precision=HI)
        o = lax.map(rows, (q_nope.reshape(nr, s // nr, hb, dn),
                           q_pe.reshape(nr, s // nr, hb, dr),
                           jnp.arange(nr) * (s // nr)))
        return out + _mm(o.reshape(s, hb * dv), wo, mode), None

    out, _ = lax.scan(heads, jnp.zeros_like(x), (q_b, kv_b, o_w))
    return x + out


def _in_rows(fn, y):
    """``fn`` over y [S, H] in ``ROW_BLOCK`` rows at a time."""
    s = y.shape[0]
    nr = _blocks(s, ROW_BLOCK)
    return lax.map(fn, y.reshape(nr, s // nr, -1)).reshape(s, -1)


def _swiglu(y, gate, up, down, mode):
    return _mm(jax.nn.silu(_mm(y, gate, mode)) * _mm(y, up, mode), down, mode)


def dense_sublayer(p, x, m, mode):
    y = rms_norm(x, p["post_norm"], m["rms_norm_eps"])
    return x + _in_rows(lambda r: _swiglu(r, p["gate_proj"], p["up_proj"],
                                          p["down_proj"], mode), y)


def route(y, router, bias, m, mode="f32"):
    """(idx [S, k] over all routed experts, w [S, k]) by the equations."""
    s = jax.nn.sigmoid(_mm(y, router, mode))
    sel = s + bias.astype(F32)
    t, e = sel.shape
    g = sel.reshape(t, m["n_group"], e // m["n_group"])
    group_score = lax.top_k(g, 2)[0].sum(-1)
    _, best = lax.top_k(group_score, m["topk_group"])
    keep = jnp.zeros((t, m["n_group"]), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    masked = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(t, e)
    _, idx = lax.top_k(masked, m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    return idx, w / w.sum(-1, keepdims=True) * m["routed_scaling_factor"]


def moe_sublayer(p, x, m, mode, held=None):
    """x + sum over the held experts of w_e(y) E_e(y), each expert over
    every row with the weight the router gave it (nought where it was not
    chosen), plus the shared expert once. ``held`` = (first id, count) of
    the experts whose weights ``p["experts"]`` holds; the configuration's
    share by default."""
    lo, n = held if held is not None else (0, m["n_routed_experts"])
    y = rms_norm(x, p["post_norm"], m["rms_norm_eps"])
    idx, w = route(y, p["router"], p["router_bias"], m, mode)

    def expert(out, xs):
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)   # [S]
        f = _in_rows(lambda r: _swiglu(r, gate, up, down, mode), y)
        return out + w_e[:, None] * f, None

    ex = p["experts"]
    out, _ = lax.scan(expert, jnp.zeros_like(x),
                      (jnp.arange(n), ex["gate"], ex["up"], ex["down"]))
    sh = p["shared"]
    shared = _in_rows(lambda r: _swiglu(r, sh["gate"], sh["up"], sh["down"],
                                        mode), y)
    return x + out + shared


@functools.partial(jax.jit, static_argnames=("m_items", "mode", "last"))
def _logits_jit(weights, ids, start, *, m_items, mode, last):
    m = dict(m_items)
    m["rope_scaling"] = dict(m.pop("rope_scaling_items"))
    x = jnp.take(weights["embed"], ids[0], axis=0).astype(F32)
    for p in weights["dense"]:
        x = dense_sublayer(p, attn_sublayer(p, x, m, mode), m, mode)

    def layer(x, p):
        return moe_sublayer(p, attn_sublayer(p, x, m, mode), m, mode), None

    x, _ = lax.scan(layer, x, weights["moe"])
    x = lax.dynamic_slice_in_dim(x, start, last, axis=0)
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    return _mm(x, weights["lm_head"], mode)


def _hashable(m):
    """The configuration as ``_logits_jit``'s static argument: its scalars,
    and the rope's group as items of its own."""
    return reference._hashable(m) + (
        ("rope_scaling_items", reference._hashable(m["rope_scaling"])),)


def logits_after(weights, m, tokens, last: int, padded: int, last_max: int,
                 mode="f32"):
    """``reference.logits_after`` through this family's forward pass."""
    fwd = lambda w, ids, start, *, m_items, mode, last: _logits_jit(
        w, ids, start, m_items=_hashable(m), mode=mode, last=last)
    return reference.logits_after(fwd, weights, m, tokens, last, padded,
                                  last_max, mode)


# -- 4. the work -----------------------------------------------------------------
# Required means what the algorithm needs once (``chipbench/flops.py``).

def attn_params(m) -> int:
    """MLA's matmul parameters of one layer: W_qa, W_qb, W_kva, W_kvb, W_o."""
    h, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * nh * (dn + dr)
            + h * (m["kv_lora_rank"] + dr)
            + m["kv_lora_rank"] * nh * (dn + dv) + nh * dv * h)


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def held_experts_per_token(m) -> float:
    """Selected experts a token finds held here, routing taken as uniform
    (seeded weights; ``moe_local_pairs_per_token.serve`` says how near)."""
    return m["num_experts_per_tok"] * m["n_routed_experts"] \
        / routed_experts(m)


def layer_matmul_params(m, moe: bool) -> float:
    """Parameters of one layer that take part in a matmul for a token."""
    if not moe:
        return attn_params(m) + 3 * m["hidden_size"] * m["intermediate_size"]
    return (attn_params(m) + m["hidden_size"] * routed_experts(m)
            + (m["n_shared_experts"] + held_experts_per_token(m))
            * expert_params(m))


def head_params(m) -> int:
    return m["hidden_size"] * m["vocab_size"]


def matmul_params(m) -> float:
    nd = m["first_k_dense_replace"]
    return (nd * layer_matmul_params(m, False)
            + (m["num_hidden_layers"] - nd) * layer_matmul_params(m, True)
            + head_params(m))


def pair_flops(m, absorbed: bool) -> int:
    """Attention FLOPs of one (query, key) pair in one layer: expanded,
    2 x heads x ((nope + rope) + v); absorbed, 2 x heads x ((rank + rope) +
    rank) against the latent."""
    nh, dr = m["num_attention_heads"], m["qk_rope_head_dim"]
    if absorbed:
        return 2 * nh * (2 * m["kv_lora_rank"] + dr)
    return 2 * nh * (m["qk_nope_head_dim"] + dr + m["v_head_dim"])


def forward_flops(m, new_tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """Serving: ``new_tokens`` tokens go through the layers, attending to
    ``context_sum`` keys in all; ``logit_rows`` of them go through the head.
    A token counts 0.5 held experts (8 x 16 / 256). Attention is counted at
    the EXPANDED rate for a prefill chunk and at the ABSORBED rate for a
    decode step, which is what a latent cache makes each need; W_kvb's
    parameters stand for the expansion of a new token's latent (prefill) or
    for the absorbed query and the output's expansion (decode: the same
    count). The harness asks for a decode step with ``logit_rows ==
    new_tokens`` and for a chunk with at most one logit row, which is how
    the two are told apart (a one-token last chunk counts as decode)."""
    decode = logit_rows == new_tokens
    layers = 2.0 * (matmul_params(m) - head_params(m)) * new_tokens \
        + m["num_hidden_layers"] * pair_flops(m, decode) * float(context_sum)
    return layers + 2.0 * head_params(m) * logit_rows


# kernels: one call of one layer

def latent_bytes(m, tokens: int, block_size: int, itemsize: int = 2) -> float:
    """Bytes of the latent blocks that hold ``tokens`` cached tokens."""
    blocks = -(-tokens // block_size)
    return float(blocks * block_size
                 * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize)


def mla_decode_call(m, context_lens, block_size: int):
    """(flops, bytes) of the absorbed decode attention of ONE layer for a
    batch whose rows attend ``context_lens`` cached tokens (the new one
    among them): each reads its latent blocks once, its [heads, W] bf16
    queries, and writes [heads, rank] float32 and one block back."""
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    w = rank + m["qk_rope_head_dim"]
    fl = float(pair_flops(m, True)) * sum(context_lens)
    by = sum(latent_bytes(m, c, block_size) for c in context_lens) \
        + len(context_lens) * (nh * (2 * w + 4 * rank) + w * block_size * 2)
    return fl, by


def mla_prefill_call(m, start: int, n: int, block_size: int):
    """(flops, bytes) of the absorbed prefill attention of ONE layer for a
    chunk of ``n`` live tokens after ``start`` cached ones: the causal
    pairs, the live latent blocks once, queries in and latent outputs out."""
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    w = rank + m["qk_rope_head_dim"]
    pairs = n * start + n * (n + 1) // 2
    by = latent_bytes(m, start + n, block_size) + n * nh * (w + rank) * 2
    return float(pair_flops(m, True)) * pairs, by


def moe_experts_call(m, pairs: int, experts_hit: int):
    """(flops, bytes) of the routed experts' grouped matmuls over any number
    of calls: gate, up and down for every (token, held expert) pair, the
    weights of every expert hit once a call. A bound on the sum."""
    return (2.0 * expert_params(m) * pairs,
            2.0 * expert_params(m) * experts_hit)


def train_kernels(m, t, peak) -> dict:
    return {}


def serve_kernels(m, e, iterations, peak) -> dict:
    """The counters ``kernel_roofline_pct`` reads in a ``serve_open`` cell,
    over the traced iterations: the least seconds the two latent-attention
    kernels could take, once a layer in every decode step and chunk. (The
    experts' count is read from the program's own registry by
    ``readers_deepseek.py``.)"""
    n_layers, bs = m["num_hidden_layers"], e["block_size"]
    dec = pre = 0.0
    for r in iterations:
        if r["decode_ctx"]:
            dec += n_layers * flops.min_seconds(
                *mla_decode_call(m, r["decode_ctx"], bs), peak)
        if r["prefill"]:
            start, n, _ = r["prefill"]
            pre += n_layers * flops.min_seconds(
                *mla_prefill_call(m, start, n, bs), peak)
    return {"mla_decode": {"least_s": dec} if dec else None,
            "mla_prefill": {"least_s": pre} if pre else None}


# -- 5. the rehearsal's size -----------------------------------------------------

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
        "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
        "vocab_size": 512}


def rehearsal(m: dict) -> dict:
    """The configuration at a size the CPU runs in seconds, every kind of
    layer in it: one dense and two expert layers, 8 of 32 experts held, 4
    groups. The rehearsal proves control flow, never a number."""
    out = dict(m, **TINY)
    out["published"] = dict(m.get("published", {}), n_routed_experts=32)
    out["rope_scaling"] = dict(m["rope_scaling"],
                               original_max_position_embeddings=64)
    return out


# -- 6. compiled for a described chip (``aot_check.py``) -------------------------

def _compiled(lower):
    from paddle_tpu.ops import _common
    with _common.interpret_mode(False):
        return lower().compile()


def aot_programs(m, t, one, with_reference):
    """(name, compile) of every program a cell of this family needs at its
    real size, from shapes placed by the sharding ``one``."""
    from paddle_tpu.models import deepseek as D
    config = deepseek_config(m)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.tree_util.tree_map(
        lambda leaf: sds(leaf.shape, jnp.bfloat16), leaves(m),
        is_leaf=is_leaf)
    i32 = jnp.int32
    e = t["engine"]
    pool = sds((config.num_hidden_layers, e["num_blocks"],
                config.latent_width, e["block_size"]), jnp.bfloat16)
    max_nb = -(-e["max_seq_len"] // e["block_size"])
    for b in (1, e["max_batch"]):       # the smallest and largest bucket
        yield f"decode, batch {b}", lambda b=b: _compiled(
            lambda: D._jitted_paged_decode(config).lower(
                params, pool, sds((b, max_nb), i32), sds((b,), i32),
                sds((b,), i32)))
    yield f"prefill chunk {e['prefill_chunk']}", lambda: _compiled(
        lambda: D._jitted_paged_prefill(config).lower(
            params, pool, sds((max_nb,), i32), sds((), i32),
            sds((e["prefill_chunk"],), i32), sds((), i32)))
    if with_reference:
        from chipbench.serve import check_shape
        padded, last_max = check_shape(t)
        yield f"reference forward at {padded} tokens", lambda: \
            _logits_jit.lower(params, sds((1, padded), i32), sds((), i32),
                              m_items=_hashable(m), mode="f32",
                              last=last_max).compile()
