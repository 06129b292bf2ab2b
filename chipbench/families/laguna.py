"""The Laguna family, on the serving path: what ``paddle_tpu.models.laguna``
runs through ``InferenceEngine``. The same six answers ``families/llama.py``
gives; ``m`` is a configuration's dict with the published key names.

Equations (the program and this file's reference both compute them):
pre-norm decoder, RMSNorm (``rms_norm_eps``), untied head; per layer l
``h <- h + Attn_l(RMSNorm(h))``, then ``h <- h + FFN_l(RMSNorm(h))``.

- Attention: ``q = x W_q`` (``num_attention_heads_per_layer[l]`` heads of
  ``head_dim``), ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads``
  each); no bias, no q/k norm. ``layer_types[l]`` ``full_attention``: YaRN
  (``rope_parameters.full_attention``: theta, factor, original length,
  beta_fast, beta_slow) over the first ``head_dim x partial_rotary_factor``
  dimensions of each head, cos and sin times its ``attention_factor``, the
  other dimensions not rotated; causal. ``sliding_attention``: plain rope at
  its theta over the whole head; query i sees keys ``i - sliding_window < j
  <= i``. Rotated dimensions pair in the half-rotation layout. Scores ``q . k
  / sqrt(head_dim)``, grouped queries (head h reads KV head h // (heads /
  kv_heads)). Output ``sum_h g_h o_h W_o[h]`` with the per-head gate ``g =
  sigmoid(x W_g)``, ``W_g`` [hidden, heads], x the attention's normed input.
- FFN: ``mlp_only_layers`` a SwiGLU of ``intermediate_size``; the others
  ``p = softmax(y W_r)`` over all ``published.num_experts``, T the
  ``num_experts_per_tok`` largest, ``w_e = moe_routed_scaling_factor p_e /
  sum_T p``, FFN(y) = sum over e in T HELD HERE of ``w_e E_e(y)`` plus the
  ungated shared expert S(y); E and S SwiGLU of ``moe_intermediate_size``
  and ``shared_expert_intermediate_size``.

THE CHIP'S SHARE, as for DeepSeek-V3: the configuration's ``num_experts``
is how many routed experts are held (ids 0 ..), the router scores all of
``published.num_experts``, and what the absent experts would add is left
out, here as in the program; ``vocab_size`` is the slice held: ids, logits
and argmax are over it. The configuration's ``assumed`` lists the four
readings the published config leaves to convention.

THE STARTS. ``chipbench/weights.py`` makes every matrix ``normal`` (x 0.02).
At that start a window layer's scores spread by 0.02^2 x hidden = 1.2 over
a unit-RMS input, its softmax weighs a hundred of the 512 keys alike, and
its output is nearly their values' mean: a program that attended the whole
context instead would move a served token's logit by half a standard
deviation at most, under what bf16 rounding moves it by. A trained model's
window heads are sharp. So ``starts`` rescales each window layer's ``q`` and
``k`` projections to an RMS of sqrt(``WINDOW_SCORE_STD`` / hidden), which
spreads its scores by ``WINDOW_SCORE_STD`` whatever the made scale; every
other leaf is as made. ``serve_engine`` and ``logits_after`` both go
through it (the configuration's ``assumed`` says so), and
``variant_controls.py`` holds it to that: the reference with the window off
must read several times the program's widest gap.

``mode`` is ``<precision>[:<variant>]``: the precision ``_mm`` rounds to,
and for the hand tool ``chipbench/variant_controls.py`` and the tests a
mechanism taken out of the reference: ``nowindow`` (window layers attend
the whole context), ``nogate`` (the gate left out), ``fullrotary`` (the full
layers' YaRN over the whole head).
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

FULL, WINDOW = "full_attention", "sliding_attention"
VARIANTS = ("", "nowindow", "nogate", "fullrotary")
WINDOW_SCORE_STD = 3.0      # the window layers' scores at the starts


def routed_experts(m) -> int:
    """How many experts the router scores: the published count."""
    return m.get("published", {}).get("num_experts", m["num_experts"])


def rotary_dim(m, kind: str) -> int:
    return int(m["head_dim"]
               * m["rope_parameters"][kind].get("partial_rotary_factor", 1))


# -- 1. the program's entry ------------------------------------------------------

def laguna_config(m: dict):
    """The program's own configuration object from the published keys."""
    from paddle_tpu.models.laguna import LagunaConfig
    full, win = (m["rope_parameters"][k] for k in (FULL, WINDOW))
    dense = [i for i, t in enumerate(m["mlp_layer_types"]) if t == "dense"]
    return LagunaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        shared_expert_intermediate_size=m["shared_expert_intermediate_size"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        layer_types=tuple(m["layer_types"]),
        heads_per_layer=tuple(m["num_attention_heads_per_layer"]),
        mlp_only_layers=tuple(dense), sliding_window=m["sliding_window"],
        n_routed_experts=routed_experts(m),
        num_experts_per_tok=m["num_experts_per_tok"],
        routed_scaling_factor=m["moe_routed_scaling_factor"],
        expert_offset=0, n_local_experts=m["num_experts"],
        rms_norm_eps=m["rms_norm_eps"],
        full_rope_theta=float(full["rope_theta"]),
        full_rotary_dim=rotary_dim(m, FULL),
        rope_factor=float(full["factor"]),
        rope_original_max=full["original_max_position_embeddings"],
        rope_beta_fast=float(full["beta_fast"]),
        rope_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        window_rope_theta=float(win["rope_theta"]),
        dtype=jnp.dtype(m.get("torch_dtype", "bfloat16")))


def train_step(m: dict, t: dict):
    raise SystemExit("chipbench: the laguna family has no training path "
                     "(ROADMAP.md M4: the window in training): no train cell "
                     "can run it")


first_moment = train_step


def decode_buckets(max_batch: int):
    """Powers of two below half of ``max_batch``, the half and the whole:
    the harness's warm-up fills the batch to one more than half of
    ``max_batch`` rows, which reaches every one of these buckets (the
    engine's own ladder at 48 ends 32, 48, and 25 rows never reach 48)."""
    half = max_batch // 2
    small = [2 ** i for i in range(half.bit_length()) if 2 ** i < half]
    return tuple(small + [half] * (half > 0) + [max_batch])


def serve_engine(weights, m: dict, e: dict):
    """The program's own serving engine for a ``serve_open`` mix's
    ``engine`` block, on the weights at their starts (``starts``); the
    window calls ``submit()`` and ``step()`` itself."""
    from paddle_tpu.inference import InferenceEngine, ServeConfig
    return InferenceEngine(
        starts(weights, m), laguna_config(m),
        ServeConfig(block_size=e["block_size"], num_blocks=e["num_blocks"],
                    max_batch=e["max_batch"],
                    prefill_chunk=e["prefill_chunk"],
                    max_seq_len=e["max_seq_len"],
                    decode_buckets=decode_buckets(e["max_batch"])))


# -- 2. the weights' tree --------------------------------------------------------

def leaves(m) -> dict:
    """The tree ``InferenceEngine`` takes for this model
    (``paddle_tpu.models.laguna.param_shapes``). Norm scales start at one."""
    from paddle_tpu.models.laguna import param_shapes

    def leaf(path, shape):
        name = str(getattr(path[-1], "key", ""))
        return Leaf(tuple(shape), "one" if name.endswith("norm") else "normal")
    return jax.tree_util.tree_map_with_path(
        leaf, param_shapes(laguna_config(m)),
        is_leaf=lambda x: isinstance(x, tuple))


@jax.jit
def _at_rms(leaf, rms):
    """[layers, ...]: each layer's matrix scaled to an RMS of ``rms``."""
    x = leaf.astype(F32)
    now = jnp.sqrt(jnp.mean(x * x, axis=tuple(range(1, x.ndim)),
                            keepdims=True))
    return (x * (rms / now)).astype(leaf.dtype)


def starts(weights, m):
    """The made tree with the window layers' ``q`` and ``k`` projections at
    their starts (the docstring above): a new tree that shares every other
    leaf. Both the engine and the reference take their weights through
    here, in the type they are held in."""
    rms = math.sqrt(WINDOW_SCORE_STD / m["hidden_size"])
    win = dict(weights["window"])
    for name in ("q_proj", "k_proj"):
        win[name] = _at_rms(win[name], rms)
    return dict(weights, window=win)


# -- 3. the plain reference ------------------------------------------------------

def _precision(mode: str):
    prec, _, variant = mode.partition(":")
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    return prec, variant


def yarn_inv_freq(m, d: int):
    """[d/2] YaRN inverse frequencies of the full layers over ``d`` rotated
    dimensions: interpolated below the correction range of (beta_fast,
    beta_slow), original above it, a linear ramp between."""
    rp = m["rope_parameters"][FULL]
    base = rp["rope_theta"]
    pos = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    extra, inter = 1.0 / pos, 1.0 / (rp["factor"] * pos)

    def correction_dim(rotations):
        return d * math.log(rp["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(inter * ramp + extra * (1 - ramp), F32)


def rope(x, positions, m, kind: str, variant: str = ""):
    """x [S, heads, D] at ``positions`` [S], by the layer kind's rope."""
    d = x.shape[-1]
    if kind == WINDOW:
        theta = m["rope_parameters"][WINDOW]["rope_theta"]
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
        r, scale = d, 1.0
    else:
        r = d if variant == "fullrotary" else rotary_dim(m, FULL)
        inv = yarn_inv_freq(m, r)
        scale = m["rope_parameters"][FULL]["attention_factor"]
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], \
        (jnp.sin(ang) * scale)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


ROW_BLOCK = 1024        # FFN rows at a time
Q_BLOCK = 512           # query rows at a time


def _blocks(n: int, block: int) -> int:
    """How many equal blocks of at most ``block`` divide ``n``."""
    nb = max(1, -(-n // block))
    while n % nb:
        nb += 1
    return nb


def _in_rows(fn, x, block=ROW_BLOCK):
    """``fn(rows, first row)`` over x [S, ...] in equal blocks of at most
    ``block`` rows, one at a time: [S, ...] back."""
    s = x.shape[0]
    nb = _blocks(s, block)
    rb = s // nb
    out = lax.map(lambda a: fn(*a), (x.reshape((nb, rb) + x.shape[1:]),
                                     jnp.arange(nb) * rb))
    return out.reshape((s,) + out.shape[2:])


def _softmax_pv(q, keys, values, keep, mode):
    """q [R, NH, D] against keys and values [T, NKV, D] (grouped queries:
    head h reads KV head h // (NH / NKV)), ``keep`` [R, T]: [R, NH, D], a
    KV head at a time."""
    r, nh, d = q.shape
    nkv = keys.shape[1]
    qg = _round(q, mode).reshape(r, nkv, nh // nkv, d).transpose(1, 0, 2, 3)

    def group(args):
        qh, kh, vh = args               # [R, G, D], [T, D], [T, D]
        sc = jnp.einsum("rgd,td->rgt", qh, kh, precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(keep[:, None, :], sc, -jnp.inf),
                           axis=-1)
        return jnp.einsum("rgt,td->rgd", _round(p, mode), vh, precision=HI)
    out = lax.map(group, (qg, _round(keys, mode).transpose(1, 0, 2),
                          _round(values, mode).transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(r, nh, d)


def attn_sublayer(p, x, kind, m, mode):
    """x [S, H] -> x + the gated attention of a ``kind`` layer. Keys and
    values over the whole sequence; queries, gate and output in blocks of
    ``Q_BLOCK`` rows, each against the keys its rows reach (a window
    layer's: the band of ``sliding_window`` before the block), so that the
    float32 scores of a 52,224-token forward stay under half a gigabyte."""
    prec, variant = _precision(mode)
    s, d = x.shape[0], m["head_dim"]
    nkv, eps = m["num_key_value_heads"], m["rms_norm_eps"]
    window = m["sliding_window"] if kind == WINDOW \
        and variant != "nowindow" else None
    y = rms_norm(x, p["input_norm"], eps)
    k = rope(_mm(y, p["k_proj"], prec).reshape(s, nkv, d), jnp.arange(s), m,
             kind, variant)
    v = _mm(y, p["v_proj"], prec).reshape(s, nkv, d)
    if window is not None:      # the band before each block, zeros first
        k, v = (jnp.pad(a, ((window, 0), (0, 0), (0, 0))) for a in (k, v))

    @jax.checkpoint
    def rows(xb, r0):
        rb = xb.shape[0]
        pos = r0 + jnp.arange(rb)
        yb = rms_norm(xb, p["input_norm"], eps)
        q = rope(_mm(yb, p["q_proj"], prec).reshape(rb, -1, d), pos, m, kind,
                 variant)
        if window is None:
            t = jnp.arange(s)
            keep = t[None, :] <= pos[:, None]
            o = _softmax_pv(q, k, v, keep, prec)
        else:
            t = r0 - window + jnp.arange(rb + window)
            keep = (t[None, :] <= pos[:, None]) & (t[None, :] >= 0) \
                & (t[None, :] > pos[:, None] - window)
            band = lambda a: lax.dynamic_slice_in_dim(a, r0, rb + window, 0)
            o = _softmax_pv(q, band(k), band(v), keep, prec)
        if variant != "nogate":
            o = o * jax.nn.sigmoid(_mm(yb, p["g_proj"], prec))[:, :, None]
        return xb + _mm(o.reshape(rb, -1), p["o_proj"], prec)
    return _in_rows(rows, x, Q_BLOCK)


def _swiglu(y, gate, up, down, prec):
    return _mm(jax.nn.silu(_mm(y, gate, prec)) * _mm(y, up, prec), down, prec)


def dense_sublayer(p, x, m, mode):
    prec, _ = _precision(mode)

    def rows(xb, _):
        y = rms_norm(xb, p["post_norm"], m["rms_norm_eps"])
        return xb + _swiglu(y, p["gate_proj"], p["up_proj"], p["down_proj"],
                            prec)
    return _in_rows(rows, x)


def route(y, router, m, prec):
    """(idx [S, k] over all routed experts, w [S, k]) by the equations."""
    probs = jax.nn.softmax(_mm(y, router, prec), axis=-1)
    w, idx = lax.top_k(probs, m["num_experts_per_tok"])
    return idx, w / w.sum(-1, keepdims=True) * m["moe_routed_scaling_factor"]


def moe_sublayer(p, x, m, mode, held=None):
    """x + sum over the held experts of w_e(y) E_e(y), each expert over
    every row with the weight the router gave it (nought where it was not
    chosen), plus the shared expert once; ``ROW_BLOCK`` rows at a time.
    ``held`` = (first id, count) of the experts whose weights
    ``p["experts"]`` holds; the configuration's share by default."""
    prec, _ = _precision(mode)
    lo, n = held if held is not None else (0, m["num_experts"])
    ex, sh = p["experts"], p["shared"]

    def rows(xb, _):
        y = rms_norm(xb, p["post_norm"], m["rms_norm_eps"])
        idx, w = route(y, p["router"], m, prec)

        def expert(out, xs):
            e, gate, up, down = xs
            w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)
            return out + w_e[:, None] * _swiglu(y, gate, up, down, prec), None

        out, _ = lax.scan(expert, xb + _swiglu(y, sh["gate"], sh["up"],
                                               sh["down"], prec),
                          (jnp.arange(n), ex["gate"], ex["up"], ex["down"]))
        return out
    return _in_rows(rows, x)


def _layers(m):
    """[(attention kind, its subtree, index in it, FFN subtree, index in
    it)] in layer order."""
    seen = {"full": 0, "window": 0, "dense": 0, "moe": 0}
    out = []
    for kind, ffn in zip(m["layer_types"], m["mlp_layer_types"]):
        a = "full" if kind == FULL else "window"
        f = "dense" if ffn == "dense" else "moe"
        out.append((kind, a, seen[a], f, seen[f]))
        seen[a] += 1
        seen[f] += 1
    return out


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


@functools.partial(jax.jit, static_argnames=("m_items", "mode", "last"))
def _logits_jit(weights, ids, start, *, m_items, mode, last):
    m = dict(m_items)
    m["rope_parameters"] = {k: dict(v) for k, v in m.pop("rope_items")}
    m["layer_types"] = m.pop("layer_types_items")
    m["mlp_layer_types"] = m.pop("mlp_layer_types_items")
    prec, _ = _precision(mode)
    x = jnp.take(weights["embed"], ids[0], axis=0).astype(F32)
    for kind, a, ai, f, fi in _layers(m):
        x = attn_sublayer(_at(weights[a], ai), x, kind, m, mode)
        p = _at(weights[f], fi)
        x = (dense_sublayer(p, x, m, mode) if f == "dense"
             else moe_sublayer(p, x, m, mode))
    x = lax.dynamic_slice_in_dim(x, start, last, axis=0)
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    return _mm(x, weights["lm_head"], prec)


def _hashable(m):
    """The configuration as ``_logits_jit``'s static argument: its scalars,
    the rope's two groups and the layers' kinds."""
    rp = tuple((k, reference._hashable(v))
               for k, v in sorted(m["rope_parameters"].items()))
    return reference._hashable(m) + (
        ("rope_items", rp),
        ("layer_types_items", tuple(m["layer_types"])),
        ("mlp_layer_types_items", tuple(m["mlp_layer_types"])))


def logits_after(weights, m, tokens, last: int, padded: int, last_max: int,
                 mode="f32"):
    """``reference.logits_after`` through this family's forward pass, on
    the weights at their starts."""
    fwd = lambda w, ids, start, *, m_items, mode, last: _logits_jit(
        w, ids, start, m_items=_hashable(m), mode=mode, last=last)
    return reference.logits_after(fwd, starts(weights, m), m, tokens, last,
                                  padded, last_max, mode)


# -- 4. the work -----------------------------------------------------------------
# Required means what the algorithm needs once (``chipbench/flops.py``).

def heads(m, kind: str) -> int:
    return next(h for t, h in zip(m["layer_types"],
                                  m["num_attention_heads_per_layer"])
                if t == kind)


def n_layers(m, kind: str) -> int:
    return list(m["layer_types"]).count(kind)


def attn_params(m, kind: str) -> int:
    """Matmul parameters of one ``kind`` layer's attention: q, k, v, the
    gate and o."""
    h, hd = m["hidden_size"], m["head_dim"]
    nh, kv = heads(m, kind), m["num_key_value_heads"] * hd
    return h * (2 * nh * hd + 2 * kv + nh)


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def held_experts_per_token(m) -> float:
    """Selected experts a token finds held here, routing taken as uniform
    (seeded weights; ``moe_local_pairs_total`` says how near)."""
    return m["num_experts_per_tok"] * m["num_experts"] / routed_experts(m)


def ffn_params(m, dense: bool) -> float:
    h = m["hidden_size"]
    if dense:
        return 3 * h * m["intermediate_size"]
    shared = 3 * h * m["shared_expert_intermediate_size"]
    return (h * routed_experts(m) + shared
            + held_experts_per_token(m) * expert_params(m))


def head_params(m) -> int:
    return m["hidden_size"] * m["vocab_size"]


def layer_params(m) -> float:
    """Matmul parameters a token meets in the layers (not the head)."""
    return sum(attn_params(m, kind) + ffn_params(m, ffn == "dense")
               for kind, ffn in zip(m["layer_types"], m["mlp_layer_types"]))


def pair_flops(m, kind: str) -> int:
    """One (query, key) pair of one ``kind`` layer: q . k and p v a head."""
    return 4 * heads(m, kind) * m["head_dim"]


def step_flops(m, reach_, logit_rows: int) -> float:
    """What one pass over rows that reach ``reach_`` positions each
    requires: the layers' matmuls (10 experts a token at the held share,
    the shared expert, the gate), every full layer's pairs over all a row
    reaches, every window layer's over min(reach, ``sliding_window``), the
    head for ``logit_rows`` rows."""
    w = m["sliding_window"]
    full = float(sum(reach_))
    band = float(sum(min(r, w) for r in reach_))
    return (2.0 * layer_params(m) * len(reach_)
            + n_layers(m, FULL) * pair_flops(m, FULL) * full
            + n_layers(m, WINDOW) * pair_flops(m, WINDOW) * band
            + 2.0 * head_params(m) * logit_rows)


def reach(start: int, n: int):
    """Positions at or before each of ``n`` rows after ``start`` cached."""
    return [start + i + 1 for i in range(n)]


def forward_flops(m, new_tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """The harness's call (``serve.trace_counters``): a decode step
    (``logit_rows == new_tokens``) or a chunk of ``new_tokens`` rows whose
    ``context_sum`` is n x start + n (n + 1) / 2, from which the start
    follows. A decode step's rows are taken as reaching ``context_sum /
    new_tokens`` each; ``mfu.repopatch`` reads ``laguna_required_flops``
    (``serve_kernels``), which has every row's own reach."""
    n = new_tokens
    if logit_rows == n:
        return step_flops(m, [context_sum / n] * n, n)
    start = (context_sum - n * (n + 1) // 2) // n
    return step_flops(m, reach(start, n), logit_rows)


# kernels: one call of one layer

def _attend_call(m, kind, reach_, chunk: bool, itemsize: int = 2):
    """(flops, bytes) of one ``kind`` layer's attention kernel for rows
    that reach ``reach_`` positions each (a window layer: min(reach,
    window) keys a row): the pairs' two products; each key and value
    column read once for a chunk's rows (one sequence) and once a row for
    a decode batch's, its new column written; the queries in and the
    output out. A bound on the work: the kernels read whole blocks."""
    kvd, hd, nh = (m["num_key_value_heads"] * m["head_dim"], m["head_dim"],
                   heads(m, kind))
    w = m["sliding_window"] if kind == WINDOW else None
    keys = [min(r, w) if w else r for r in reach_]
    fl = float(pair_flops(m, kind)) * sum(keys)
    if chunk:
        first = reach_[0] - keys[0]            # the lowest position read
        cols = reach_[-1] - first
        io = len(reach_) * nh * hd * 2 * itemsize
    else:
        cols = sum(keys)
        io = len(reach_) * (nh * hd * (itemsize + 4) + 2 * kvd * itemsize)
    return fl, float(2 * kvd * itemsize * cols + io)


def window_attend_call(m, reach_, chunk: bool):
    return _attend_call(m, WINDOW, reach_, chunk)


def full_attend_call(m, reach_, chunk: bool):
    return _attend_call(m, FULL, reach_, chunk)


def moe_experts_call(m, pairs: int, experts_hit: int):
    """(flops, bytes) of the routed experts' grouped matmuls over any number
    of calls: gate, up and down for every (token, held expert) pair, the
    weights of every expert hit once a call. A bound on the sum."""
    return (2.0 * expert_params(m) * pairs,
            2.0 * expert_params(m) * experts_hit)


def train_kernels(m, t, peak) -> dict:
    return {}


def serve_kernels(m, e, iterations, peak) -> dict:
    """The counters ``kernel_roofline_pct`` and ``mfu_pct`` read in a
    ``serve_open`` cell, over the traced iterations: the least seconds the
    two attention kernels could take, once a layer of their kind in every
    decode step and chunk, and the required FLOPs with every row's own
    reach. (The experts' count is the program's own registry's.)"""
    win = full = need = 0.0
    for r in iterations:
        parts = []
        if r["prefill"]:
            start, n, first = r["prefill"]
            parts.append((reach(start, n), True, first))
        if r["decode_ctx"]:
            parts.append((r["decode_ctx"], False, len(r["decode_ctx"])))
        for reach_, chunk, rows in parts:
            win += n_layers(m, WINDOW) * flops.min_seconds(
                *window_attend_call(m, reach_, chunk), peak)
            full += n_layers(m, FULL) * flops.min_seconds(
                *full_attend_call(m, reach_, chunk), peak)
            need += step_flops(m, reach_, rows)
    return {"window_attend": {"least_s": win} if win else None,
            "full_attend": {"least_s": full} if full else None,
            "laguna_required_flops": need}


# -- 5. the rehearsal's size -----------------------------------------------------

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
        "num_experts_per_tok": 4, "sliding_window": 16, "vocab_size": 512}


def rehearsal(m: dict) -> dict:
    """The configuration at a size the CPU runs in seconds, every kind of
    layer in it as the configuration orders them (groups of 6 and 9 query
    heads a KV head, as published), 8 of 32 experts held, a window of 16.
    The rehearsal proves control flow, never a number."""
    out = dict(m, **TINY)
    out["num_attention_heads_per_layer"] = [
        h // m["num_key_value_heads"] * TINY["num_key_value_heads"]
        for h in m["num_attention_heads_per_layer"]]
    out["published"] = dict(m.get("published", {}), num_experts=32)
    rp = m["rope_parameters"]
    out["rope_parameters"] = dict(rp, **{FULL: dict(
        rp[FULL], original_max_position_embeddings=64)})
    return out


# -- 6. compiled for a described chip (``aot_check.py``) -------------------------

def _compiled(lower):
    from paddle_tpu.ops import _common
    with _common.interpret_mode(False):
        return lower().compile()


def aot_programs(m, t, one, with_reference):
    """(name, compile) of every program a cell of this family needs at its
    real size, from shapes placed by the sharding ``one``: the serving
    programs, with the token board, as the engine launches them."""
    from paddle_tpu.models import laguna as La
    config = laguna_config(m)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.tree_util.tree_map(
        lambda leaf: sds(leaf.shape, jnp.bfloat16), leaves(m),
        is_leaf=is_leaf)
    i32 = jnp.int32
    e = t["engine"]
    like = lambda a: sds(a.shape, a.dtype)
    ring = La.ring_size(config, e["prefill_chunk"], e["block_size"])
    cache = [like(a) for a in (
        *jax.eval_shape(lambda: La.init_paged_kv_pool(
            config, e["num_blocks"], e["block_size"])),
        *jax.eval_shape(lambda: La.init_rings(
            config, e["max_batch"] + 1, ring, e["block_size"])))]
    max_nb = -(-e["max_seq_len"] // e["block_size"])
    board = sds((e["max_batch"] + 1,), i32)
    step = lambda kind: La._jitted_paged_step(kind, config, True)
    for b in (1, e["max_batch"]):       # the smallest and largest bucket
        yield f"decode, batch {b}", lambda b=b: _compiled(
            lambda: step("decode").lower(
                params, *cache, sds((b, max_nb), i32), sds((b,), i32),
                sds((b,), i32), sds((b,), i32), board, sds((b,), i32)))
    chunk = (sds((max_nb,), i32), sds((), i32),
             sds((e["prefill_chunk"],), i32), sds((), i32))
    yield f"prefill chunk {e['prefill_chunk']}", lambda: _compiled(
        lambda: step("prefill").lower(params, *cache, *chunk, sds((), i32),
                                      board))
    b = e["max_batch"]
    yield f"prefill chunk {e['prefill_chunk']} carrying batch {b}", \
        lambda: _compiled(lambda: step("prefill+decode").lower(
            params, *cache, *chunk, sds((b, max_nb), i32), sds((b,), i32),
            sds((b,), i32), sds((), i32), sds((b,), i32), board,
            sds((b,), i32)))
    if with_reference:
        from chipbench.serve import check_shape
        padded, last_max = check_shape(t)
        yield f"reference forward at {padded} tokens", lambda: \
            _logits_jit.lower(params, sds((1, padded), i32), sds((), i32),
                              m_items=_hashable(m), mode="f32",
                              last=last_max).compile()
