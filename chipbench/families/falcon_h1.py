"""The Falcon-H1 family, on the serving path: what
``paddle_tpu.models.falcon_h1`` runs through ``InferenceEngine``. The same
six answers ``families/llama.py`` gives; ``m`` is a configuration's dict
with the published key names.

Equations (the program and this file's reference both compute them), every
layer alike, no biases except the convolution's:

- Embedding: ``x0 = E[token] * embedding_multiplier``.
- Block: ``h = RMSNorm_in(x)``; ``x <- x + Mixer(h * ssm_in_multiplier) *
  ssm_out_multiplier + Attn(h * attention_in_multiplier) *
  attention_out_multiplier``; then ``x <- x + MLP(RMSNorm_ff(x))``. The two
  mixers read ONE normed input and are summed.
- Attention: ``q = W_q u``, ``k = (W_k u) * key_multiplier``, ``v = W_v u``;
  rope (half-rotation layout, the whole head, base ``rope_theta``, no
  scaling) on q and k; causal softmax of ``q.k / sqrt(head_dim)``; grouped
  queries (query head ``h`` reads KV head ``h // (heads / kv_heads)``);
  ``W_o``. ``head_dim`` is a key of its own (20 x 128 = 2560, not 5120).
- MLP: ``W_down((W_up u) * silu((W_gate u) * mlp_multipliers[0])) *
  mlp_multipliers[1]``.
- Mixer (Mamba-2): ``p = (W_in u) * mup``, ``mup`` = ``ssm_multipliers[0..4]``
  spread over ``[z d_ssm | x d_ssm | B G N | C G N | dt NH]``. ``xBC_t <-
  silu(sum_k w_conv[:, k] xBC_{t-3+k} + b_conv)`` (depthwise, causal, zeros
  before the sequence). Per head ``h`` of group ``g = h // (NH / G)``:
  ``delta_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``, ``S_t =
  exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t`` (``S`` [P, N], zero before
  the sequence), ``y_t = S_t C_t + D x_t``. Then ``y <-
  RMSNorm_grouped(y * silu(z))``: the mean square over each of the G groups
  of ``d_ssm / G`` channels, a learned scale; ``out = W_out y``.
- Head: ``logits = (W_head RMSNorm_f(x_L)) * lm_head_multiplier``.

READINGS of keys the published config does not spell out (the
configuration's ``assumed`` lists them): ``mamba_use_mlp`` true = every
block has the SwiGLU; no clamp on ``delta``; the gated norm grouped by
``mamba_n_groups`` with ``mamba_norm_before_gate`` false (gate, then norm);
``mamba_d_ssm`` = ``mamba_n_heads`` x ``mamba_d_head`` taken as given where
``mamba_expand`` x hidden would say 10240. The program keeps the recurrent
state in float32 (a sum over every token seen) and the convolution's three
columns in bf16; the reference is float32 throughout.

THE STARTS. ``chipbench/weights.py`` makes every leaf ``normal`` (x 0.02),
``one`` or ``zero``. At those starts the convolution's output is ~0.004,
``delta`` = softplus(0) = 0.69 for every head (a memory of a token or two)
and ``D x`` outweighs ``S C`` by 10^4: no comparison would see the
recurrence. So ``starts`` maps the made leaves onto the published Mamba-2
initialisation, and ``serve_engine`` and ``logits_after`` both go through
it: ``A`` uniform in [1, 16] and ``delta``'s bias the inverse softplus of a
log-uniform [0.001, 0.1] (each from the made leaf's normal draw through its
CDF), ``D`` one, the convolution's bias zero. DEPARTURE from that
initialisation, for the same reason: the convolution's weights are scaled
so that its OUTPUT has a standard deviation near 1, as a trained model's
activations do: 0.5 a tap (four taps of unit-variance input give 1) over
the input's own deviation at these starts, ``0.02 sqrt(hidden)
ssm_in_multiplier ssm_multipliers[segment]`` (0.09 for x, 0.064 for B, 0.18
for C). At 0.5 alone ``B . C`` would be 10^-3 and ``S C`` half a percent of
``D x``; scaled so, ``S C`` is 0.4 of ``D x`` over all heads and outweighs
it in the heads with small ``A``. ``state_controls.py`` holds the starts to
it: the reference without ``S C``, and with the state lost every 512
positions, must both read not ``correct``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import flops, reference
from chipbench.reference import F32, _mm, attention, rms_norm, rope
from chipbench.weights import STD, Leaf, is_leaf


# -- 1. the program's entry ------------------------------------------------------

def falcon_config(m: dict):
    """The program's own configuration object from the published keys."""
    from paddle_tpu.models.falcon_h1 import FalconH1Config
    return FalconH1Config(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        mamba_d_ssm=m["mamba_d_ssm"], mamba_n_heads=m["mamba_n_heads"],
        mamba_d_head=m["mamba_d_head"], mamba_n_groups=m["mamba_n_groups"],
        mamba_d_state=m["mamba_d_state"], mamba_d_conv=m["mamba_d_conv"],
        mamba_chunk_size=m["mamba_chunk_size"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        embedding_multiplier=m["embedding_multiplier"],
        lm_head_multiplier=m["lm_head_multiplier"],
        attention_in_multiplier=m["attention_in_multiplier"],
        attention_out_multiplier=m["attention_out_multiplier"],
        key_multiplier=m["key_multiplier"],
        ssm_in_multiplier=m["ssm_in_multiplier"],
        ssm_out_multiplier=m["ssm_out_multiplier"],
        ssm_multipliers=tuple(m["ssm_multipliers"]),
        mlp_multipliers=tuple(m["mlp_multipliers"]),
        dtype=jnp.dtype(m.get("torch_dtype", "bfloat16")))


def train_step(m: dict, t: dict):
    raise SystemExit("chipbench: the falcon_h1 family has no training path "
                     "(ROADMAP.md M6: the scan has no backward): no train "
                     "cell can run it")


first_moment = train_step


def serve_engine(weights, m: dict, e: dict):
    """The program's own serving engine for a ``serve_open`` mix's
    ``engine`` block, on the weights at their starts (``starts``)."""
    from paddle_tpu.inference import InferenceEngine, ServeConfig
    return InferenceEngine(
        starts(weights, m), falcon_config(m),
        ServeConfig(block_size=e["block_size"], num_blocks=e["num_blocks"],
                    max_batch=e["max_batch"],
                    prefill_chunk=e["prefill_chunk"],
                    max_seq_len=e["max_seq_len"]))


# -- 2. the weights' tree --------------------------------------------------------

def leaves(m) -> dict:
    """The tree ``InferenceEngine`` takes for this model
    (``paddle_tpu.models.falcon_h1.param_shapes``): the layers' leaves
    stacked on axis 0. Norm scales and ``D`` start at one, the convolution's
    bias at zero; ``A_log``, ``dt_bias`` and the convolution's weights are
    drawn like a matrix and mapped by ``starts``."""
    from paddle_tpu.models.falcon_h1 import param_shapes

    def leaf(path, shape):
        name = str(getattr(path[-1], "key", ""))
        if name.endswith("norm") or name == "ssm_D":
            return Leaf(tuple(shape), "one")
        return Leaf(tuple(shape), "zero" if name == "ssm_conv_b" else "normal")
    return jax.tree_util.tree_map_with_path(
        leaf, param_shapes(falcon_config(m)),
        is_leaf=lambda x: isinstance(x, tuple))


A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 0.1)
CONV_TAP_STD = 0.5


def conv_input_std(m) -> np.ndarray:
    """[conv_dim]: the deviation of the convolution's input at the made
    starts and a unit-RMS normed stream: 0.02 sqrt(hidden) ssm_in_multiplier
    x the segment's ``ssm_multipliers`` entry (x, B, C are 1, 2, 3)."""
    gn = m["mamba_n_groups"] * m["mamba_d_state"]
    base = STD * math.sqrt(m["hidden_size"]) * m["ssm_in_multiplier"]
    return np.repeat(base * np.asarray(m["ssm_multipliers"][1:4]),
                     (m["mamba_d_ssm"], gn, gn))


@jax.jit
def _starts_jit(a_log, dt_bias, conv_w, input_std):
    # a made leaf is 0.02 z, z standard normal: u = CDF(z) is uniform
    cdf = lambda leaf: 0.5 * (1.0 + lax.erf(
        leaf.astype(F32) / (STD * math.sqrt(2.0))))
    a = A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * cdf(a_log)
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * cdf(dt_bias))
    bias = dt + jnp.log(-jnp.expm1(-dt))           # the inverse softplus
    w = conv_w.astype(F32) / STD * CONV_TAP_STD / input_std[None, :, None]
    return (jnp.log(a).astype(a_log.dtype), bias.astype(dt_bias.dtype),
            w.astype(conv_w.dtype))


def starts(weights, m):
    """The made tree with its Mamba-2 leaves at their starts (the docstring
    above): a new tree that shares every other leaf. Both the engine and the
    reference take their weights through here, in the type they are held
    in."""
    lay = dict(weights["layers"])
    lay["ssm_A_log"], lay["ssm_dt_bias"], lay["ssm_conv_w"] = _starts_jit(
        lay["ssm_A_log"], lay["ssm_dt_bias"], lay["ssm_conv_w"],
        jnp.asarray(conv_input_std(m), F32))
    return dict(weights, layers=lay)


# -- 3. the plain reference ------------------------------------------------------

RESET_EVERY = 512       # the ``reset`` control: an engine chunk's length


def _precision(mode: str):
    """A mode is ``<precision>[:<variant>]``: the precision ``_mm`` rounds
    to, and for ``state_controls.py`` what is planted in the recurrence:
    ``reset`` (the state zeroed at every multiple of 512 positions: a
    program that lost it between engine chunks) or ``norecur`` (``S C`` left
    out of ``y``: a program without the recurrence)."""
    prec, _, variant = mode.partition(":")
    if variant not in ("", "reset", "norecur"):
        raise ValueError(f"unknown variant {variant!r}")
    return prec, variant


def mixer(p, u, m, mode):
    """u [S, H] (normed, scaled) -> the mixer's output [S, H]: the
    recurrence as a plain scan over tokens."""
    prec, variant = _precision(mode)
    s = u.shape[0]
    nh, hd, g, n = (m["mamba_n_heads"], m["mamba_d_head"],
                    m["mamba_n_groups"], m["mamba_d_state"])
    d_ssm, gn, k = m["mamba_d_ssm"], g * n, m["mamba_d_conv"]
    mup = jnp.asarray(np.repeat(np.asarray(m["ssm_multipliers"], np.float32),
                                (d_ssm, d_ssm, gn, gn, nh)))
    proj = _mm(u, p["ssm_in_proj"], prec) * mup
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    w = p["ssm_conv_w"].astype(F32)
    conv = p["ssm_conv_b"].astype(F32) + sum(
        w[:, j] * padded[j:j + s] for j in range(k))
    x, bm, cm = jnp.split(jax.nn.silu(conv), [d_ssm, d_ssm + gn], axis=-1)
    x = x.reshape(s, nh, hd)
    bm = jnp.repeat(bm.reshape(s, g, n), nh // g, axis=1)       # [S, NH, N]
    cm = jnp.repeat(cm.reshape(s, g, n), nh // g, axis=1)
    delta = jax.nn.softplus(dt + p["ssm_dt_bias"].astype(F32))  # [S, NH]
    a = -jnp.exp(p["ssm_A_log"].astype(F32))

    def token(state, xs):
        x_t, b_t, c_t, d_t, pos = xs
        if variant == "reset":
            state = jnp.where(pos % RESET_EVERY == 0, 0.0, state)
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = lax.scan(token, jnp.zeros((nh, hd, n), F32),
                    (x, bm, cm, delta, jnp.arange(s)))
    if variant == "norecur":
        y = jnp.zeros_like(y)
    y = y + p["ssm_D"].astype(F32)[None, :, None] * x
    y = y.reshape(s, d_ssm) * jax.nn.silu(z)
    grp = y.reshape(s, g, d_ssm // g)
    grp = grp * lax.rsqrt(jnp.mean(grp * grp, axis=-1, keepdims=True)
                          + m["rms_norm_eps"])
    y = grp.reshape(s, d_ssm) * p["ssm_norm"].astype(F32)
    return _mm(y, p["ssm_out_proj"], prec)


def attn(p, u, m, mode):
    """u [S, H] (normed, scaled) -> the attention's output [S, H]."""
    prec, _ = _precision(mode)
    s, d = u.shape[0], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    pos = jnp.arange(s)
    q = rope(_mm(u, p["q_proj"], prec).reshape(1, s, nh, d), pos,
             m["rope_theta"])
    k = rope((_mm(u, p["k_proj"], prec) * m["key_multiplier"]).reshape(
        1, s, nkv, d), pos, m["rope_theta"])
    v = _mm(u, p["v_proj"], prec).reshape(1, s, nkv, d)
    return _mm(attention(q, k, v, prec).reshape(s, nh * d), p["o_proj"],
               prec)


def mlp(p, u, m, mode):
    prec, _ = _precision(mode)
    gate, out = m["mlp_multipliers"]
    gated = _mm(u, p["up_proj"], prec) * jax.nn.silu(
        _mm(u, p["gate_proj"], prec) * gate)
    return _mm(gated, p["down_proj"], prec) * out


def block(p, x, m, mode):
    """One layer on x [S, H] float32."""
    h = rms_norm(x, p["input_norm"], m["rms_norm_eps"])
    x = x + mixer(p, h * m["ssm_in_multiplier"], m, mode) \
        * m["ssm_out_multiplier"] \
        + attn(p, h * m["attention_in_multiplier"], m, mode) \
        * m["attention_out_multiplier"]
    return x + mlp(p, rms_norm(x, p["post_norm"], m["rms_norm_eps"]), m, mode)


HEAD_COLUMNS = 32768    # the head in column blocks: 261,120 x 5120 in
#                         float32 is 5.3 GB beside 10.5 GB of weights


def head(weights, x, m, mode):
    """x [R, H] -> logits [R, V] float32, the vocabulary in blocks of
    columns so that the float32 copy of the head stays small."""
    prec, _ = _precision(mode)
    y = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    w, v = weights["lm_head"], m["vocab_size"]
    step = next(c for c in range(min(v, HEAD_COLUMNS), 0, -1) if v % c == 0)
    cols = lax.map(lambda i: _mm(y, lax.dynamic_slice_in_dim(
        w, i * step, step, axis=1), prec), jnp.arange(v // step))
    return jnp.moveaxis(cols, 0, 1).reshape(x.shape[0], v) \
        * m["lm_head_multiplier"]


@functools.partial(jax.jit, static_argnames=("m_items", "mode", "last"))
def _logits_jit(weights, ids, start, *, m_items, mode, last):
    m = dict(m_items)
    x = jnp.take(weights["embed"], ids[0], axis=0).astype(F32) \
        * m["embedding_multiplier"]
    x, _ = lax.scan(lambda x, p: (block(p, x, m, mode), None), x,
                    weights["layers"])
    return head(weights, lax.dynamic_slice_in_dim(x, start, last, axis=0), m,
                mode)


def _lists(m):
    """``reference._hashable`` keeps scalars only; the two lists this model
    needs ride beside it as tuples."""
    return (("mlp_multipliers", tuple(m["mlp_multipliers"])),
            ("ssm_multipliers", tuple(m["ssm_multipliers"])))


def _forward(weights, ids, start, *, m_items, mode, last, lists):
    return _logits_jit(weights, ids, start, m_items=m_items + lists,
                       mode=mode, last=last)


def logits_after(weights, m, tokens, last: int, padded: int, last_max: int,
                 mode="f32"):
    """``reference.logits_after`` through this family's forward pass, on
    the weights at their starts. ``mode``: see ``_precision``."""
    return reference.logits_after(
        functools.partial(_forward, lists=_lists(m)), starts(weights, m), m,
        tokens, last, padded, last_max, mode)


# -- 4. the work -----------------------------------------------------------------
# Required means what the algorithm needs once (``chipbench/flops.py``).

def conv_dim(m) -> int:
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def state_elements(m) -> int:
    """Elements of one layer's recurrent state for one sequence."""
    return m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"]


def layer_matmul_params(m) -> int:
    h, i = m["hidden_size"], m["intermediate_size"]
    qo = 2 * h * m["num_attention_heads"] * m["head_dim"]
    kv = 2 * h * m["num_key_value_heads"] * m["head_dim"]
    mixer_ = h * (m["mamba_d_ssm"] + conv_dim(m) + m["mamba_n_heads"]) \
        + m["mamba_d_ssm"] * h
    return qo + kv + mixer_ + 3 * h * i


def head_params(m) -> int:
    return m["hidden_size"] * m["vocab_size"]


def recurrence_flops_per_token(m) -> float:
    """One token through one layer's recurrence: per state element the
    decay, the outer product's multiply and its add, and ``S C``'s multiply
    and add (5); the convolution's taps on top."""
    return 5.0 * state_elements(m) + 2.0 * m["mamba_d_conv"] * conv_dim(m)


def forward_flops(m, new_tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """Serving: ``new_tokens`` tokens go through the layers (matmuls, the
    recurrence, attention over ``context_sum`` keys in all); ``logit_rows``
    of them go through the head. The scan and the update count alike: what
    the recurrence needs, not what a chunked form spends."""
    q_width = m["num_attention_heads"] * m["head_dim"]
    layers = m["num_hidden_layers"] * (
        (2.0 * layer_matmul_params(m) + recurrence_flops_per_token(m))
        * new_tokens + 4.0 * q_width * context_sum)
    return layers + 2.0 * head_params(m) * logit_rows


# kernels: one call of one layer

def ssm_update_call(m, rows: int, itemsize: int = 2):
    """(flops, bytes) of ``ssm_state_update`` for ``rows`` live rows: each
    row's float32 state in and out, beside it x and y (float32), the decay
    a head, and B and C in the model's type."""
    gn = m["mamba_n_groups"] * m["mamba_d_state"]
    d_ssm, nh = m["mamba_d_ssm"], m["mamba_n_heads"]
    by = rows * (2 * state_elements(m) * 4 + 2 * d_ssm * 4 + nh * 4
                 + 2 * gn * itemsize)
    return 5.0 * state_elements(m) * rows, float(by)


def ssd_scan_call(m, tokens: int, itemsize: int = 2):
    """(flops, bytes) of ``ssd_chunk_scan`` for a chunk of ``tokens`` live
    tokens in its state-space-duality form over pieces of
    ``mamba_chunk_size``: a piece's ``C B^T`` once a group; per head the
    masked product with x, ``C S`` and the state's ``x^T B``; x, B, C in,
    delta and its running sum, y out in float32, the slot's state in and
    out."""
    nh, p, n, g = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                   m["mamba_n_groups"])
    q = m["mamba_chunk_size"]
    fl = 2.0 * tokens * (g * q * n + nh * (q * p + 2 * p * n))
    by = tokens * (nh * p + 2 * g * n) * itemsize + tokens * nh * 8 \
        + tokens * nh * p * 4 + 2 * state_elements(m) * 4
    return fl, float(by)


def train_kernels(m, t, peak) -> dict:
    return {}


def serve_kernels(m, e, iterations, peak) -> dict:
    """The counters ``kernel_roofline_pct`` reads in a ``serve_open`` cell,
    over the traced iterations: the least seconds the two state-space
    kernels could take, once a layer in every decode step (its live rows)
    and every chunk (its live tokens)."""
    n_layers = m["num_hidden_layers"]
    upd = scan = 0.0
    for r in iterations:
        if r["decode_ctx"]:
            upd += n_layers * flops.min_seconds(
                *ssm_update_call(m, len(r["decode_ctx"])), peak)
        if r["prefill"]:
            scan += n_layers * flops.min_seconds(
                *ssd_scan_call(m, r["prefill"][1]), peak)
    return {"ssm_update": {"least_s": upd} if upd else None,
            "ssd_scan": {"least_s": scan} if scan else None}


# -- 5. the rehearsal's size -----------------------------------------------------

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 5, "num_key_value_heads": 1, "head_dim": 16,
        "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_n_groups": 2, "mamba_d_state": 32, "mamba_chunk_size": 16,
        "vocab_size": 512, "num_hidden_layers": 2}


def rehearsal(m: dict) -> dict:
    """The configuration at a size the CPU runs in seconds: the group of 5
    query heads a KV head, two groups of B and C, and every multiplier
    stay. The rehearsal proves control flow, never a number."""
    return dict(m, **TINY)


# -- 6. compiled for a described chip (``aot_check.py``) -------------------------

def _compiled(lower):
    from paddle_tpu.ops import _common
    with _common.interpret_mode(False):
        return lower().compile()


def aot_programs(m, t, one, with_reference):
    """(name, compile) of every program a cell of this family needs at its
    real size, from shapes placed by the sharding ``one``."""
    from paddle_tpu.models import falcon_h1 as H
    config = falcon_config(m)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.tree_util.tree_map(
        lambda leaf: sds(leaf.shape, jnp.bfloat16), leaves(m),
        is_leaf=is_leaf)
    i32 = jnp.int32
    e = t["engine"]
    like = lambda a: sds(a.shape, a.dtype)
    cache = [like(a) for a in (
        *jax.eval_shape(lambda: H.init_paged_kv_pool(
            config, e["num_blocks"], e["block_size"])),
        *jax.eval_shape(lambda: H.init_state(config, e["max_batch"] + 1)))]
    max_nb = -(-e["max_seq_len"] // e["block_size"])
    for b in (1, e["max_batch"]):       # the smallest and largest bucket
        yield f"decode, batch {b}", lambda b=b: _compiled(
            lambda: H._jitted_paged_step("decode", config).lower(
                params, *cache, sds((b, max_nb), i32), sds((b,), i32),
                sds((b,), i32), sds((b,), i32)))
    chunk = (sds((max_nb,), i32), sds((), i32),
             sds((e["prefill_chunk"],), i32), sds((), i32))
    yield f"prefill chunk {e['prefill_chunk']}", lambda: _compiled(
        lambda: H._jitted_paged_step("prefill", config).lower(
            params, *cache, *chunk, sds((), i32)))
    b = e["max_batch"]
    yield f"prefill chunk {e['prefill_chunk']} carrying batch {b}", \
        lambda: _compiled(
            lambda: H._jitted_paged_step("prefill+decode", config).lower(
                params, *cache, *chunk, sds((b, max_nb), i32), sds((b,), i32),
                sds((b,), i32), sds((), i32), sds((b,), i32)))
    if with_reference:
        from chipbench.serve import check_shape
        padded, last_max = check_shape(t)
        yield f"reference forward at {padded} tokens", lambda: \
            _logits_jit.lower(params, sds((1, padded), i32), sds((), i32),
                              m_items=reference._hashable(m) + _lists(m),
                              mode="f32", last=last_max).compile()
