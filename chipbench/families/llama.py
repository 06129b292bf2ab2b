"""The Llama family: the dense pre-norm decoder with grouped-query
attention that ``paddle_tpu.models.llama`` runs (Yi-1.5 and Mistral-7B share
its equations). What the harness asks of a family, in the order of its
sections: the program's entry, the weights' tree, the plain reference, the
work counted, the rehearsal's size, and the programs ``aot_check.py``
compiles for a described chip. ``m`` is a configuration's dict with the
published key names (``hidden_size`` ...).

Equations: pre-norm decoder; RMSNorm; rotary embedding in the half-rotation
(NeoX) layout at base ``rope_theta``; grouped-query causal attention scaled
by ``1/sqrt(head_dim)``; SwiGLU ``down(silu(gate(x)) * up(x))``; untied
head. The reference's shared pieces are ``chipbench/reference.py``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import flops, reference
from chipbench.reference import F32, _mm, attention, rms_norm, rope
from chipbench.weights import Leaf, is_leaf


# -- 1. the program's entry ------------------------------------------------------

def llama_config(m: dict):
    """The program's own configuration object from the published keys."""
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=jnp.dtype(m["torch_dtype"]))


def parallel_config(t: dict):
    """The program's layout object from a training mix's file."""
    from paddle_tpu.models.llama import ParallelConfig
    return ParallelConfig(remat=True, remat_policy=t["remat_policy"])


def train_step(m: dict, t: dict):
    """The program's own ``(step_fn, params, opt)`` for a ``train`` mix; the
    window calls ``step_fn(params, opt, ids, labels)`` itself."""
    from paddle_tpu.models.llama import build_train_step
    return build_train_step(llama_config(m), parallel_config(t), lr=t["lr"],
                            seed=0)


def first_moment(opt):
    """AdamW's first moment, a tree like the parameters', in ``opt``."""
    return opt["m"]


def serve_engine(weights, m: dict, e: dict):
    """The program's own serving engine for a ``serve_open`` mix's
    ``engine`` block; the window calls ``submit()`` and ``step()`` itself."""
    from paddle_tpu.inference import InferenceEngine, ServeConfig
    return InferenceEngine(
        weights, llama_config(m),
        ServeConfig(block_size=e["block_size"], num_blocks=e["num_blocks"],
                    max_batch=e["max_batch"],
                    prefill_chunk=e["prefill_chunk"],
                    max_seq_len=e["max_seq_len"]))


# -- 2. the weights' tree --------------------------------------------------------

def leaves(m) -> dict:
    """The tree ``build_train_step`` and ``InferenceEngine`` take: per-layer
    leaves stacked on axis 0; norms start at one."""
    h, i, v, n = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                  m["num_hidden_layers"])
    q = m["num_attention_heads"] * head_dim(m)
    kv = kv_dim(m)
    return {
        "embed": Leaf((v, h)),
        "layers": {
            "input_norm": Leaf((n, h), "one"), "q_proj": Leaf((n, h, q)),
            "k_proj": Leaf((n, h, kv)), "v_proj": Leaf((n, h, kv)),
            "o_proj": Leaf((n, q, h)), "post_norm": Leaf((n, h), "one"),
            "gate_proj": Leaf((n, h, i)), "up_proj": Leaf((n, h, i)),
            "down_proj": Leaf((n, i, h)),
        },
        "final_norm": Leaf((h,), "one"),
        "lm_head": Leaf((h, v)),
    }


# -- 3. the plain reference ------------------------------------------------------

def attn_sublayer(p, x, m, mode):
    """x [B, S, H] -> x + attention(norm(x))."""
    b, s, _ = x.shape
    d = head_dim(m)
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    pos = jnp.arange(s)
    y = rms_norm(x, p["input_norm"], m["rms_norm_eps"])
    q = rope(_mm(y, p["q_proj"], mode).reshape(b, s, nh, d), pos,
             m["rope_theta"])
    k = rope(_mm(y, p["k_proj"], mode).reshape(b, s, nkv, d), pos,
             m["rope_theta"])
    v = _mm(y, p["v_proj"], mode).reshape(b, s, nkv, d)
    a = attention(q, k, v, mode).reshape(b, s, nh * d)
    return x + _mm(a, p["o_proj"], mode)


def mlp_sublayer(p, x, m, mode):
    y = rms_norm(x, p["post_norm"], m["rms_norm_eps"])
    gated = jax.nn.silu(_mm(y, p["gate_proj"], mode)) \
        * _mm(y, p["up_proj"], mode)
    return x + _mm(gated, p["down_proj"], mode)


ATTN_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "o_proj")
MLP_LEAVES = ("post_norm", "gate_proj", "up_proj", "down_proj")


def _pick(p, names):
    return {n: p[n] for n in names}


# -- forward only (serving) ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_items", "mode", "last"))
def _logits_jit(weights, ids, start, *, m_items, mode, last):
    m = dict(m_items)
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)

    def layer(x, p):
        x = attn_sublayer(_pick(p, ATTN_LEAVES), x, m, mode)
        return mlp_sublayer(_pick(p, MLP_LEAVES), x, m, mode), None

    x, _ = lax.scan(layer, x, weights["layers"])
    x = lax.dynamic_slice_in_dim(x, start, last, axis=1)
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    return _mm(x, weights["lm_head"], mode)[0]


def logits_after(weights, m, tokens, last: int, padded: int, last_max: int,
                 mode="f32"):
    """``reference.logits_after`` through this family's forward pass."""
    return reference.logits_after(_logits_jit, weights, m, tokens, last,
                                  padded, last_max, mode)


# -- training: three AdamW steps, sub-layer by sub-layer -------------------------

def train_programs(m, hp, mode):
    """The jitted pieces of one reference step, by name."""
    return reference.train_programs(
        m, hp, mode, {"attn": attn_sublayer, "mlp": mlp_sublayer},
        m["rms_norm_eps"])


class Trainer:
    """The reference's three steps. ``weights`` is the benchmark's tree
    (stacked layers); it is unstacked here so that each layer's leaves can
    be updated, and donated, alone."""

    def __init__(self, weights, m, hp, mode="f32", fault=None):
        if fault not in (None, "half_batch"):
            raise ValueError(f"unknown fault {fault!r}")
        self.m, self.hp, self.mode = dict(m), dict(hp), mode
        self.half_batch = fault == "half_batch"     # planted, for readings
        self.top = {k: weights[k] for k in ("embed", "final_norm", "lm_head")}
        self.layers = [{k: a[i] for k, a in weights["layers"].items()}
                       for i in range(m["num_hidden_layers"])]
        zeros = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, F32), t)
        self.top_m, self.top_v = zeros(self.top), zeros(self.top)
        self.layers_m = [zeros(l) for l in self.layers]
        self.layers_v = [zeros(l) for l in self.layers]
        self.t = 0
        for name, fn in train_programs(self.m, hp, mode).items():
            setattr(self, name, fn)

    def step(self, ids, labels, head_rows=1024):
        """One step on host arrays ids, labels [B, S]. Returns (loss,
        {leaf's path: squared gradient norm}), the leaves being the
        program's stacked ones (``layers/q_proj``: all layers' together)."""
        if self.half_batch:
            ids, labels = ids[: len(ids) // 2], labels[: len(labels) // 2]
        self.t += 1
        t = jnp.float32(self.t)
        ids = jnp.asarray(ids, jnp.int32)
        b, s = ids.shape
        # sub-layer inputs wait on the host for the backward pass
        x = self.embed_fwd(self.top["embed"], ids)
        xs = [np.asarray(x)]
        for p in self.layers:
            for names, fwd in ((ATTN_LEAVES, self.attn_fwd),
                               (MLP_LEAVES, self.mlp_fwd)):
                x = fwd(_pick(p, names), x)
                xs.append(np.asarray(x))
        xs.pop()
        lab = jnp.asarray(np.asarray(labels, np.int32).reshape(-1))
        rows = min(head_rows, b * s)
        loss, g_norm, g_head, dy = self.head_bwd(
            self.top["final_norm"], self.top["lm_head"],
            x.reshape(b * s, -1), lab, rows)
        del x
        dy = dy.reshape(b, s, -1)
        sq = {}
        head_p = {k: self.top[k] for k in ("final_norm", "lm_head")}
        head_p, hm, hv, s_ = self.update(
            head_p, {k: self.top_m[k] for k in head_p},
            {k: self.top_v[k] for k in head_p},
            {"final_norm": g_norm, "lm_head": g_head}, t)
        del g_norm, g_head
        self.top.update(head_p), self.top_m.update(hm), self.top_v.update(hv)
        sq.update({k: float(v) for k, v in s_.items()})
        for i in reversed(range(len(self.layers))):
            for names, bwd in ((MLP_LEAVES, self.mlp_bwd),
                               (ATTN_LEAVES, self.attn_bwd)):
                x_in = jnp.asarray(xs.pop())
                p, mo, vo, dy, s_ = bwd(
                    _pick(self.layers[i], names),
                    _pick(self.layers_m[i], names),
                    _pick(self.layers_v[i], names), x_in, dy, t)
                self.layers[i].update(p)
                self.layers_m[i].update(mo)
                self.layers_v[i].update(vo)
                for k, v in s_.items():
                    sq["layers/" + k] = sq.get("layers/" + k, 0.0) + float(v)
        ge = self.embed_grad(ids, dy, self.m["vocab_size"])
        e, em, ev, s_ = self.update(
            {"embed": self.top["embed"]}, {"embed": self.top_m["embed"]},
            {"embed": self.top_v["embed"]}, {"embed": ge}, t)
        self.top.update(e), self.top_m.update(em), self.top_v.update(ev)
        sq["embed"] = float(s_["embed"])
        return float(loss), sq

    def change_sq(self, initial):
        """{leaf's path: squared norm of (parameters now - ``initial``)},
        ``initial`` being the benchmark's tree made again from the seed."""
        sq = jax.jit(lambda a, b: jnp.sum(jnp.square(
            a.astype(F32) - b.astype(F32))))
        diff = lambda a, b: float(sq(a, b))
        out = {k: diff(self.top[k], initial[k]) for k in self.top}
        for name in self.layers[0]:
            out["layers/" + name] = sum(diff(l[name], initial["layers"][name][i])
                            for i, l in enumerate(self.layers))
        return out


# -- 4. the work -----------------------------------------------------------------
# Required means what the algorithm needs once (``chipbench/flops.py``).

def head_dim(m) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def kv_dim(m) -> int:
    return m["num_key_value_heads"] * head_dim(m)


def layer_matmul_params(m) -> int:
    h, i = m["hidden_size"], m["intermediate_size"]
    qo = 2 * h * m["num_attention_heads"] * head_dim(m)
    kv = 2 * h * kv_dim(m)
    return qo + kv + 3 * h * i


def head_params(m) -> int:
    return m["hidden_size"] * m["vocab_size"]


def matmul_params(m) -> int:
    """Parameters that take part in a matmul for every token."""
    return m["num_hidden_layers"] * layer_matmul_params(m) + head_params(m)


def train_flops_per_token(m, seq: int) -> float:
    """Forward and backward of one token in a sequence of ``seq``: 6 a
    matmul parameter, and causal attention, whose forward is QK^T and PV
    over on average seq/2 keys: 2 matmuls x 2 x (seq/2) x q width = 2 seq
    q_width a layer, three times that with the backward."""
    q_width = m["num_attention_heads"] * head_dim(m)
    attn = 6.0 * m["num_hidden_layers"] * seq * q_width
    return 6.0 * matmul_params(m) + attn


def forward_flops(m, new_tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """Serving: ``new_tokens`` tokens go through the layers, attending to
    ``context_sum`` keys in all (the sum over those tokens of the keys each
    one sees, itself included); ``logit_rows`` of them go through the head."""
    q_width = m["num_attention_heads"] * head_dim(m)
    layers = m["num_hidden_layers"] * (
        2.0 * layer_matmul_params(m) * new_tokens + 4.0 * q_width * context_sum)
    return layers + 2.0 * head_params(m) * logit_rows


# kernels: one call

def flash_fwd_call(m, batch: int, seq: int, itemsize: int = 2):
    """(flops, bytes) of one causal flash forward over [batch, seq]."""
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = head_dim(m)
    flops = 2.0 * batch * nh * seq * seq * d        # 2 matmuls, half square
    q_o = 2 * batch * seq * nh * d * itemsize
    k_v = 2 * batch * seq * nkv * d * itemsize
    lse = batch * nh * seq * 4
    return flops, float(q_o + k_v + lse)


def flash_bwd_call(m, batch: int, seq: int, itemsize: int = 2):
    """(flops, bytes) of one fused causal flash backward: five matmuls over
    the half square (QK^T again, dP, dV, dQ, dK); reads q k v o do lse,
    writes dq dk dv."""
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = head_dim(m)
    flops = 5.0 * batch * nh * seq * seq * d
    q_like = 4 * batch * seq * nh * d * itemsize      # q o do dq
    kv_like = 4 * batch * seq * nkv * d * itemsize    # k v dk dv
    lse = 2 * batch * nh * seq * 4                    # lse and delta
    return flops, float(q_like + kv_like + lse)


def paged_decode_call(m, context_lens, block_size: int, itemsize: int = 2):
    """(flops, bytes) of the paged decode attention of ONE layer for a batch
    whose sequences hold ``context_lens`` cached tokens: each reads its
    blocks of K and V once and writes one new column."""
    nh, d = m["num_attention_heads"], head_dim(m)
    kvd = kv_dim(m)
    keys = float(sum(context_lens))
    blocks = float(sum(-(-c // block_size) for c in context_lens))
    flops = 4.0 * nh * d * keys
    kv_bytes = 2 * blocks * block_size * kvd * itemsize
    q_o = 2 * len(context_lens) * nh * d * itemsize
    return flops, kv_bytes + q_o


def train_kernels(m, t, peak) -> dict:
    """The counters ``kernel_roofline_pct`` reads in a ``train`` cell: the
    least seconds one call of each flash kernel could take."""
    fwd = flash_fwd_call(m, t["batch"], t["seq"])
    bwd = flash_bwd_call(m, t["batch"], t["seq"])
    return {"flash_fwd": {"per_call_least_s": flops.min_seconds(*fwd, peak)},
            "flash_bwd": {"per_call_least_s": flops.min_seconds(*bwd, peak)}}


def serve_kernels(m, e, iterations, peak) -> dict:
    """The same in a ``serve_open`` cell, over the traced iterations (each
    with ``decode_ctx``, the cached tokens of every row it decoded): the
    paged decode attention runs once a layer in every decode step."""
    least = 0.0
    for r in iterations:
        if r["decode_ctx"]:
            least += m["num_hidden_layers"] * flops.min_seconds(
                *paged_decode_call(m, r["decode_ctx"], e["block_size"]), peak)
    return {"paged_decode": {"least_s": least} if least else None}


# -- 5. the rehearsal's size -----------------------------------------------------

TINY = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "vocab_size": 512, "num_hidden_layers": 2}


def rehearsal(m: dict) -> dict:
    """The configuration at a size the CPU runs in seconds: the rehearsal
    proves control flow, never a number. The ratio of heads to KV heads
    stays."""
    ratio = m["num_attention_heads"] // m["num_key_value_heads"]
    out = dict(m, **TINY)
    out["num_key_value_heads"] = max(1, TINY["num_attention_heads"] // ratio)
    out.pop("head_dim", None)
    return out


# -- 6. compiled for a described chip (``aot_check.py``) -------------------------

def _compiled(lower):
    """``lower()`` compiled with the kernels lowered for the chip: under a
    CPU backend the program's kernels would take their interpreter."""
    from paddle_tpu.ops import _common
    with _common.interpret_mode(False):
        return lower().compile()


def _train_step_from_shapes(L, config, parallel, lr):
    """``build_train_step``'s jitted step without its arrays: the same loss
    and AdamW update, traced from shapes."""
    def step(p, opt, ids, labels):
        loss, grads = jax.value_and_grad(
            lambda p_: L.llama_loss(p_, ids, labels, config, parallel, None,
                                    use_flash=True))(p)
        new_p, new_opt = L._adamw_update(p, grads, opt, lr)
        return new_p, new_opt, loss
    return jax.jit(step, donate_argnums=(0, 1))


def aot_programs(m, t, one, with_reference):
    """(name, compile) of every program a cell of this family needs at its
    real size, from shapes placed by the sharding ``one`` (a described
    device); ``with_reference`` adds the plain reference's."""
    from paddle_tpu.models import llama as L
    config = llama_config(m)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    shapes = leaves(m)
    tree = lambda dtype: jax.tree_util.tree_map(
        lambda leaf: sds(leaf.shape, dtype), shapes, is_leaf=is_leaf)
    params, i32 = tree(jnp.bfloat16), jnp.int32
    if t["kind"] == "train":
        moments = tree(jnp.float32)
        opt = {"m": moments, "v": moments, "t": sds((), jnp.float32)}
        batch = sds((t["batch"], t["seq"]), i32)
        # build_train_step makes real arrays; lower its step from shapes
        # instead, with the program's own pieces and use_flash on
        step = _train_step_from_shapes(L, config, parallel_config(t),
                                       t["lr"])
        yield "train step", lambda: _compiled(
            lambda: step.lower(params, opt, batch, batch))
        if not with_reference:
            return
        progs = train_programs(m, dict(t["adamw"], lr=t["lr"]), "f32")
        lay = {k: sds(leaf.shape[1:], jnp.bfloat16)
               for k, leaf in shapes["layers"].items()}
        lay32 = {k: sds(leaf.shape[1:], jnp.float32)
                 for k, leaf in shapes["layers"].items()}
        x = sds((t["batch"], t["seq"], m["hidden_size"]), jnp.float32)
        tt = sds((), jnp.float32)
        for name, names in (("attn_bwd", ATTN_LEAVES),
                            ("mlp_bwd", MLP_LEAVES)):
            yield f"reference {name}", lambda name=name, names=names: \
                progs[name].lower(_pick(lay, names), _pick(lay32, names),
                                  _pick(lay32, names), x, x, tt).compile()
        n = t["batch"] * t["seq"]
        yield "reference head_bwd", lambda: progs["head_bwd"].lower(
            sds(shapes["final_norm"].shape, jnp.bfloat16),
            sds(shapes["lm_head"].shape, jnp.bfloat16),
            sds((n, m["hidden_size"]), jnp.float32), sds((n,), i32),
            1024).compile()
        return
    # serving: the engine's own jitted decode and prefill programs
    e = t["engine"]
    pool = sds((config.num_hidden_layers, e["num_blocks"],
                config.num_key_value_heads * config.head_dim,
                e["block_size"]), jnp.bfloat16)
    max_nb = -(-e["max_seq_len"] // e["block_size"])
    fz = L._freeze_config(config)
    for b in (1, e["max_batch"]):       # the smallest and largest bucket
        yield f"decode, batch {b}", lambda b=b: _compiled(
            lambda: L._jitted_paged_decode(fz).lower(
                params, pool, pool, sds((b, max_nb), i32), sds((b,), i32),
                sds((b,), i32)))
    yield f"prefill chunk {e['prefill_chunk']}", lambda: _compiled(
        lambda: L._jitted_paged_prefill(fz).lower(
            params, pool, pool, sds((max_nb,), i32), sds((), i32),
            sds((e["prefill_chunk"],), i32), sds((), i32)))
    if with_reference:
        from chipbench.serve import check_shape
        padded, last_max = check_shape(t)
        yield f"reference forward at {padded} tokens", lambda: \
            _logits_jit.lower(params, sds((1, padded), i32), sds((), i32),
                              m_items=reference._hashable(m), mode="f32",
                              last=last_max).compile()
