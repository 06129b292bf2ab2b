"""The plain reference: the decoder in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision, with no kernels, no cache, no
paging and no batching tricks. It imports nothing of the program and takes
nothing the program made: its weights are the benchmark's, made again from
the seed, held in the type the configuration states (bf16) and raised to
float32 layer by layer as they are used.

Equations (Yi-1.5 and Mistral-7B share them): pre-norm decoder; RMSNorm
``x * rsqrt(mean(x^2) + eps) * w``; rotary embedding in the half-rotation
(NeoX) layout at base ``rope_theta``; grouped-query causal attention scaled
by ``1/sqrt(head_dim)`` (query head ``h`` reads KV head ``h // (heads /
kv_heads)``); SwiGLU ``down(silu(gate(x)) * up(x))``; untied head.

``mode`` lowers the precision for the control: every matmul operand is
rounded to fp8 (e4m3, scaled by the tensor's largest magnitude) or bf16
before a float32 multiply.

Training follows the configuration's optimizer: AdamW on every leaf (norm
scales included), float32 moments, parameters stored in bf16 with no
float32 master copy. To fit beside 8 bytes a parameter of moments, the
backward pass goes sub-layer by sub-layer and each leaf is updated as soon
as its gradient exists.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import flops

F32 = jnp.float32
HI = lax.Precision.HIGHEST


def _round(x, mode):
    """``x`` rounded to the control's precision; the gradient passes
    straight through the rounding (a cotangent is not rounded)."""
    if mode == "f32":
        return x
    if mode == "bf16":
        r = x.astype(jnp.bfloat16).astype(F32)
    elif mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    else:
        raise ValueError(f"unknown precision mode {mode!r}")
    return x + lax.stop_gradient(r - x)


def _mm(a, b, mode):
    return jnp.matmul(_round(a.astype(F32), mode), _round(b.astype(F32), mode),
                      precision=HI)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta):
    """x [..., S, heads, D]; positions [S]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend_group(q, k, v, mode, q_block):
    """One sequence, one KV head: q [S, G, D], k v [S, D]. Query rows go in
    blocks so that the float32 scores stay small."""
    s, g, d = q.shape
    scale = 1.0 / math.sqrt(d)
    kr, vr = _round(k, mode), _round(v, mode)

    @jax.checkpoint
    def block(args):
        qb, row0 = args
        sc = jnp.einsum("sgd,td->gst", _round(qb, mode), kr,
                        precision=HI) * scale
        rows = row0 + jnp.arange(qb.shape[0])
        mask = rows[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", _round(p, mode), vr, precision=HI)

    nb = max(1, s // q_block) if s % q_block == 0 else 1
    qs = q.reshape(nb, s // nb, g, d)
    out = lax.map(block, (qs, jnp.arange(nb) * (s // nb)))
    return out.reshape(s, g, d)


def attention(q, k, v, mode, q_block=1024):
    """q [B, S, NH, D], k v [B, S, NKV, D] -> [B, S, NH, D], causal."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b * nkv, s, g, d)
    kg = k.transpose(0, 2, 1, 3).reshape(b * nkv, s, d)
    vg = v.transpose(0, 2, 1, 3).reshape(b * nkv, s, d)
    out = lax.map(lambda a: _attend_group(*a, mode=mode, q_block=q_block),
                  (qg, kg, vg))
    return out.reshape(b, nkv, s, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, s, nh, d)


def attn_sublayer(p, x, m, mode):
    """x [B, S, H] -> x + attention(norm(x))."""
    b, s, _ = x.shape
    d = flops.head_dim(m)
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


def _hashable(m):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def logits_after(weights, m, tokens, last: int, padded: int, last_max: int,
                 mode="f32"):
    """[last, vocab] next-token logits after each of the ``last`` final
    tokens of ``tokens``: row j scores what follows
    ``tokens[:len(tokens) - last + 1 + j]``. One sequence, padded on the
    right to ``padded`` (causal, so padding changes nothing before it).
    ``padded`` and ``last_max`` (at least ``last``) are the compiled shape,
    one for a whole mix."""
    n = len(tokens)
    ids = np.zeros((1, padded), np.int32)
    ids[0, :n] = tokens
    start = max(0, min(n - last, padded - last_max))
    rows = np.asarray(_logits_jit(weights, jnp.asarray(ids), np.int32(start),
                                  m_items=_hashable(m), mode=mode,
                                  last=last_max))
    first = n - last - start
    return rows[first:first + last]


# -- training: three AdamW steps, sub-layer by sub-layer -------------------------

def _adamw(p, g, mo, vo, t, hp):
    b1, b2 = hp["beta1"], hp["beta2"]
    g = g.astype(F32)
    mo = b1 * mo + (1 - b1) * g
    vo = b2 * vo + (1 - b2) * g * g
    m_hat = mo / (1 - b1 ** t)
    v_hat = vo / (1 - b2 ** t)
    p32 = p.astype(F32)
    new = p32 - hp["lr"] * (m_hat / (jnp.sqrt(v_hat) + hp["eps"])
                            + hp["weight_decay"] * p32)
    return new.astype(p.dtype), mo, vo


def _tree_adamw(p, g, mo, vo, t, hp):
    out = {k: _adamw(p[k], g[k], mo[k], vo[k], t, hp) for k in p}
    sq = {k: jnp.sum(jnp.square(g[k].astype(F32))) for k in p}
    return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()}, sq)


def train_programs(m, hp, mode):
    """The jitted pieces of one reference step, by name."""
    mi, mode_ = _hashable(m), mode
    hp_ = dict(hp)

    embed_fwd = jax.jit(
        lambda embed, ids: jnp.take(embed, ids, axis=0).astype(F32))

    def sub(fn):
        @jax.jit
        def fwd(p, x):
            return fn(p, x, dict(mi), mode_)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def bwd(p, mo, vo, x, dy, t):
            # differentiate at float32 copies, so that the gradient is
            # float32 and not rounded to the stored type
            p32 = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
            _, vjp = jax.vjp(lambda p_, x_: fn(p_, x_, dict(mi), mode_),
                             p32, x)
            dp, dx = vjp(dy)
            p, mo, vo, sq = _tree_adamw(p, dp, mo, vo, t, hp_)
            return p, mo, vo, dx, sq
        return fwd, bwd

    attn_fwd, attn_bwd = sub(attn_sublayer)
    mlp_fwd, mlp_bwd = sub(mlp_sublayer)

    @functools.partial(jax.jit, static_argnums=(4,))
    def head_bwd(norm_w, head, x, labels, rows):
        """x [N, H], labels [N]: the mean loss over the labelled rows, its
        gradients to the final norm, the head and x. Rows go in chunks, so
        that the float32 logits stay small; gradients are summed."""
        n32, w32 = norm_w.astype(F32), head.astype(F32)
        count = jnp.maximum(jnp.sum(labels != -100), 1).astype(F32)

        def loss_sum(n32, w32, xc, lc):
            y = rms_norm(xc, n32, dict(mi)["rms_norm_eps"])
            logits = _mm(y, w32, mode_)
            valid = lc != -100
            safe = jnp.where(valid, lc, 0)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, safe[..., None],
                                         axis=-1)[..., 0]
            return jnp.sum(jnp.where(valid, lse - picked, 0.0))

        def chunk(carry, xs):
            ls, gn, gh = carry
            l, (dn, dh, dx) = jax.value_and_grad(
                loss_sum, argnums=(0, 1, 2))(n32, w32, *xs)
            return (ls + l, gn + dn, gh + dh), dx

        c = x.shape[0] // rows
        init = (jnp.zeros((), F32), jnp.zeros_like(n32), jnp.zeros_like(w32))
        (ls, gn, gh), dx = lax.scan(
            chunk, init, (x.reshape(c, rows, -1), labels.reshape(c, rows)))
        return ls / count, gn / count, gh / count, \
            dx.reshape(x.shape) / count

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, mo, vo, g, t):
        return _tree_adamw(p, g, mo, vo, t, hp_)

    @functools.partial(jax.jit, static_argnums=(2,))
    def embed_grad(ids, dx, vocab):
        flat = dx.reshape(-1, dx.shape[-1])
        return jnp.zeros((vocab, dx.shape[-1]), F32).at[
            ids.reshape(-1)].add(flat)
    return {"embed_fwd": embed_fwd, "attn_fwd": attn_fwd, "attn_bwd": attn_bwd,
            "mlp_fwd": mlp_fwd, "mlp_bwd": mlp_bwd, "head_bwd": head_bwd,
            "update": update, "embed_grad": embed_grad}


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
        {leaf name: squared gradient norm}), the leaf names being the
        program's stacked ones."""
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
                    sq[k] = sq.get(k, 0.0) + float(v)
        ge = self.embed_grad(ids, dy, self.m["vocab_size"])
        e, em, ev, s_ = self.update(
            {"embed": self.top["embed"]}, {"embed": self.top_m["embed"]},
            {"embed": self.top_v["embed"]}, {"embed": ge}, t)
        self.top.update(e), self.top_m.update(em), self.top_v.update(ev)
        sq["embed"] = float(s_["embed"])
        return float(loss), sq

    def change_sq(self, initial):
        """{leaf: squared norm of (parameters now - ``initial``)}, ``initial``
        being the benchmark's tree made again from the seed."""
        sq = jax.jit(lambda a, b: jnp.sum(jnp.square(
            a.astype(F32) - b.astype(F32))))
        diff = lambda a, b: float(sq(a, b))
        out = {k: diff(self.top[k], initial[k]) for k in self.top}
        for name in self.layers[0]:
            out[name] = sum(diff(l[name], initial["layers"][name][i])
                            for i, l in enumerate(self.layers))
        return out
