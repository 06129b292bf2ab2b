"""The plain reference, the part every family shares: straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision, with no kernels,
no cache, no paging and no batching tricks. It imports nothing of the
program and takes nothing the program made: its weights are the
benchmark's, made again from the seed, held in the type the configuration
states (bf16) and raised to float32 as they are used. A family
(``families/<name>.py``) holds its layers' equations and its leaf names and
builds its forward pass and its trainer from what is here.

Here: RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; rotary embedding in the
half-rotation (NeoX) layout at base ``theta``; grouped-query causal
attention scaled by ``1/sqrt(head_dim)`` (query head ``h`` reads KV head
``h // (heads / kv_heads)``), query rows in blocks; the head and its loss
in rows; AdamW.

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


def _hashable(m):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def logits_after(forward, weights, m, tokens, last: int, padded: int,
                 last_max: int, mode="f32"):
    """[last, vocab] next-token logits after each of the ``last`` final
    tokens of ``tokens``: row j scores what follows
    ``tokens[:len(tokens) - last + 1 + j]``. One sequence, padded on the
    right to ``padded`` (causal, so padding changes nothing before it).
    ``padded`` and ``last_max`` (at least ``last``) are the compiled shape,
    one for a whole mix. ``forward`` is the family's jitted forward pass:
    ``forward(weights, ids [1, padded], start, m_items=, mode=, last=)`` gives
    the logits of ``last`` rows from ``start`` on."""
    n = len(tokens)
    ids = np.zeros((1, padded), np.int32)
    ids[0, :n] = tokens
    start = max(0, min(n - last, padded - last_max))
    rows = np.asarray(forward(weights, jnp.asarray(ids), np.int32(start),
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


def train_programs(m, hp, mode, sublayers, norm_eps):
    """The jitted pieces of one reference step, by name. ``sublayers`` is
    the family's {name: fn(p, x, m, mode)}: each gives ``<name>_fwd`` and
    ``<name>_bwd``, the backward with its leaves' AdamW update. The embedding
    and the head in rows (behind a final RMSNorm of ``norm_eps``) are every
    family's."""
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

    @functools.partial(jax.jit, static_argnums=(4,))
    def head_bwd(norm_w, head, x, labels, rows):
        """x [N, H], labels [N]: the mean loss over the labelled rows, its
        gradients to the final norm, the head and x. Rows go in chunks, so
        that the float32 logits stay small; gradients are summed."""
        n32, w32 = norm_w.astype(F32), head.astype(F32)
        count = jnp.maximum(jnp.sum(labels != -100), 1).astype(F32)

        def loss_sum(n32, w32, xc, lc):
            y = rms_norm(xc, n32, norm_eps)
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
    out = {"embed_fwd": embed_fwd, "head_bwd": head_bwd, "update": update,
           "embed_grad": embed_grad}
    for name, fn in sublayers.items():
        out[name + "_fwd"], out[name + "_bwd"] = sub(fn)
    return out
