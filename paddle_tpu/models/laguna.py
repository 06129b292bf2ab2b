"""Laguna (poolside) on the serving path: full and sliding-window attention
layers with head counts of their own, a per-head gate on the attention's
output, and an expert layer that routes each token to the top 10 of 256
experts by softmax beside a shared expert. Serving only
(``InferenceEngine``): one chip, bf16, greedy, chunked prefill and batched
decode.

Equations (pre-norm, RMSNorm, untied head; per layer ``h <- h +
Attn(RMSNorm(h))``, then ``h <- h + FFN(RMSNorm(h))``; the residual stream
is float32, as DeepSeek's: a sub-layer's input is rounded to the weights'
dtype, its last product's accumulator is added unrounded):

- Attention of layer l with ``n_l`` query heads (``heads_per_layer``) over
  ``num_key_value_heads`` KV heads of ``head_dim``: ``q = x W_q``, ``k = x
  W_k``, ``v = x W_v``; no bias, no q/k norm. A ``full_attention`` layer
  ropes the first ``full_rotary_dim`` dimensions of each head by YaRN
  (``models/deepseek.py`` ``yarn_inv_freq``: interpolated and original
  frequencies blended over the correction range of (beta_fast, beta_slow)),
  cos and sin times ``rope_attention_factor``, and leaves the rest as they
  are; a ``sliding_attention`` layer ropes the whole head at
  ``window_rope_theta``. Both in the half-rotation layout over the rotated
  dimensions. Scores ``q . k / sqrt(head_dim)``; query i sees j <= i in a
  full layer and ``i - sliding_window < j <= i`` in a window layer. Each
  head's output is scaled by its gate ``sigmoid(x W_g)`` (one scalar a
  head, read from the attention's normed input) before ``W_o``.
- FFN: a SwiGLU of ``intermediate_size`` in ``mlp_only_layers``; elsewhere
  the expert layer of ``models/deepseek.py`` ``moe_ffn`` with softmax
  routing (``route``): ``p = softmax(y W_r)`` over all
  ``n_routed_experts``, the ``num_experts_per_tok`` largest kept, their
  weights renormalised and scaled by ``routed_scaling_factor``; FFN(y) =
  sum over the kept experts HELD HERE (``expert_offset`` .. ``+
  n_local_experts``) of ``w_e E_e(y)``, plus the ungated shared expert.
  What absent experts would add is left out, as for DeepSeek-V3.

What a sequence keeps between steps is of two kinds: the full layers' KV
columns in blocks of the paged pool (Llama's layout, [Lf, NP, KVD, bs]),
and for each window layer a RING of blocks in the sequence's slot
(``init_state``: [Lw, slots, ring, KVD, bs]), the window's keys and values
and nothing older, so its size does not grow with the context
(``ops/paged_attention.py`` ``ring_blocks``). The full layers attend
through Llama's paged kernels, the window layers through the windowed
kernels over the rings; prefix sharing is refused (a ring cannot share a
prefix).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.sampling import sampled, with_board
from .deepseek import _runs, moe_ffn, rms_norm, swiglu, yarn_inv_freq
from .llama import (_chunk_window, _paged_attend_chunk, _paged_attend_rows,
                    _pin_pool_layout, _pool_write_chunk, rows_attend)

F32 = jnp.float32
FULL, WINDOW = "full_attention", "sliding_attention"
_TREE = {FULL: "full", WINDOW: "window"}    # an attention kind's subtree


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published keys of a ``laguna`` config.json that shape the model
    (the rope's two groups flattened); frozen and hashable, so it keys the
    jitted programs as it is."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288          # the dense layers' SwiGLU
    moe_intermediate_size: int = 1024       # one routed expert's
    shared_expert_intermediate_size: int = 1024
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (FULL, WINDOW, WINDOW, WINDOW)
    heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72)
    mlp_only_layers: Tuple[int, ...] = (0,)
    sliding_window: int = 512
    n_routed_experts: int = 256             # what the router scores
    num_experts_per_tok: int = 10
    routed_scaling_factor: float = 2.5
    # this chip's share of the routed experts: ids offset .. offset + local
    expert_offset: int = 0
    n_local_experts: int = 256
    scoring_func: str = "softmax"           # deepseek.route's softmax path
    rms_norm_eps: float = 1e-6
    full_rope_theta: float = 500000.0
    full_rotary_dim: int = 64               # head_dim x partial_rotary_factor
    rope_factor: float = 128.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.4852030263919618
    window_rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.layer_types) != len(self.heads_per_layer) \
                or set(self.layer_types) - {FULL, WINDOW}:
            raise ValueError("layer_types names each layer full_attention or "
                             "sliding_attention, heads_per_layer its heads")
        for kind in (FULL, WINDOW):
            n = {h for t, h in zip(self.layer_types, self.heads_per_layer)
                 if t == kind}
            if len(n) > 1 or any(h % self.num_key_value_heads for h in n):
                raise ValueError(f"the {kind} layers need one head count, "
                                 f"a multiple of the KV heads: {sorted(n)}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    def heads(self, kind: str) -> int:
        return next(h for t, h in zip(self.layer_types, self.heads_per_layer)
                    if t == kind)

    def count(self, kind: str) -> int:
        """Layers of an attention kind, or of an FFN kind (dense, moe)."""
        if kind in (FULL, WINDOW):
            return self.layer_types.count(kind)
        dense = sum(i in self.mlp_only_layers
                    for i in range(self.num_hidden_layers))
        return dense if kind == "dense" else self.num_hidden_layers - dense


def param_shapes(c: LagunaConfig) -> Dict[str, Any]:
    """The tree ``InferenceEngine`` takes: each attention kind's layers
    stacked on axis 0 under ``full`` / ``window`` (in layer order), the FFNs
    likewise under ``dense`` / ``moe``, the routed experts of every expert
    layer stacked whole (``moe_ffn`` takes the stack and a layer's slot in
    it). ``g_proj`` is the attention's per-head gate; norm scales end in
    ``norm``."""
    h, hd, kv = c.hidden_size, c.head_dim, c.num_key_value_heads
    out = {"embed": (c.vocab_size, h)}
    for kind in (FULL, WINDOW):
        n, nh = c.count(kind), c.heads(kind) if c.count(kind) else 0
        if n:
            out[_TREE[kind]] = {
                "input_norm": (n, h), "q_proj": (n, h, nh * hd),
                "k_proj": (n, h, kv * hd), "v_proj": (n, h, kv * hd),
                "g_proj": (n, h, nh), "o_proj": (n, nh * hd, h)}
    nd, nm, i = c.count("dense"), c.count("moe"), c.intermediate_size
    if nd:
        out["dense"] = {"post_norm": (nd, h), "gate_proj": (nd, h, i),
                        "up_proj": (nd, h, i), "down_proj": (nd, i, h)}
    if nm:
        e, s, el = (c.moe_intermediate_size,
                    c.shared_expert_intermediate_size, c.n_local_experts)
        out["moe"] = {
            "post_norm": (nm, h), "router": (nm, h, c.n_routed_experts),
            "shared": {"gate": (nm, h, s), "up": (nm, h, s),
                       "down": (nm, s, h)},
            "experts": {"gate": (nm, el, h, e), "up": (nm, el, h, e),
                        "down": (nm, el, e, h)}}
    out["final_norm"] = (h,)
    out["lm_head"] = (h, c.vocab_size)
    return out


def ring_size(c: LagunaConfig, chunk: int, block_size: int) -> int:
    """Blocks of a window layer's ring for prefill chunks of ``chunk``."""
    from ..ops.paged_attention import ring_blocks
    return ring_blocks(c.sliding_window, chunk, block_size)


def init_paged_kv_pool(c: LagunaConfig, num_blocks: int, block_size: int):
    """The full layers' pools, Llama's layout: k and v [Lf, num_blocks,
    KV*HD, block_size], block 0 the null block."""
    shape = (c.count(FULL), num_blocks, c.num_key_value_heads * c.head_dim,
             block_size)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


def init_rings(c: LagunaConfig, num_slots: int, ring: int, block_size: int):
    """The window layers' rings: k and v [Lw, num_slots, ring, KV*HD,
    block_size], a sequence's slot on axis 1, slot 0 the null slot."""
    shape = (c.count(WINDOW), num_slots, ring,
             c.num_key_value_heads * c.head_dim, block_size)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


# -- rope ------------------------------------------------------------------------

class _Yarn(NamedTuple):
    """What ``deepseek.yarn_inv_freq`` reads, for the full layers' rotated
    dimensions."""
    qk_rope_head_dim: int
    rope_theta: float
    rope_factor: float
    rope_original_max: int
    rope_beta_fast: float
    rope_beta_slow: float


def rope_tables(c: LagunaConfig, positions):
    """{kind: (cos, sin) [T, rotated / 2] f32} at integer ``positions`` [T]:
    YaRN over the full layers' first ``full_rotary_dim`` dimensions, cos and
    sin times the attention factor; plain rope over the window layers'
    whole head."""
    pos = positions.astype(F32)[:, None]
    inv = yarn_inv_freq(_Yarn(c.full_rotary_dim, c.full_rope_theta,
                              c.rope_factor, c.rope_original_max,
                              c.rope_beta_fast, c.rope_beta_slow))
    af = c.rope_attention_factor
    d = c.head_dim
    win = 1.0 / (c.window_rope_theta
                 ** (jnp.arange(0, d, 2, dtype=F32) / d))
    return {FULL: (jnp.cos(pos * inv) * af, jnp.sin(pos * inv) * af),
            WINDOW: (jnp.cos(pos * win), jnp.sin(pos * win))}


def rotate(x, cos, sin):
    """x [T, heads, D] with its first ``2 * cos.shape[-1]`` dimensions
    rotated (half-rotation pairs within them), the rest as they are."""
    r = 2 * cos.shape[-1]
    x32 = x.astype(F32)
    x1, x2, rest = x32[..., :r // 2], x32[..., r // 2:r], x32[..., r:]
    cs, sn = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cs - x2 * sn, x2 * cs + x1 * sn, rest],
                           -1).astype(x.dtype)


# -- the layers ------------------------------------------------------------------

def _stack_runs(c: LagunaConfig):
    """Consecutive layers alike in both attention and FFN kind, each with
    where its first layer sits in its attention stack and its FFN stack:
    [(attention kind, ffn kind, attention index, ffn index, layers)]."""
    kinds = [(t, "dense" if i in c.mlp_only_layers else "moe")
             for i, t in enumerate(c.layer_types)]
    seen = dict.fromkeys((FULL, WINDOW, "dense", "moe"), 0)
    out = []
    for (a, f), lo, hi in _runs(kinds):
        out.append((a, f, seen[a], seen[f], hi - lo))
        seen[a] += hi - lo
        seen[f] += hi - lo
    return out


def _attn(kind, p, h, rope, caches, layer, attend, c: LagunaConfig):
    """h + the gated attention of one layer over the float32 stream h [T,
    H]; ``attend(kind, q, k, v, caches, layer) -> (o [T, NH*hd], caches)``
    with q [T, NH, hd], k and v [T, NKV, hd] roped."""
    t, hd, nkv = h.shape[0], c.head_dim, c.num_key_value_heads
    x = rms_norm(h, p["input_norm"], c.rms_norm_eps).astype(c.dtype)
    cos, sin = rope[kind]
    q = rotate((x @ p["q_proj"]).reshape(t, -1, hd), cos, sin)
    k = rotate((x @ p["k_proj"]).reshape(t, nkv, hd), cos, sin)
    v = (x @ p["v_proj"]).reshape(t, nkv, hd)
    gate = jax.nn.sigmoid(jnp.dot(x, p["g_proj"], preferred_element_type=F32))
    o, caches = attend(kind, q, k, v, caches, layer)
    o = (o.reshape(t, -1, hd).astype(F32) * gate[:, :, None]).astype(c.dtype)
    return h + jnp.dot(o.reshape(t, -1), p["o_proj"],
                       preferred_element_type=F32), caches


def _layers(params, c: LagunaConfig, h, rope, caches, live, attend):
    """Every layer over the float32 residual stream h [T, H], the caches
    (full pools, then the rings as ``ring_pool`` views) riding as carries: a
    run of alike layers in one scan, each layer reading its leaves out of
    its kind's stacks. Returns (h, caches, counts [n_moe, 4] i32: the
    expert layers' ``moe_ffn`` counts in layer order)."""
    at = lambda tree, i: jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)
    experts = params["moe"]["experts"] if "moe" in params else None
    counts = []
    for a_kind, f_kind, a0, f0, n in _stack_runs(c):
        stack_a = params[_TREE[a_kind]]
        stack_f = {k: v for k, v in params[f_kind].items() if k != "experts"}

        def layer(carry, idx, a_kind=a_kind, f_kind=f_kind, stack_a=stack_a,
                  stack_f=stack_f):
            h, caches = carry
            ai, fi = idx
            h, caches = _attn(a_kind, at(stack_a, ai), h, rope, caches, ai,
                              attend, c)
            p = at(stack_f, fi)
            y = rms_norm(h, p["post_norm"], c.rms_norm_eps)
            if f_kind == "dense":
                with jax.named_scope("ffn.dense"):
                    return (h + swiglu(y.astype(c.dtype), p["gate_proj"],
                                       p["up_proj"], p["down_proj"]),
                            caches), None
            f, stats = moe_ffn(y, live, p, experts, fi, c)
            return (h + f, caches), stats

        idx = (jnp.arange(a0, a0 + n, dtype=jnp.int32),
               jnp.arange(f0, f0 + n, dtype=jnp.int32))
        (h, caches), stats = lax.scan(layer, (h, caches), idx)
        if f_kind == "moe":
            counts.append(stats)
    return h, caches, jnp.concatenate(counts)


def _logits(params, h, c: LagunaConfig):
    with jax.named_scope("lm_head"):
        x = rms_norm(h, params["final_norm"], c.rms_norm_eps).astype(c.dtype)
        return jnp.dot(x, params["lm_head"], preferred_element_type=F32)


def _attend_counts(c: LagunaConfig, reach):
    """[2] i32 after the experts' counts: the keys the window layers and the
    full layers attended, summed over rows and layers, from ``reach`` [R]
    (positions at or before each live row, 0 for padding)."""
    return jnp.stack([
        c.count(WINDOW) * jnp.sum(jnp.minimum(reach, c.sliding_window)),
        c.count(FULL) * jnp.sum(reach)]).astype(jnp.int32)


def _caches(pools, rings):
    from ..ops.paged_attention import ring_pool
    return (*pools, *(ring_pool(r) for r in rings))


def _walks(pools, rings, tables, positions, slots, c: LagunaConfig):
    """The decode batch's walks, made once before the layers: the full
    layers' over their tables, bounded by the pool's blocks (no table
    shares a block: the prefix cache is refused), and the window layers'
    over the rings."""
    from ..ops.paged_attention import paged_update_walk, window_update_walk
    b, max_nb = tables.shape
    n_blocks, bs = pools[0].shape[1], pools[0].shape[-1]
    return (paged_update_walk(tables, positions, bs,
                              min(b * max_nb, n_blocks - 1 + b)),
            window_update_walk(slots, positions, rings[0].shape[2], bs,
                               c.sliding_window))


def _rows_attend(walks, c: LagunaConfig):
    """The decode batch's ``attend``: a full layer through Llama's fused
    paged update, a window layer through the windowed one over the rings."""
    from ..ops.paged_attention import window_attend_update
    walk_full, walk_window = walks

    def attend(kind, q, k, v, caches, layer):
        if kind == FULL:
            with jax.named_scope("attn.full"):
                o, pools = _paged_attend_rows(q, k, v, caches[:2], walk_full,
                                              layer, c)
            return o, pools + caches[2:]
        kr, vr = caches[2:]
        with jax.named_scope("attn.window"):
            o, rings = rows_attend(q, k, v, lambda qs, k, v: (
                window_attend_update(qs, k.astype(kr.dtype),
                                     v.astype(vr.dtype), kr, vr, walk_window,
                                     layer, c.sliding_window)), c.dtype)
        return o, caches[:2] + rings
    return attend


def _chunk_attend(table_row, start, n_live, slot, ring, where,
                  c: LagunaConfig):
    """A prefill chunk's ``attend``: a full layer writes the chunk's columns
    into the sequence's blocks and attends through Llama's chunk kernel; a
    window layer writes them into the slot's ring and attends through the
    windowed chunk kernel."""
    from ..ops.paged_attention import window_prefill_attention
    where_full, (wbid, fresh, window) = where

    def attend(kind, q, k, v, caches, layer):
        if kind == FULL:
            with jax.named_scope("attn.full"):
                o, pools = _paged_attend_chunk(q, k, v, caches[:2], table_row,
                                               start, n_live, layer,
                                               where_full)
            return o, pools + caches[2:]
        n = k.shape[0]
        with jax.named_scope("attn.window"):
            rings = tuple(
                _pool_write_chunk(_pin_pool_layout(r), layer, wbid, fresh,
                                  window(col.reshape(n, -1).astype(r.dtype)))
                for r, col in zip(caches[2:], (k, v)))
            o = window_prefill_attention(q, *rings, slot, ring, start, n_live,
                                         layer, c.sliding_window)
        return o.reshape(n, -1), caches[:2] + rings
    return attend


def _chunk_where(table_row, start, n_live, slot, ring, n, bs):
    """Where a chunk's columns land: the full layers' blocks and the
    window layers' ring blocks (``_chunk_window`` of each table)."""
    from ..ops.paged_attention import ring_row
    return (_chunk_window(table_row, start, n_live, n, bs),
            _chunk_window(ring_row(slot, ring, table_row.shape[0]), start,
                          n_live, n, bs))


def _out(logits, caches, rings, counts):
    """(logits..., *full pools, *rings in their own shape, *counts)."""
    return (*logits, *caches[:2],
            *(r.reshape(s.shape) for r, s in zip(caches[2:], rings)), *counts)


def laguna_paged_decode_step(params, pools, rings, tables, positions, ids,
                             slots, c: LagunaConfig):
    """One token for every row of a decode batch: ``pools`` the full
    layers' (k, v), ``rings`` the window layers' (``init_rings``), tables
    [B, max_nb], positions [B] (the new token's), ids [B], slots [B] (each
    row's ring; padding rows at block 0, position 0, slot 0). Returns
    (logits [B, vocab] f32, *pools, *rings, expert counts [n_moe, 4],
    attended keys [2])."""
    h = jnp.take(params["embed"], ids, axis=0).astype(F32)
    live = tables[:, 0] > 0
    walks = _walks(pools, rings, tables, positions, slots, c)
    h, caches, counts = _layers(params, c, h, rope_tables(c, positions),
                                _caches(pools, rings), live,
                                _rows_attend(walks, c))
    reach = jnp.where(live, positions + 1, 0)
    return _out((_logits(params, h, c),), caches, rings,
                (counts, _attend_counts(c, reach)))


def laguna_paged_prefill_chunk(params, pools, rings, table_row, start, ids,
                               n_live, slot, c: LagunaConfig):
    """One chunk of one prompt: table_row [max_nb], ``start`` tokens
    cached, ids [C] padded, ``n_live`` real, ``slot`` the sequence's ring.
    Returns (the last live token's logits [vocab] f32, *pools, *rings,
    counts, attended keys)."""
    n = ids.shape[0]
    bs, ring = pools[0].shape[-1], rings[0].shape[2]
    pos = start + jnp.arange(n, dtype=jnp.int32)
    live = jnp.arange(n) < n_live
    h = jnp.take(params["embed"], ids, axis=0).astype(F32)
    where = _chunk_where(table_row, start, n_live, slot, ring, n, bs)
    h, caches, counts = _layers(
        params, c, h, rope_tables(c, pos), _caches(pools, rings), live,
        _chunk_attend(table_row, start, n_live, slot, ring, where, c))
    last = lax.dynamic_slice_in_dim(h, n_live - 1, 1, 0)
    return _out((_logits(params, last, c)[0],), caches, rings,
                (counts, _attend_counts(c, jnp.where(live, pos + 1, 0))))


def laguna_paged_prefill_chunk_with_decode(
        params, pools, rings, table_row, start, ids, n_live, tables,
        positions, row_ids, slot, slots, c: LagunaConfig):
    """A prefill chunk with the decode batch riding it: ONE pass of the
    layers over the chunk's C rows and the batch's R rows (inputs as the two
    steps above take them: the chunk's, then the batch's, then the chunk's
    slot and the rows' slots), so the weights stream once. Between the
    projections and the gate the rows part, each to its own attention (the
    batch's fused updates first, so that the chunk's attention is the
    caches' last reader in a layer). Returns (the chunk's logits [vocab],
    the batch's [R, vocab], *pools, *rings, counts, attended keys)."""
    n = ids.shape[0]
    bs, ring = pools[0].shape[-1], rings[0].shape[2]
    chunk_pos = start + jnp.arange(n, dtype=jnp.int32)
    pos = jnp.concatenate([chunk_pos, positions])
    live = jnp.concatenate([jnp.arange(n) < n_live, tables[:, 0] > 0])
    h = jnp.take(params["embed"], jnp.concatenate([ids, row_ids]),
                 axis=0).astype(F32)
    rows = _rows_attend(_walks(pools, rings, tables, positions, slots, c),
                        c)
    chunk = _chunk_attend(table_row, start, n_live, slot, ring,
                          _chunk_where(table_row, start, n_live, slot, ring,
                                       n, bs), c)

    def attend(kind, q, k, v, caches, layer):
        o_rows, caches = rows(kind, q[n:], k[n:], v[n:], caches, layer)
        o_chunk, caches = chunk(kind, q[:n], k[:n], v[:n], caches, layer)
        return jnp.concatenate([o_chunk, o_rows]), caches

    h, caches, counts = _layers(params, c, h, rope_tables(c, pos),
                                _caches(pools, rings), live, attend)
    heads = jnp.concatenate([lax.dynamic_slice_in_dim(h, n_live - 1, 1, 0),
                             h[n:]])
    logits = _logits(params, heads, c)
    return _out((logits[0], logits[1:]), caches, rings,
                (counts, _attend_counts(c, jnp.where(live, pos + 1, 0))))


# kind -> (the step, the jitted program's name: what the benchmark's metrics
# match in a device trace; the chunk that carries the batch goes by the
# chunk's name first, so what reads ``paged_prefill_chunk_laguna`` reads
# both; where the rows' tokens sit among the inputs after the caches: what
# the serving program's board replaces, ``ops/sampling.py`` ``with_board``)
_PAGED_STEPS = {
    "prefill": (laguna_paged_prefill_chunk, "paged_prefill_chunk_laguna",
                None),
    "decode": (laguna_paged_decode_step, "paged_decode_step_laguna", 2),
    "prefill+decode": (laguna_paged_prefill_chunk_with_decode,
                       "paged_prefill_chunk_laguna_with_decode", 6),
}
_N_POOLS, _N_RINGS = 2, 2


@functools.lru_cache(maxsize=24)
def _jitted_paged_step(kind: str, c: LagunaConfig, board: bool = False):
    """The jitted program of ``kind``: ``fn(params, *pools, *rings,
    *inputs) -> (token, finite, *pools, *rings, counts, attended)`` with
    both caches donated and the greedy head (``ops/sampling.py``) on the
    step's logits (``prefill+decode``: the chunk's pair, then the rows').
    With ``board`` the serving program the engine launches (``with_board``).
    """
    step, name, toks_at = _PAGED_STEPS[kind]
    n = _N_POOLS + _N_RINGS

    def fn(params, *args):
        return sampled(step(params, args[:_N_POOLS], args[_N_POOLS:n],
                            *args[n:], c), len(kind.split("+")))
    if board:
        fn = with_board(kind, fn, n, toks_at)
    fn.__name__ = name
    return jax.jit(fn, donate_argnums=tuple(range(1, 1 + n)))


class LagunaServing:
    """What ``InferenceEngine`` asks of a model (``llama.LlamaServing``
    states the contract), for a model with window layers: beside the full
    layers' block-indexed pools of ``init_cache`` it answers ``init_state``
    (the window layers' rings, indexed by SLOT on axis 1, slot 0 reserved),
    and its programs take the rings after the pools and the rows' slots as
    their last inputs, as ``falcon_h1.FalconH1Serving``'s take their state:
    ``fn(params, *pools, *rings, <inputs>, slots) -> (*heads, *pools,
    *rings, counts, attended)``."""

    # span argument -> registry counter (paddle_tpu_serve_<name>)
    work = {"pairs": "moe_pairs_total",
            "local_pairs": "moe_local_pairs_total",
            "experts_hit": "moe_expert_hits_total",
            "busiest_rows": "moe_busiest_rows_total",
            "window_attend": "window_attend_tokens_total",
            "full_attend": "full_attend_tokens_total"}

    # registry gauge -> (help, read from the engine)
    gauges = {"window_ring_bytes": (
        "bytes of the window layers' rings held by running sequences",
        lambda eng: eng.slots.used_slots
        * sum(a.nbytes for a in eng.state) // eng.slots.num_slots)}

    @staticmethod
    def refuse(*, mp, kv_dtype, speculative, draft, prefix_cache) -> None:
        """Out of scope, refused rather than half-done (ROADMAP.md M4 has
        each)."""
        for on, what in (
                (mp > 1, "ServeConfig.mp > 1 (the rings and the windowed "
                         "kernels are not sharded)"),
                (kv_dtype != "auto", "kv_dtype='int8' (no int8 rings)"),
                (speculative or draft,
                 "speculative decoding and a draft model"),
                (prefix_cache, "prefix_cache (a ring cannot share a "
                               "prefix)")):
            if on:
                raise NotImplementedError(
                    f"Laguna serving does not support {what}")

    @staticmethod
    def freeze(config: LagunaConfig) -> LagunaConfig:
        return config           # frozen and hashable as it is

    @staticmethod
    def prepare(params, config):
        return params           # the programs read the leaves as stored

    @staticmethod
    def init_cache(config, num_blocks, block_size, kv_dtype):
        return init_paged_kv_pool(config, num_blocks, block_size)

    @staticmethod
    def init_state(config, num_slots, serve):
        return init_rings(config, num_slots,
                          ring_size(config, serve.prefill_chunk,
                                    serve.block_size), serve.block_size)

    @staticmethod
    def step_fn(kind, frozen, quant, mesh, board=True):
        return _jitted_paged_step(kind, frozen, board) \
            if kind in _PAGED_STEPS else None

    @staticmethod
    def counted(kind, counts, *ctx):
        """The span arguments of one step: the expert layers' counts summed
        (``DeepSeekServing.counted``'s four) and the keys each attention
        kind attended."""
        moe, keys = (np.asarray(x) for x in counts)  # noqa: PTA006 -- read inside the wait the step's tokens already pay
        pairs, local, hit, busiest = (int(x) for x in moe.sum(axis=0))
        return {"pairs": pairs, "local_pairs": local, "experts_hit": hit,
                "busiest_rows": busiest, "window_attend": int(keys[0]),
                "full_attend": int(keys[1])}
