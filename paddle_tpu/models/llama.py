"""Llama model family — the flagship (BASELINE configs 3 & 4).

Ref: the reference trains Llama-2 via paddle.distributed.fleet HybridParallel
(ColumnParallelLinear/RowParallelLinear TP, PipelineLayer 1F1B, GroupSharded
ZeRO) + fused CUDA kernels (fused_rope, flash_attn, fused_rms_norm).

TPU-native architecture (not a translation):
- a PURE functional core: params are a pytree with every decoder layer
  STACKED on a leading axis, the depth loop is lax.scan (one compiled layer
  body), attention is the Pallas flash kernel, norms the fused RMSNorm,
  RoPE the fused rotary op. Remat per layer.
- parallelism is declarative: ParallelConfig(dp, mp, pp, sharding/fsdp, sep)
  maps to PartitionSpecs over the fleet mesh. TP/FSDP/DP via GSPMD param and
  activation specs; sep>1 switches attention to ring attention (KV rotation
  over ICI inside shard_map); pp>1 wraps the stage scan in the collective
  pipeline (shard_map over 'pp' + ppermute, see parallel/pipeline.py).
- the Layer-based eager API (LlamaForCausalLM) wraps the same functional
  core for dygraph-style use and weight interchange.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import trace as _obs
from ..ops.flash_attention import flash_attention_bshd
from ..ops.rms_norm import fused_rms_norm
from ..ops.rope import apply_rope, build_rope_cache
from ..ops.sampling import greedy_head, sampled, with_board


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def llama_7b():
    return LlamaConfig()


def llama_13b():
    return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                       num_hidden_layers=40, num_attention_heads=40,
                       num_key_value_heads=40)


def llama_tiny(vocab=256, hidden=64, layers=4, heads=4, kv_heads=2, inter=128,
               seq=128):
    return LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                       intermediate_size=inter, num_hidden_layers=layers,
                       num_attention_heads=heads, num_key_value_heads=kv_heads,
                       max_position_embeddings=seq, dtype=jnp.float32)


@dataclasses.dataclass
class ParallelConfig:
    dp: int = 1
    mp: int = 1
    pp: int = 1
    sharding: int = 1   # ZeRO/FSDP degree over the 'sharding' axis
    sep: int = 1        # context parallel (ring or ulysses attention)
    # context-parallel strategy: 'ring' (KV rotation) or 'ulysses'
    # (all-to-all heads<->sequence; needs num_heads % sep == 0).
    # None = follow PADDLE_TPU_SEP_STRATEGY (default 'ring').
    sep_strategy: Optional[str] = None
    microbatches: int = 1
    remat: bool = True
    # 'full' recomputes the whole block; 'dots' saves matmul outputs and
    # recomputes only cheap elementwise ops (jax checkpoint_policies) —
    # trades a little memory for most of the recompute FLOPs back.
    remat_policy: str = "full"
    # lax.scan unroll over the layer stack: >1 amortizes while-loop step
    # overhead (checkpoint granularity stays per-layer)
    scan_unroll: int = 1
    zero_stage: int = 3  # what 'sharding' shards: 1=os, 2=os+g, 3=os+g+p
    use_flash: Optional[bool] = None  # None = auto (TPU yes, CPU no)
    # async pp p2p: each activation ppermute overlaps the next tick's stage
    # compute (one extra skew tick per stage). None = PADDLE_TPU_PP_OVERLAP.
    overlap_p2p: Optional[bool] = None

    @property
    def total(self):
        return self.dp * self.mp * self.pp * self.sharding * self.sep


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def init_llama_params(config: LlamaConfig, seed: int = 0) -> Dict[str, Any]:
    """Params with per-layer leaves stacked on axis 0 (length = num layers)."""
    c = config
    k = jax.random.PRNGKey(seed)
    keys = jax.random.split(k, 10)
    d = c.dtype
    h, kv = c.num_attention_heads, c.num_key_value_heads
    hd = c.head_dim
    std = 0.02

    def norm_init(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(d)

    L = c.num_hidden_layers
    layers = {
        "input_norm": jnp.ones((L, c.hidden_size), d),
        "q_proj": norm_init(keys[1], (L, c.hidden_size, h * hd)),
        "k_proj": norm_init(keys[2], (L, c.hidden_size, kv * hd)),
        "v_proj": norm_init(keys[3], (L, c.hidden_size, kv * hd)),
        "o_proj": norm_init(keys[4], (L, h * hd, c.hidden_size)),
        "post_norm": jnp.ones((L, c.hidden_size), d),
        "gate_proj": norm_init(keys[5], (L, c.hidden_size, c.intermediate_size)),
        "up_proj": norm_init(keys[6], (L, c.hidden_size, c.intermediate_size)),
        "down_proj": norm_init(keys[7], (L, c.intermediate_size, c.hidden_size)),
    }
    params = {
        "embed": norm_init(keys[0], (c.vocab_size, c.hidden_size)),
        "layers": layers,
        "final_norm": jnp.ones((c.hidden_size,), d),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = norm_init(keys[8], (c.hidden_size, c.vocab_size))
    return params


def param_pspecs(config: LlamaConfig, parallel: ParallelConfig) -> Dict[str, Any]:
    """PartitionSpecs mirroring the reference's fleet sharding:
    column-parallel out-dim over 'mp', row-parallel in-dim over 'mp',
    FSDP shards a remaining big dim over 'sharding' (ZeRO-3)."""
    fs = "sharding" if (parallel.sharding > 1 and parallel.zero_stage >= 3) else None
    mp = "mp" if parallel.mp > 1 else None

    layers = {
        "input_norm": P(None, None),
        "q_proj": P(None, fs, mp),
        "k_proj": P(None, fs, mp),
        "v_proj": P(None, fs, mp),
        "o_proj": P(None, mp, fs),
        "post_norm": P(None, None),
        "gate_proj": P(None, fs, mp),
        "up_proj": P(None, fs, mp),
        "down_proj": P(None, mp, fs),
    }
    specs = {
        "embed": P(mp, fs),
        "layers": layers,
        "final_norm": P(None),
    }
    if not config.tie_word_embeddings:
        specs["lm_head"] = P(fs, mp)
    return specs


def opt_state_pspecs(config, parallel, pspec_tree):
    """ZeRO stage 1/2: optimizer states shard over 'sharding' even when the
    params don't. Stage >=3 states follow the (already sharded) param specs."""
    if parallel.sharding > 1 and parallel.zero_stage < 3:
        def shard_state(spec):
            parts = list(spec) if len(spec) else []
            for i, p_ in enumerate(parts):
                if p_ is None:
                    parts[i] = "sharding"
                    return P(*parts)
            return spec
        return jax.tree_util.tree_map(shard_state, pspec_tree,
                                      is_leaf=lambda x: isinstance(x, P))
    return pspec_tree


# ---------------------------------------------------------------------------
# functional forward
# ---------------------------------------------------------------------------

def _act_spec(parallel):
    # activations [B, S, H]: batch over dp(+sharding for ZeRO grads), seq over sep
    batch_axes = ("dp",) if parallel.sharding == 1 else ("dp", "sharding")
    seq_axis = "sep" if parallel.sep > 1 else None
    return P(batch_axes, seq_axis, None)


def _maybe_hint(x, mesh, spec):
    if mesh is None:
        return x
    return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))



def _mat(x, w):
    """x @ w for plain weights, weight-only int8 ({'w': int8 [..., in,
    out], 's': [..., out] scales}), or the decode-transposed form (key
    'wT': [..., out, in], optional 's'; see _decode_weights). The int8->bf16
    convert fuses into the matmul's operand read (measured 1.97x on a
    decode-shaped matvec), so quantized weights stream at half the
    bytes — see quantize_llama_int8."""
    if isinstance(w, dict):
        if "wT" in w:
            r = jnp.einsum("...i,oi->...o", x, w["wT"].astype(x.dtype))
            return r * w["s"].astype(x.dtype) if "s" in w else r
        return (x @ w["w"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _mat_out_dim(w):
    if isinstance(w, dict):
        if "wT" in w:
            return w["wT"].shape[-2]
        return w["w"].shape[-1]
    return w.shape[-1]


_QKV = ("q_proj", "k_proj", "v_proj")


def _qkv_decode_form(qkv, ratio):
    """The q/k/v stacks ``{"q_proj", "k_proj", "v_proj"}`` (plain [L, in,
    out], int8 ``{"w", "s"}`` or already ``{"wT"[, "s"]}``) laid out as the
    decode matmuls read them: ``{"qkv_proj": {"wT": [L, (nh + 2 nkv) hd,
    in][, "s"]}}``, or the three as ``{"wT"[, "s"]}`` where the shapes do
    not split by the config's head ``ratio`` (nh // nkv)."""
    q_out = _mat_out_dim(qkv["q_proj"])
    k_out = _mat_out_dim(qkv["k_proj"])
    # the fused split site (_paged_qkv, llama_decode_step) re-derives
    # nh/nkv from config, so only fuse when the actual weight shapes agree
    # with the config's head ratio — mismatched params (e.g. pruned heads)
    # keep the unfused three-matmul path instead of silently mis-splitting
    if q_out != k_out * ratio or k_out != _mat_out_dim(qkv["v_proj"]):
        out = {}
        for name, w in qkv.items():
            if isinstance(w, dict):
                out[name] = w if "wT" in w else {
                    "wT": jnp.swapaxes(w["w"], -1, -2), "s": w["s"]}
            else:
                out[name] = {"wT": jnp.swapaxes(w, -1, -2)}
        return out
    ws = [qkv[n] for n in _QKV]
    if isinstance(ws[0], dict):
        return {"qkv_proj": {
            "wT": jnp.concatenate(
                [jnp.swapaxes(w["w"], -1, -2) for w in ws], axis=-2),
            "s": jnp.concatenate([w["s"] for w in ws], axis=-1)}}
    return {"qkv_proj": {"wT": jnp.concatenate(
        [jnp.swapaxes(w, -1, -2) for w in ws], axis=-2)}}


# one jitted relayout, no donation: the caller may still hold the stacks
_relay_qkv = jax.jit(_qkv_decode_form, static_argnums=(1,))


def _decode_weights(params, config):
    """Transpose the stacked q/k/v projections to [L, out, in] ONCE per
    generate call (outside the token scan). XLA's chosen operand layout
    for the [B, H] @ W decode matmuls is in-dim-minor; slicing the
    natural [L, in, out] stack per layer forced a 2 MB relayout copy per
    projection per layer EVERY token step (profiled ~0.2 ms/step at hd64
    b8 — constant_dynamic-slice fusions with transposed output layout).
    The transposed stack slices straight into the wanted layout; the
    one-time transpose cost amortizes over the whole continuation.
    ``InferenceEngine`` applies the same form itself, once, when it takes
    the weights (``LlamaServing.prepare``).

    HBM note (advisor r4): when this runs INSIDE a generate/sample jit
    the raw q/k/v stacks remain live as jit inputs while the fused copy
    exists, so decode holds ~2x the qkv projection bytes (GB-scale at
    7B+). Callers decoding repeatedly should pre-prepare once with
    prepare_decode_params (donating the raw stacks) instead."""
    layers = dict(params["layers"])
    if "qkv_proj" in layers:
        return params  # already prepared
    ratio = config.num_attention_heads // config.num_key_value_heads
    layers.update(_qkv_decode_form({n: layers.pop(n) for n in _QKV}, ratio))
    out = dict(params)
    out["layers"] = layers
    return out


def _in_decode_form(layers) -> bool:
    """Whether a layer tree is in ``_decode_weights``' form already: the
    fused ``qkv_proj``, or the unfused ``wT`` leaves that shape-mismatched
    (e.g. pruned-head) params take."""
    return "qkv_proj" in layers or (isinstance(layers.get("q_proj"), dict)
                                    and "wT" in layers["q_proj"])


def prepare_decode_params(params, config):
    """Pre-fuse/transpose the q/k/v projection stacks for decode ONCE,
    outside any generate call, DONATING the raw stacks. generate_scan/
    sample_scan re-derive the fused copy internally when handed raw
    training-layout params, and since the raw stacks stay live as jit
    inputs, decode then holds ~2x the qkv projection bytes in HBM
    (advisor r4). After ``params = prepare_decode_params(params, cfg)``
    only the fused copy is resident (pass-through weights alias via
    donation), and every subsequent generate call skips the re-derive.
    ``InferenceEngine`` relays a training-layout tree itself and, since the
    caller keeps the raw tree, holds the q/k/v stacks twice; handing it a
    tree prepared here avoids that copy. Idempotent: prepared params return
    unchanged (both the fused qkv_proj form and the unfused wT form that
    shape-mismatched — e.g. pruned-head — params take)."""
    if _in_decode_form(params["layers"]):
        return params
    fn = jax.jit(lambda p: _decode_weights(p, config), donate_argnums=(0,))
    return fn(params)


def quantize_llama_int8(params):
    """Weight-only int8 quantization for serving (ref: the reference's
    weight-only path in paddle.quantization + its int8 fused kernels).

    Matmul weights become {'w': int8, 's': per-output-channel bf16 scale}
    (symmetric, per (layer, out) channel for the stacked layer weights);
    the embedding (row gather, never streamed) and norms keep their float
    dtype. Decode is weight-stream-bound, so halving the bytes roughly
    doubles decode throughput — BELOW the bf16 weight floor, which is the
    point. Training/prefill accuracy paths should keep the float params."""
    names = {"q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
             "up_proj", "down_proj"}

    def quant(w):
        from ..nn.quant import absmax_intq
        wi, sc = absmax_intq(w, axis=-2)
        return {"w": wi, "s": jnp.squeeze(sc, -2).astype(w.dtype)}

    out = dict(params)
    out["layers"] = {k: (quant(v) if k in names else v)
                     for k, v in params["layers"].items()}
    if "lm_head" in params:
        out["lm_head"] = quant(params["lm_head"])
    return out


def _per_shard(fn, mesh, in_specs, out_specs):
    """``fn`` as a fully-manual shard_map island over ``mesh`` (``fn``
    itself where there is no mesh). Mosaic kernels cannot be partitioned
    by GSPMD ("Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map": the TPU lowering refuses the whole
    step), so on the GSPMD path (dp / mp / sharding; the sep and pp paths
    already run inside an island) every Pallas-backed op runs per shard,
    under the specs GSPMD gives its operands anyway."""
    if mesh is None:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _rms_norm_on(mesh, parallel, eps):
    """fused_rms_norm over [B, S, H] activations, per shard on a mesh."""
    norm = lambda x, w: fused_rms_norm(x, w, eps)  # noqa: E731
    if mesh is None:
        return norm
    act = _act_spec(parallel)
    return _per_shard(norm, mesh, (act, P(None)), act)


def decoder_layer(p, h_in, cos, sin, config: LlamaConfig,
                  parallel: ParallelConfig, mesh=None, use_flash=True,
                  in_shard_map=False, tp_axis=None):
    """One decoder block. h_in: [B, S, H].

    tp_axis: when set (inside a manual shard_map region) weights arrive
    mp-SLICED and this runs the explicit Megatron pattern — local head slice
    compute + lax.psum after the row-parallel matmuls (o_proj, down_proj);
    when None, GSPMD derives the same collectives from param shardings.
    """
    from jax.ad_checkpoint import checkpoint_name as _ckpt_name
    c = config
    b, s, _ = h_in.shape
    hd = c.head_dim
    nh = _mat_out_dim(p["q_proj"]) // hd  # local head count (sliced under TP)
    nkv = _mat_out_dim(p["k_proj"]) // hd

    rms_norm = _rms_norm_on(mesh, parallel, c.rms_norm_eps)
    # jax.named_scope boundaries (measurement-only): the scope names land
    # in the lowered ops' metadata, so device traces and merge_device_trace
    # can attribute kernel time back to step components by name.
    with jax.named_scope("decoder.qkv"):
        x = rms_norm(h_in, p["input_norm"])
        q = _mat(x, p["q_proj"]).reshape(b, s, nh, hd)
        k = _mat(x, p["k_proj"]).reshape(b, s, nkv, hd)
        v = _mat(x, p["v_proj"]).reshape(b, s, nkv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    with jax.named_scope("decoder.attn"):
        if parallel.sep > 1 and in_shard_map:
            from ..parallel.ring_attention import ring_attention
            from ..parallel.ulysses_attention import (resolve_sep_strategy,
                                                      ulysses_attention)
            if resolve_sep_strategy(parallel.sep_strategy) == "ulysses":
                if use_flash:
                    attn = ulysses_attention(q, k, v, axis_name="sep",
                                             causal=True)
                else:
                    from ..nn.functional.attention import _xla_sdpa
                    attn = ulysses_attention(
                        q, k, v, axis_name="sep", causal=True,
                        attn_fn=lambda qg, kg, vg: _xla_sdpa(
                            qg, kg, vg, is_causal=True))
            else:
                attn = ring_attention(q, k, v, axis_name="sep", causal=True,
                                      impl="flash" if use_flash else "xla")
        elif use_flash:
            # per shard: batch over the data axes, heads over 'mp' (the
            # column-parallel q/k/v projections leave them so)
            act = _act_spec(parallel)
            heads = P(act[0], act[1], "mp" if parallel.mp > 1 else None,
                      None)
            if mesh is not None and nkv % parallel.mp:
                # too few kv heads to split: repeat them up to the q
                # heads first (the kernel's own GQA handling, hoisted)
                k = jnp.repeat(k, nh // nkv, axis=2)
                v = jnp.repeat(v, nh // nkv, axis=2)
            attn = _per_shard(
                lambda q, k, v: flash_attention_bshd(q, k, v, causal=True),
                mesh, (heads, heads, heads), heads)(q, k, v)
        else:
            from ..nn.functional.attention import _xla_sdpa
            attn = _xla_sdpa(q, k, v, is_causal=True)
        attn = attn.reshape(b, s, nh * hd)
        # named so the 'save_attn' remat policy can keep it (skips
        # recomputing the flash kernel in backward at the cost of one
        # [B,S,H*D] residual)
        attn = _ckpt_name(attn, "attn_out")
        attn_out = _mat(attn, p["o_proj"])
        if tp_axis is not None:
            attn_out = lax.psum(attn_out, tp_axis)
    h = h_in + _maybe_hint(attn_out, mesh, _act_spec(parallel))

    with jax.named_scope("decoder.ffn"):
        x = rms_norm(h, p["post_norm"])
        mlp_out = _fused_ffn_overlap(x, p, parallel, mesh, tp_axis)
        if mlp_out is None:
            # named so 'save_mlp' can keep the gate/up matmul outputs across
            # the remat boundary — gate+up are HALF the forward matmul
            # FLOPs, so saving them halves the backward recompute at the
            # cost of two [B, S, I] residuals per layer
            g = _ckpt_name(_mat(x, p["gate_proj"]), "mlp_gate")
            u = _ckpt_name(_mat(x, p["up_proj"]), "mlp_up")
            gated = jax.nn.silu(g) * u
            mlp_out = _mat(gated, p["down_proj"])
            if tp_axis is not None:
                mlp_out = lax.psum(mlp_out, tp_axis)
    out = h + _maybe_hint(mlp_out, mesh, _act_spec(parallel))
    return out


def _fused_ffn_overlap(x, p, parallel, mesh, tp_axis):
    """gate/up -> silu-mul -> down inside ONE ring island (the [B, S, I]
    activation never leaves the mp shard; the only collective is the down
    matmul's chunked reduce ring). None -> run the GSPMD path: overlap off,
    manual-TP region (weights arrive pre-sliced), sep sharding on the seq
    dim, 'save_mlp' remat (the island hides the gate/up checkpoint names),
    int8 weights, or shapes that don't divide the ring."""
    from ..parallel import collective_matmul as cm
    if (tp_axis is not None or mesh is None or parallel.mp <= 1
            or parallel.sep > 1 or parallel.remat_policy == "save_mlp"
            or not cm.overlap_enabled()
            or any(isinstance(p[k], dict)
                   for k in ("gate_proj", "up_proj", "down_proj"))):
        return None
    plan = cm.plan_fused_ffn(
        tuple(x.shape), tuple(p["gate_proj"].shape),
        tuple(p["down_proj"].shape), mesh, n_cols=2, activation=cm.swiglu,
        batch_axis=_act_spec(parallel)[0])
    if plan is None:
        return None
    return plan(x, (p["gate_proj"], p["up_proj"]), p["down_proj"])


def _remat_policy(parallel):
    """Resolve ParallelConfig.remat_policy to a jax checkpoint policy."""
    if parallel.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if parallel.remat_policy == "save_attn":
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    if parallel.remat_policy == "save_mlp":
        # attn output + gate/up matmul outputs: backward recomputes only
        # the cheap elementwise/norm chain plus qkv/o (19% of fwd FLOPs)
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "mlp_gate", "mlp_up")
    if parallel.remat_policy == "full":
        return None
    if parallel.remat_policy == "offload_attn":
        # keep flash outputs across the remat boundary but park them in
        # host RAM instead of HBM: frees activation memory for larger
        # batch/depth at big hidden sizes (the v5e HBM ceiling binds
        # before compute does at 7B-layer geometry)
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["attn_out"],
            offload_src="device", offload_dst="pinned_host")
    raise ValueError(
        f"unknown remat_policy {parallel.remat_policy!r}; "
        "expected 'full', 'dots', 'save_attn', 'save_mlp', or "
        "'offload_attn'")


def vocab_parallel_embed(embed, ids, config, parallel, mesh=None,
                         force_matmul=False):
    """Embedding lookup that PARTITIONS when the table is vocab-sharded.

    A plain jnp.take over an embed table sharded P('mp', ...) is a gather
    GSPMD cannot partition: the compiler emits "Involuntary full
    rematerialization" and all-gathers the whole [V, H] table every step
    (seen in the round-4 multichip dryrun). This is exactly what the
    reference's VocabParallelEmbedding avoids (ref: fleet/meta_parallel/
    parallel_layers/mp_layers.py): each mp shard looks up only ids that
    land in its vocab slice (masked local gather) and the partial rows
    are summed over 'mp' — every (b, s) row is non-zero on exactly one
    shard, so the psum is exact in any dtype. Implemented as a partial-
    manual shard_map over 'mp' alone; dp/sharding/sep stay auto."""
    c = config
    mp_sharded = (mesh is not None and parallel.mp > 1
                  and "mp" in mesh.axis_names)
    if not (mp_sharded or force_matmul):
        return jnp.take(embed, ids, axis=0).astype(c.dtype)
    # One-hot matmul: the lookup becomes [B,S,V] @ [V,H] with the vocab
    # dim CONTRACTED — GSPMD partitions it over 'mp' as local partial
    # products + psum (each shard multiplies only its vocab slice:
    # numerically the reference's masked-local-lookup + allreduce), and
    # the hidden dim over 'sharding' falls out of normal matmul
    # partitioning. The backward is the transposed matmul — equally
    # partition-friendly, unlike take's scatter-add whose cotangent
    # resharding was r4's second involuntary-remat warning. XLA fuses
    # the iota/compare one-hot into the dot's operand read, so the
    # [B,S,V] operand never materializes in HBM.
    oh = jax.nn.one_hot(ids, embed.shape[0], dtype=embed.dtype)
    return jnp.einsum("bsv,vh->bsh", oh, embed).astype(c.dtype)


def llama_hidden(params, ids, config, parallel, mesh=None, use_flash=True,
                 layer_slice=None, in_shard_map=False):
    """Embed + scan decoder stack. Returns final hidden (pre-norm)."""
    c = config
    # inside the sep manual region the mesh handle is gone but the table
    # is still mp-sharded on the auto axes — keep the one-hot matmul
    # there too (the in-region take is the same unpartitionable gather)
    h = vocab_parallel_embed(params["embed"], ids, config, parallel,
                             None if in_shard_map else mesh,
                             force_matmul=in_shard_map and parallel.mp > 1)
    h = _maybe_hint(h, mesh, _act_spec(parallel))
    s_total = ids.shape[1] * (parallel.sep if in_shard_map else 1)
    cos, sin = build_rope_cache(s_total, c.head_dim, base=c.rope_theta)
    if parallel.sep > 1 and in_shard_map:
        # each sep shard sees its slice of positions
        idx = lax.axis_index("sep") * ids.shape[1]
        cos = lax.dynamic_slice_in_dim(cos, idx, ids.shape[1], 0)
        sin = lax.dynamic_slice_in_dim(sin, idx, ids.shape[1], 0)

    body = functools.partial(decoder_layer, config=c, parallel=parallel,
                             mesh=mesh, use_flash=use_flash,
                             in_shard_map=in_shard_map)
    raw_body = lambda h, p: (body(p, h, cos, sin), None)
    if parallel.remat:
        scan_body = jax.checkpoint(raw_body, policy=_remat_policy(parallel))
    else:
        scan_body = raw_body
    layer_params = params["layers"]
    if layer_slice is not None:
        layer_params = jax.tree_util.tree_map(lambda a: a[layer_slice],
                                              layer_params)
    h, _ = lax.scan(scan_body, h, layer_params,
                    unroll=parallel.scan_unroll)
    return h


def llama_logits(params, h, config, mesh=None, parallel=None,
                 out_dtype=None):
    """Final norm + lm head. ``mesh``/``parallel`` are the GSPMD training
    path's (see _per_shard); serving and the manual islands pass none.
    ``out_dtype``: what the head's matmul writes (default: the operands'
    dtype). The paged steps ask for float32, the accumulator as it is: a
    bf16 model's logits rounded to bf16 tie at the top (8 bits of mantissa
    over 32768 columns), and a greedy head then serves the first of the
    tied columns, not the best. XLA used to grant this unasked (a bf16
    matmul whose only reader is a convert to float32 keeps its float32
    result, ``xla_allow_excess_precision``) while the logits left the
    program; the head inside the program reads the bf16 array."""
    with jax.named_scope("lm_head"):
        x = _rms_norm_on(mesh, parallel, config.rms_norm_eps)(
            h, params["final_norm"])
        w = (params["embed"].T if config.tie_word_embeddings
             else params["lm_head"])
        if out_dtype is None:
            return _mat(x, w)
        if isinstance(w, dict):     # weight-only int8: scaled in x's dtype
            return _mat(x, w).astype(out_dtype)
        return jnp.matmul(x, w, preferred_element_type=out_dtype)


def masked_ce_loss(logits, labels, sep_psum: bool = False, psum_axes=None):
    """Mean CE over labels != -100 (fp32 logits). With sep_psum (or an
    explicit psum_axes tuple of MANUAL mesh axes), the sum and the token
    count are psum'd over those axes BEFORE the clamp so shards with no
    valid tokens don't deflate the denominator."""
    if psum_axes is None and sep_psum:
        psum_axes = ("sep",)
    with jax.named_scope("ce_loss"):
        mask = labels != -100
        safe = jnp.where(mask, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        loss_sum = jnp.sum(jnp.where(mask, -picked, 0.0))
        count = jnp.sum(mask)
        if psum_axes:
            loss_sum = lax.psum(loss_sum, psum_axes)
            count = lax.psum(count, psum_axes)
        return loss_sum / jnp.maximum(count, 1)


def chunked_ce_loss(x, head, labels, sep_psum: bool = False, n_chunks=8):
    """Fused head-matmul + CE over SEQUENCE chunks: the full [B*S, vocab]
    fp32 logits (1 GB at the flagship shape) never materialize — each
    chunk's logits live once for (lse, picked) and are rematerialized for
    the backward (jax.checkpoint), trading one extra chunk matmul for
    several HBM round-trips of the big array (~8 ms/step measured on v5e).
    Chunking the sequence axis (not flattened B*S) keeps the batch dim
    intact for GSPMD dp sharding. x: [B, S, D]; head: [D, vocab]."""
    b, s, d = x.shape
    rem = (-s) % n_chunks
    if rem:
        # pad to a chunk multiple with ignored labels — falling back to
        # dense would materialize exactly the logits this function avoids
        x = jnp.pad(x, ((0, 0), (0, rem), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, rem)), constant_values=-100)
        s += rem

    @jax.checkpoint
    def chunk(xc, lc):
        logits = (xc @ head).astype(jnp.float32)
        m = lc != -100
        safe = jnp.where(m, lc, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return (jnp.sum(jnp.where(m, lse - picked, 0.0)),
                m.sum().astype(jnp.float32))

    xt = x.reshape(b, n_chunks, s // n_chunks, d).swapaxes(0, 1)
    lt = labels.reshape(b, n_chunks, s // n_chunks).swapaxes(0, 1)

    def body(c, xs):
        ls, cnt = chunk(*xs)
        return (c[0] + ls, c[1] + cnt), None

    (ls, cnt), _ = lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                            (xt, lt))
    if sep_psum:
        ls = lax.psum(ls, "sep")
        cnt = lax.psum(cnt, "sep")
    return ls / jnp.maximum(cnt, 1.0)


def llama_loss(params, ids, labels, config, parallel=ParallelConfig(),
               mesh=None, use_flash=True, in_shard_map=False,
               loss_psum_axes=None):
    """Causal LM loss, fp32 softmax. labels: [B, S] with -100 = ignore.

    Uses the DENSE logits path: chunked_ce_loss measured faster in
    isolation (~8 ms) but SLOWER composed into the full train step
    (+14 ms — the sequential per-chunk head-grad matmuls lose more MXU
    efficiency than the saved logits traffic); kept available for
    memory-constrained callers."""
    h = llama_hidden(params, ids, config, parallel, mesh, use_flash,
                     in_shard_map=in_shard_map)
    logits = llama_logits(params, h, config, mesh,
                          parallel).astype(jnp.float32)
    # psum over whatever MANUAL axes shard the loss terms (callers pass
    # loss_psum_axes; default: 'sep' alone — dp/sharding stay auto and
    # GSPMD reduces them)
    return masked_ce_loss(
        logits, labels,
        psum_axes=(loss_psum_axes if loss_psum_axes is not None
                   else (("sep",) if in_shard_map and parallel.sep > 1
                         else ())))


# ---------------------------------------------------------------------------
# KV-cache decode (ref: fused_multi_transformer_op.cu — the reference's
# inference kernel is a full decoder stack with an in-place KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(config: LlamaConfig, batch: int, max_len: int):
    """Stacked per-layer cache: k AND v [L, B, KV*HD, max_len]
    (time-in-lanes slabs) for head_dim < 128.

    These are the layouts the BLOCK-DIAGONAL decode attention consumes
    (see llama_decode_step): scores = Q_blockdiag [NH, KV*HD] @ K-slab
    [KV*HD, T] and values = V-slab [KV*HD, T] contracted over T — one
    MXU-shaped matmul per batch element per layer instead of NH separate
    [1, HD] matvecs. At head_dim 64 the per-head matvecs ran 2.5x their
    bytes-bound time (M=1 sublane padding + HD=64 half-lane contraction,
    profiled 14 us vs 5.6 for the score einsum at b8); the slab matmuls
    are bytes-bound. V shares K's layout so both per-token writes are
    in-place lane columns and both per-layer reads fuse into the dot —
    a time-major [T, KV*HD] V measured a 4.2 MB slice copy + a copying
    row update per layer per step (~0.26 ms/step at hd64 b8). At
    head_dim >= 128 the per-head contraction already fills the lanes and
    the block-diag detour measured SLOWER (flagship b8: 2.92 vs 2.81
    ms/step), so those configs keep the head-major [L, B, KV, T, HD]
    cache + grouped einsums. Earlier layouts for the next reader:
    [B, T, KV, HD] forced whole-cache transposes every layer (~1.5
    ms/step of pure copies)."""
    c = config
    if c.head_dim >= 128:
        shape = (c.num_hidden_layers, batch, c.num_key_value_heads,
                 max_len, c.head_dim)
        return {"k": jnp.zeros(shape, c.dtype),
                "v": jnp.zeros(shape, c.dtype),
                "pos": jnp.zeros((), jnp.int32)}
    kvd = c.num_key_value_heads * c.head_dim
    shape = (c.num_hidden_layers, batch, kvd, max_len)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype),
            "pos": jnp.zeros((), jnp.int32)}


def llama_prefill(params, cache, ids, config: LlamaConfig):
    """Batched prompt prefill: one pass over [B, S] fills the KV cache and
    returns last-position logits — S single-token decode dispatches collapse
    into one compiled call with MXU-sized matmuls."""
    c = config
    b, s = ids.shape
    slab = c.head_dim < 128  # see init_kv_cache
    max_len = cache["k"].shape[3]  # T is dim 3 in both layouts
    h = jnp.take(params["embed"], ids, axis=0).astype(c.dtype)  # [B, S, H]
    cos_all, sin_all = build_rope_cache(max_len, c.head_dim, base=c.rope_theta)
    cos, sin = cos_all[:s], sin_all[:s]

    def layer_step(h, xs):
        p, k_cache, v_cache = xs
        hd = c.head_dim
        x = fused_rms_norm(h, p["input_norm"], c.rms_norm_eps)
        if "qkv_proj" in p:
            # decode-prepared params (prepare_decode_params): one fused
            # matmul, split into q/k/v
            ratio = c.num_attention_heads // c.num_key_value_heads
            nkv = _mat_out_dim(p["qkv_proj"]) // hd // (ratio + 2)
            nh = nkv * ratio
            qkv = _mat(x, p["qkv_proj"])
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q = q.reshape(b, s, nh, hd)
            k = k.reshape(b, s, nkv, hd)
            v = v.reshape(b, s, nkv, hd)
        else:
            nh = _mat_out_dim(p["q_proj"]) // hd
            nkv = _mat_out_dim(p["k_proj"]) // hd
            q = _mat(x, p["q_proj"]).reshape(b, s, nh, hd)
            k = _mat(x, p["k_proj"]).reshape(b, s, nkv, hd)
            v = _mat(x, p["v_proj"]).reshape(b, s, nkv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if slab:
            # k and v [B, KV*HD, T] (time-in-lanes)
            k_cache = lax.dynamic_update_slice(
                k_cache,
                k.reshape(b, s, nkv * hd).transpose(0, 2, 1)
                 .astype(k_cache.dtype),
                (0, 0, 0))
            v_cache = lax.dynamic_update_slice(
                v_cache,
                v.reshape(b, s, nkv * hd).transpose(0, 2, 1)
                 .astype(v_cache.dtype),
                (0, 0, 0))
        else:
            # head-major [B, KV, T, HD]
            k_cache = lax.dynamic_update_slice(
                k_cache, k.transpose(0, 2, 1, 3).astype(k_cache.dtype),
                (0, 0, 0, 0))
            v_cache = lax.dynamic_update_slice(
                v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype),
                (0, 0, 0, 0))
        from ..ops._common import interpret_mode
        if s >= 1024 and not interpret_mode():
            # long prompts: the Pallas flash kernel (O(S) memory, causal
            # DMA skipping) — XLA sdpa materializes [B, H, S, S] scores
            attn = flash_attention_bshd(q, k, v, causal=True)
        else:
            from ..nn.functional.attention import _xla_sdpa
            attn = _xla_sdpa(q, k, v, is_causal=True)
        attn_out = _mat(attn.reshape(b, s, nh * hd), p["o_proj"])
        h = h + attn_out
        x2 = fused_rms_norm(h, p["post_norm"], c.rms_norm_eps)
        gated = jax.nn.silu(_mat(x2, p["gate_proj"])) * _mat(x2, p["up_proj"])
        h = h + _mat(gated, p["down_proj"])
        return h, (k_cache, v_cache)

    h, (new_k, new_v) = lax.scan(layer_step, h,
                                 (params["layers"], cache["k"], cache["v"]))
    logits = llama_logits(params, h[:, -1:], config)[:, 0]
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v,
                                        "pos": cache["pos"] + s}


def llama_decode_step(params, cache, ids, config: LlamaConfig):
    """One incremental decode step: ids [B, 1] -> (logits [B, vocab], cache).

    jit-stable: cache position is a traced scalar, cache updates are
    dynamic_update_slice, attention masks positions >= pos+1. The layer
    loop is a lax.scan over the stacked layer params + cache slices
    (measured: an unrolled static-index loop is SLOWER at b8 — the scan's
    per-iteration xs slicing pipelines the weight stream better than a
    chain of static slices, 2.57 vs 2.15 ms/step on the hd64 shape).
    """
    c = config
    b = ids.shape[0]
    slab = c.head_dim < 128  # see init_kv_cache
    max_len = cache["k"].shape[3]  # T is dim 3 in both layouts
    pos = cache["pos"]
    h = jnp.take(params["embed"], ids[:, 0], axis=0).astype(c.dtype)  # [B, H]

    cos_all, sin_all = build_rope_cache(max_len, c.head_dim,
                                        base=c.rope_theta)
    cos = lax.dynamic_slice_in_dim(cos_all, pos, 1, 0)
    sin = lax.dynamic_slice_in_dim(sin_all, pos, 1, 0)

    def layer_step(carry, xs):
        # full stacked caches ride the CARRY (in-place loop state, buffer
        # aliased across iterations), NOT xs/ys: a ys cache would be
        # copied wholesale every layer of every token (~full-cache HBM
        # traffic per step — measured 2.5x decode slowdown at b8)
        h, kc, vc = carry
        p, layer = xs
        hd = c.head_dim
        x = fused_rms_norm(h[:, None], p["input_norm"], c.rms_norm_eps)
        if "qkv_proj" in p:
            # fused projection (_decode_weights): one weight slice + one
            # matmul per layer instead of three
            ratio = c.num_attention_heads // c.num_key_value_heads
            nkv = _mat_out_dim(p["qkv_proj"]) // hd // (ratio + 2)
            nh = nkv * ratio
            qkv = _mat(x, p["qkv_proj"])
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q = q.reshape(b, 1, nh, hd)
            k = k.reshape(b, 1, nkv, hd)
            v = v.reshape(b, 1, nkv, hd)
        else:
            nh = _mat_out_dim(p["q_proj"]) // hd
            nkv = _mat_out_dim(p["k_proj"]) // hd
            q = _mat(x, p["q_proj"]).reshape(b, 1, nh, hd)
            k = _mat(x, p["k_proj"]).reshape(b, 1, nkv, hd)
            v = _mat(x, p["v_proj"]).reshape(b, 1, nkv, hd)
        kvd = nkv * hd
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        zero = jnp.zeros((), jnp.int32)
        layer_i = jnp.asarray(layer, jnp.int32)
        rep = nh // nkv
        qg = q[:, 0].reshape(b, nkv, rep, hd)
        if slab:
            # BLOCK-DIAGONAL attention: per batch element ONE [NH, KV*HD]
            # x [KV*HD, T] score matmul and ONE [KV*HD, T] x [T, NH]
            # value matmul. q is scattered into a block-diagonal
            # [NH, KV*HD] (head (g, r) occupies kv-group g's column
            # block; the zeros kill cross-head terms exactly), and the
            # value result's diagonal blocks are gathered back. Trades
            # nkv x padded FLOPs (~0.3 us/layer; decode is bytes-bound)
            # for MXU-shaped operands: per-head [1, HD<128] matvecs ran
            # 2.5x bytes-bound time (M=1 sublane padding, profiled 14 vs
            # 5.6 us at hd64 b8); a VPU broadcast+reduce formulation was
            # worse still (2.48 ms/step).
            eye = jnp.eye(nkv, dtype=qg.dtype)
            q_bd = jnp.einsum("bgrd,ge->bgred", qg, eye).reshape(b, nh, kvd)
            if max_len % 128 == 0:
                # fused Pallas attend+update: the new k/v column is
                # written in-place INSIDE the kernel (caches alias
                # through the custom call), and the attention reads the
                # slabs directly — neither the per-layer cache slice
                # nor the V relayout copy exists. Requires the
                # 128-aligned cache extents _prefill_for_generate now
                # allocates.
                from ..ops.decode_attention import (
                    _LOG2E, decode_attend_update_slab)
                qs = (q_bd.astype(jnp.float32)
                      * (_LOG2E / (hd ** 0.5))).astype(q_bd.dtype)
                attn_full, kc, vc = decode_attend_update_slab(
                    qs, k.reshape(b, kvd).astype(kc.dtype),
                    v.reshape(b, kvd).astype(vc.dtype), kc, vc,
                    layer_i, pos)
            else:
                # ragged extent: XLA einsum path. V slab as the dot RHS
                # contracting its minor (T) dim — the same operand role
                # the K slab plays in the score einsum, so XLA assigns
                # the same in-place layout.
                kc = lax.dynamic_update_slice(
                    kc, k.reshape(b, kvd, 1).astype(kc.dtype)[None],
                    (layer_i, zero, zero, pos))
                vc = lax.dynamic_update_slice(
                    vc, v.reshape(b, kvd, 1).astype(vc.dtype)[None],
                    (layer_i, zero, zero, pos))
                k_cache = lax.dynamic_index_in_dim(kc, layer, 0,
                                                   keepdims=False)
                v_cache = lax.dynamic_index_in_dim(vc, layer, 0,
                                                   keepdims=False)
                scores = jnp.einsum("bhc,bct->bht", q_bd, k_cache,
                                    preferred_element_type=jnp.float32)
                scores = scores / (hd ** 0.5)
                valid = jnp.arange(max_len)[None, None, :] <= pos
                scores = jnp.where(valid, scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1) \
                    .astype(v_cache.dtype)
                attn_full = jnp.einsum("bht,bct->bhc", probs, v_cache,
                                       preferred_element_type=jnp.float32)
            attn = jnp.einsum("bgred,ge->bgrd",
                              attn_full.reshape(b, nkv, rep, nkv, hd),
                              eye.astype(attn_full.dtype)).astype(c.dtype)
        else:
            # head-major cache [B, KV, T, HD]: grouped-query einsums
            # against contiguous per-head [T, HD] panels — at HD >= 128
            # the contraction fills the lanes and this is bytes-bound;
            # the block-diag detour measured slower here.
            kc = lax.dynamic_update_slice(
                kc, k.transpose(0, 2, 1, 3).astype(kc.dtype)[None],
                (layer_i, zero, zero, pos, zero))
            vc = lax.dynamic_update_slice(
                vc, v.transpose(0, 2, 1, 3).astype(vc.dtype)[None],
                (layer_i, zero, zero, pos, zero))
            k_cache = lax.dynamic_index_in_dim(kc, layer, 0, keepdims=False)
            v_cache = lax.dynamic_index_in_dim(vc, layer, 0, keepdims=False)
            scores = jnp.einsum("bgrd,bgtd->bgrt", qg, k_cache,
                                preferred_element_type=jnp.float32)
            scores = scores / (hd ** 0.5)
            valid = jnp.arange(max_len)[None, None, None, :] <= pos
            scores = jnp.where(valid, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
            attn = jnp.einsum("bgrt,bgtd->bgrd", probs, v_cache,
                              preferred_element_type=jnp.float32
                              ).astype(c.dtype)
        attn_out = _mat(attn.reshape(b, nh * hd), p["o_proj"])
        h = h + attn_out

        x2 = fused_rms_norm(h[:, None], p["post_norm"], c.rms_norm_eps)[:, 0]
        gated = jax.nn.silu(_mat(x2, p["gate_proj"])) * _mat(x2, p["up_proj"])
        h = h + _mat(gated, p["down_proj"])
        return (h, kc, vc), None

    n_layers = cache["k"].shape[0]
    (h, new_k, new_v), _ = lax.scan(
        layer_step, (h, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    logits = llama_logits(params, h[:, None], config)[:, 0]
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v,
                                        "pos": pos + 1}


def init_paged_kv_pool(config: LlamaConfig, num_blocks: int,
                       block_size: int, kv_dtype: str = "auto"):
    """Paged KV pool for the serving engine: k and v
    [L, num_blocks, KV*HD, block_size] — each block is a time-in-lanes
    slab fragment, so the paged kernel's per-block dots are the same
    [KVD, bs] shapes the contiguous slab kernel tiles into. Block 0 is
    reserved as the null block (see inference/kv_cache.py): padding
    rows scribble there and live tables never reference it.

    ``kv_dtype='auto'`` stores the model dtype (the pre-PR-16 path,
    bit-identical); ``'int8'`` stores quantized bytes — pair with
    :func:`init_paged_kv_scales`."""
    c = config
    if kv_dtype not in ("auto", "int8"):
        raise ValueError(f"kv_dtype must be 'auto' or 'int8', "
                         f"got {kv_dtype!r}")
    dt = jnp.int8 if kv_dtype == "int8" else c.dtype
    kvd = c.num_key_value_heads * c.head_dim
    shape = (c.num_hidden_layers, num_blocks, kvd, block_size)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def init_paged_kv_scales(config: LlamaConfig, num_blocks: int,
                         block_size: int):
    """f32 scale pools [L, num_blocks, NKV, block_size] for an int8
    paged KV pool: one symmetric absmax scale per block / kv head /
    COLUMN (ops/paged_attention.kv_quant_columns). Zero-initialized so
    never-written columns (incl. null-block scribbles) dequantize to
    exactly 0."""
    c = config
    shape = (c.num_hidden_layers, num_blocks, c.num_key_value_heads,
             block_size)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


# ---------------------------------------------------------------------------
# tensor-parallel serving (PR 19): paged steps inside an mp shard_map
# ---------------------------------------------------------------------------
#
# The serving TP path threads ``tp=(axis_name, n)`` through the three
# paged step functions below. Weights arrive PRE-SLICED by the island's
# in_specs (param_pspecs over 'mp'), so the column-parallel projections
# need no code change at all — nh/nkv are derived from weight shapes and
# become local head counts, the paged kernels and their _fit_* fitters
# price the per-shard [KVD/n, bs] geometry from argument shapes, and the
# block-diagonal-q attention is exact per kv-head. Only three collectives
# exist: the vocab-parallel embed psum (exact — each id is non-zero on
# one rank), the o_proj/down_proj row-parallel reduce (the ONLY
# re-associated sums vs mp=1; greedy argmax keeps token streams
# identical), and the verify logits all-gather (exact vocab concat so
# accept/commit logic is rank-identical). See PARITY.md (PR 19).

def _tp_vocab_embed(embed, ids, tp):
    """Masked vocab-parallel lookup INSIDE the serving island: ``embed``
    is this rank's [V/n, H] vocab slice; every id row is non-zero on
    exactly one rank, so the psum is EXACT in any dtype (same contract
    as vocab_parallel_embed, manual-collective form)."""
    axis, _ = tp
    vs = embed.shape[0]
    local = ids - lax.axis_index(axis) * vs
    ok = (local >= 0) & (local < vs)
    rows = jnp.take(embed, jnp.clip(local, 0, vs - 1), axis=0)
    rows = jnp.where(ok[..., None], rows, 0).astype(embed.dtype)
    with _obs.comm_span("serve.tp_ring.embed",
                        nbytes=rows.size * rows.dtype.itemsize,
                        site="serve.tp_ring.embed"):
        return lax.psum(rows, axis)


def _tp_row_matmul(x, w, tp):
    """Row-parallel ``x @ w_local`` + cross-rank reduce for the serving
    TP path: x [..., k/n] holds this rank's slice of the contracted dim
    (its attention heads / FFN columns), w [k/n, out] the matching row
    shard. Routes through the overlapped reduce-scatter ring
    (ring_allreduce_matmul) when PADDLE_TPU_TP_OVERLAP is on and the
    row count divides the ring, else the blocking psum — the mp=2 ring
    is pinned bitwise-vs-blocking (parallel/collective_matmul), so the
    knob never changes mp=2 streams."""
    from ..parallel.collective_matmul import (overlap_enabled,
                                              resolve_chunks,
                                              ring_allreduce_matmul)
    axis, n = tp
    lead = x.shape[:-1]
    t = x.size // x.shape[-1]
    x2 = x.reshape(t, x.shape[-1])
    if overlap_enabled() and t % n == 0 and not isinstance(w, dict):
        out = ring_allreduce_matmul(x2, w, n, axis, resolve_chunks(n, t // n))
    else:
        out = lax.psum(_mat(x2, w), axis)
    return out.reshape(lead + out.shape[-1:])


def _tp_o_proj(a, w, tp):
    t = a.size // a.shape[-1]
    with _obs.comm_span("serve.tp_ring.o_proj",
                        nbytes=t * _mat_out_dim(w) * a.dtype.itemsize,
                        site="serve.tp_ring.o_proj"):
        return _tp_row_matmul(a, w, tp)


def _tp_down_proj(a, w, tp):
    t = a.size // a.shape[-1]
    with _obs.comm_span("serve.tp_ring.down_proj",
                        nbytes=t * _mat_out_dim(w) * a.dtype.itemsize,
                        site="serve.tp_ring.down_proj"):
        return _tp_row_matmul(a, w, tp)


def _tp_gather_logits(logits, tp):
    """All-gather vocab-sliced logits to the full vocab axis INSIDE the
    island (tiled concat in rank order — exact, no arithmetic), so the
    greedy head of every program, and verify's accept/commit logic after
    it, compute from identical full logits on every rank."""
    axis, n = tp
    with _obs.comm_span("serve.tp_ring.logits",
                        nbytes=logits.size * (n - 1) * logits.dtype.itemsize,
                        site="serve.tp_ring.logits"):
        return lax.all_gather(logits, axis, axis=logits.ndim - 1,
                              tiled=True)


def _paged_embed(params, ids, config, tp):
    """The hidden rows a paged step starts from: ``ids`` of any shape ->
    [..., H] in the model's dtype (vocab-parallel inside a ``tp`` island)."""
    rows = (jnp.take(params["embed"], ids, axis=0) if tp is None
            else _tp_vocab_embed(params["embed"], ids, tp))
    return rows.astype(config.dtype)


def _paged_qkv(p, x, cos, sin, config):
    """A layer's projections of a paged step's normed rows x [a, n, H]:
    q [a, n, NH, hd], k and v [a, n, NKV, hd], q and k roped by the rows'
    own phases. The head counts come from the weights' shapes, so they are
    the local ones inside a tensor-parallel island."""
    hd = config.head_dim
    if "qkv_proj" in p:
        ratio = config.num_attention_heads // config.num_key_value_heads
        nkv = _mat_out_dim(p["qkv_proj"]) // hd // (ratio + 2)
        nh = nkv * ratio
        q, k, v = jnp.split(_mat(x, p["qkv_proj"]),
                            [nh * hd, (nh + nkv) * hd], axis=-1)
    else:
        q, k, v = (_mat(x, p[name])
                   for name in ("q_proj", "k_proj", "v_proj"))
    q, k, v = (t.reshape(x.shape[:2] + (-1, hd)) for t in (q, k, v))
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _paged_attend_rows(q, k, v, pools, walk, layer, config):
    """The decode batch's attention in one layer: q [B, NH, hd], k and v
    [B, NKV, hd] (roped) of one new token a row; ``walk`` the batch's
    ``paged_update_walk``, made once before the layer loop. The fused
    paged kernel writes each row's column at its position into its block
    and attends over the row's table, updating ``pools`` ((k, v) or the
    int8 (k, v, k_scale, v_scale)) in place through input_output_aliases.
    Returns (rows [B, NH*hd], pools)."""
    from ..ops.paged_attention import (kv_quant_columns, paged_attend_update,
                                       paged_attend_update_quant)
    nkv = k.shape[1]

    def kernel(qs, k, v):
        if len(pools) == 2:
            kp, vp = pools
            return paged_attend_update(
                qs, k.astype(kp.dtype), v.astype(vp.dtype), kp, vp, walk,
                layer)
        # each new column is quantized per-kv-head OUTSIDE the kernel (the
        # bytes a prefill of the same tokens writes); the fused update
        # merges bytes + scales in place
        nk_q, nk_s = kv_quant_columns(k, nkv)
        nv_q, nv_s = kv_quant_columns(v, nkv)
        return paged_attend_update_quant(qs, nk_q, nv_q, nk_s, nv_s, *pools,
                                         walk, layer)
    return rows_attend(q, k, v, kernel, config.dtype)


def rows_attend(q, k, v, kernel, dtype):
    """One new token a row through a fused update kernel: q [B, NH, hd], k
    and v [B, NKV, hd] (roped) in the kernel's layout (block-diagonal q
    [B, NH, NKV*hd] pre-scaled for its base-2 softmax: the kernel reads
    whole [KVD, bs] slab fragments per sequence, see llama_decode_step;
    k and v [B, KVD]); ``kernel(qs, k, v) -> (attn [B, NH, KVD] f32,
    *caches)``. Returns (rows [B, NH*hd] in ``dtype``, caches)."""
    from ..ops.paged_attention import _LOG2E
    b, nh, hd = q.shape
    nkv = k.shape[1]
    kvd, rep = nkv * hd, nh // nkv
    qg = q.reshape(b, nkv, rep, hd)
    eye = jnp.eye(nkv, dtype=qg.dtype)
    q_bd = jnp.einsum("bgrd,ge->bgred", qg, eye).reshape(b, nh, kvd)
    qs = (q_bd.astype(jnp.float32)
          * (_LOG2E / (hd ** 0.5))).astype(q_bd.dtype)
    attn_full, *caches = kernel(qs, k.reshape(b, kvd), v.reshape(b, kvd))
    attn = jnp.einsum("bgred,ge->bgrd",
                      attn_full.reshape(b, nkv, rep, nkv, hd),
                      eye.astype(attn_full.dtype)).astype(dtype)
    return attn.reshape(b, nh * hd), tuple(caches)


def _paged_layer_tail(p, h, ao, config, tp):
    """What follows a paged step's attention, on rows of any leading shape:
    ``o_proj`` of the attention's rows ``ao``, the residual, the post norm,
    the FFN and its residual (the two row-parallel products reduce across
    ranks inside a ``tp`` island)."""
    h = h + (_mat(ao, p["o_proj"]) if tp is None
             else _tp_o_proj(ao, p["o_proj"], tp))
    x2 = fused_rms_norm(h, p["post_norm"], config.rms_norm_eps)
    gated = jax.nn.silu(_mat(x2, p["gate_proj"])) * _mat(x2, p["up_proj"])
    return h + (_mat(gated, p["down_proj"]) if tp is None
                else _tp_down_proj(gated, p["down_proj"], tp))


def _scan_paged_layers(layer_step, h, pools, params):
    """Run ``layer_step(h, pools, p, layer) -> (h, pools)`` over the stacked
    layers with the pools as donated carries; returns (h, pools)."""
    xs = (params["layers"],
          jnp.arange(pools[0].shape[0], dtype=jnp.int32))
    return lax.scan(lambda carry, x: (layer_step(*carry, *x), None),
                    (h, tuple(pools)), xs)[0]


def llama_paged_decode_step(params, pools, tables, positions, ids,
                            config: LlamaConfig, tp=None):
    """One decode step over a PAGED cache: ``pools`` (k, v), or the int8
    (k, v, k_scale, v_scale) (see ``_paged_attend_rows``); ids [B] i32, tables
    [B, max_nb] i32 block tables, positions [B] i32 = the slot each
    row's new token occupies (== its cached length; the block holding
    it must already be in the table). Per-row rope phases come from
    ``positions`` so every sequence in the batch can sit at a different
    depth — the whole point of continuous batching. Padding rows point
    their tables at null block 0 with positions 0.

    Returns (logits [B, vocab] f32, *pools). The pools ride
    the layer scan as carries and the Pallas kernel updates them
    in-place through input_output_aliases, so no per-layer cache copy
    exists (the conservative-aliasing trap documented in
    ops/decode_attention.py STATUS)."""
    from ..ops.paged_attention import paged_update_walk
    c = config
    h = _paged_embed(params, ids, c, tp)[:, None]               # [B, 1, H]
    cos, sin = build_rope_cache(ids.shape[0], c.head_dim, base=c.rope_theta,
                                position_ids=positions[:, None])  # [B,1,·]
    walk = paged_update_walk(tables, positions, pools[0].shape[-1])

    def layer_step(h, pools, p, layer):
        x = fused_rms_norm(h, p["input_norm"], c.rms_norm_eps)
        q, k, v = _paged_qkv(p, x, cos, sin, c)
        ao, pools = _paged_attend_rows(q[:, 0], k[:, 0], v[:, 0], pools,
                                       walk, layer, c)
        return _paged_layer_tail(p, h, ao[:, None], c, tp), pools

    h, pools = _scan_paged_layers(layer_step, h, pools, params)
    logits = llama_logits(params, h, config, out_dtype=jnp.float32)[:, 0]
    return (logits,) + pools


def _pin_pool_layout(pool):
    """Hold a paged pool [L, NP, W, bs] to its row-major, time-in-lanes
    layout inside a step that touches it with XLA ops only. The chunk's
    new columns reach the pool through transposes, and XLA's TPU layout
    assignment reads a transpose as a free change of layout: left alone it
    carries the pool through the layer loop KVD-minor, the chunk's layout,
    and copies the whole pool to that layout and back around the loop. The
    decode and verify steps need no pin: their Pallas calls fix the
    operand's layout."""
    from jax.experimental.layout import Layout, with_layout_constraint
    return with_layout_constraint(
        pool, Layout(major_to_minor=tuple(range(pool.ndim))))


def _pool_write_chunk(pool, layer, bids, fresh, tiles):
    """Write a prefill chunk into one layer of a paged pool IN PLACE:
    pool [L, NP, W, bs]; tiles [n_slot, W, bs] new block tiles for pool
    blocks ``bids`` [n_slot]; fresh [n_slot, 1, bs] marks the lanes to
    take from ``tiles`` (the rest keep the block's bytes). One
    dynamic-slice / select / dynamic-update-slice per touched block, which
    XLA's TPU backend updates inside the donated scan carry. A scatter
    along the lane axis (``pool.at[layer, bid, :, col].set``) writes the
    same bytes but makes the compiler re-lay the WHOLE pool out KVD-minor
    and back on every chunk: a second pool-sized HBM buffer (a pool over
    half of HBM is refused at compile time) and two full-pool copies per
    chunk."""
    z = jnp.int32(0)
    for j in range(tiles.shape[0]):
        at = (layer, bids[j], z, z)
        old = lax.dynamic_slice(pool, at, (1, 1) + tiles.shape[1:])
        pool = lax.dynamic_update_slice(
            pool, jnp.where(fresh[j], tiles[j], old[0, 0])[None, None], at)
    return pool


def _chunk_window(table_row, start, n_live, C, bs):
    """Where a prefill chunk's columns land, for ``_pool_write_chunk``: in at
    most n_slot consecutive table slots, written a whole [W, bs] block tile
    at a time. Slot j of the window is table slot s0 + j, the chunk's token
    0 sits `off` lanes into the window. Returns (wbid [n_slot] the pool
    blocks, slots past the live span writing the null block 0 back onto
    itself; fresh [n_slot, 1, bs] the lanes real tokens fill; window(cols):
    [C, W] chunk columns -> [n_slot, W, bs] block tiles, time in lanes, the
    other lanes zero and masked out by `fresh`)."""
    max_nb = table_row.shape[0]
    n_slot = -(-C // bs) + 1
    s0 = start // bs
    off = start - s0 * bs
    slot = s0 + jnp.arange(n_slot, dtype=jnp.int32)
    slot_live = (slot * bs < start + n_live) & (slot < max_nb)
    wbid = jnp.where(slot_live, table_row[jnp.clip(slot, 0, max_nb - 1)],
                     0).astype(jnp.int32)
    lane = jnp.arange(n_slot * bs, dtype=jnp.int32).reshape(n_slot, 1, bs)
    fresh = ((lane >= off) & (lane < off + n_live)
             & slot_live[:, None, None])

    def window(cols):
        w = cols.shape[1]
        win = lax.dynamic_update_slice(
            jnp.zeros((w, n_slot * bs), cols.dtype), cols.T,
            (jnp.int32(0), off))
        return win.reshape(w, n_slot, bs).transpose(1, 0, 2)
    return wbid, fresh, window


def _paged_attend_chunk(q, k, v, pools, table_row, start, n_live, layer,
                        where):
    """A prefill chunk's attention in one layer: q [C, NH, hd], k and v
    [C, NKV, hd] (roped), ``where`` the chunk's ``_chunk_window``. The
    chunk's KV columns land in their blocks first (int8 pools: quantized
    per-kv-head, one scale a column, so the bytes do not depend on chunk
    boundaries); the attention then reads prefix and chunk alike from the
    pools, walking the sequence's live blocks. Returns (rows [C, NH*hd],
    pools)."""
    from ..ops.paged_attention import (kv_quant_columns,
                                       paged_prefill_attention)
    wbid, fresh, window = where
    C, nkv = k.shape[:2]
    k, v = k.reshape(C, -1), v.reshape(C, -1)
    if len(pools) == 2:
        cols = (k.astype(pools[0].dtype), v.astype(pools[1].dtype))
    else:
        kq, ksq = kv_quant_columns(k, nkv)
        vq, vsq = kv_quant_columns(v, nkv)
        cols = (kq, vq, ksq, vsq)
    pools = tuple(
        _pool_write_chunk(_pin_pool_layout(pool), layer, wbid, fresh,
                          window(col))
        for pool, col in zip(pools, cols))
    attn = paged_prefill_attention(q, pools[0], pools[1], table_row, start,
                                   n_live, layer,
                                   kv_scales=pools[2:] or None)
    return attn.reshape(C, -1), pools


def llama_paged_prefill_chunk(params, pools, table_row, start, ids, n_live,
                              config: LlamaConfig, tp=None):
    """One chunked-prefill slice for ONE sequence over ``pools`` ((k, v) or
    the int8 four, see ``_paged_attend_chunk``): ids [C] i32 padded
    to the chunk bucket, n_live (traced) real tokens, start (traced) =
    tokens already cached from earlier chunks. Scatters the chunk's KV
    into the sequence's blocks (padding tokens land in null block 0),
    attends each chunk token over cached-prefix + chunk causally by
    walking the sequence's LIVE blocks through the block table
    (ops.paged_attention.paged_prefill_attention: the pools are read back
    after the write, so the chunk's own columns come from there too), and
    returns the logits of the LAST REAL token ([vocab] f32 — only
    meaningful on the final chunk) plus the updated pools:
    (logits, *pools)."""
    c = config
    C = ids.shape[0]
    h = _paged_embed(params, ids, c, tp)[None]                  # [1, C, H]
    pidx = start + jnp.arange(C, dtype=jnp.int32)          # [C] positions
    cos, sin = build_rope_cache(C, c.head_dim, base=c.rope_theta,
                                position_ids=pidx)         # [C, hd/2]
    where = _chunk_window(table_row, start, n_live, C, pools[0].shape[-1])

    def layer_step(h, pools, p, layer):
        x = fused_rms_norm(h, p["input_norm"], c.rms_norm_eps)
        q, k, v = _paged_qkv(p, x, cos, sin, c)
        ao, pools = _paged_attend_chunk(q[0], k[0], v[0], pools, table_row,
                                        start, n_live, layer, where)
        return _paged_layer_tail(p, h, ao[None], c, tp), pools

    h, pools = _scan_paged_layers(layer_step, h, pools, params)
    h_last = lax.dynamic_slice_in_dim(h[0], n_live - 1, 1, 0)[None]
    logits = llama_logits(params, h_last, config,
                          out_dtype=jnp.float32)[0, 0]
    return (logits,) + pools


def llama_paged_prefill_chunk_with_decode(params, pools, table_row, start,
                                          ids, n_live, tables, positions,
                                          row_ids, config: LlamaConfig,
                                          tp=None):
    """A prefill chunk with the decode batch riding it, for an iteration
    that has both: ONE layer scan over the chunk's C rows (``table_row``,
    ``start``, ``ids``, ``n_live`` as ``llama_paged_prefill_chunk`` takes
    them) and the batch's R rows (``tables``, ``positions``, ``row_ids`` as
    ``llama_paged_decode_step`` takes them; padding rows at null block 0,
    position 0), so the weights stream once for both. Embedding, norms,
    projections, ``o_proj``, the FFN and the head run on the C + R rows
    together; between the projections and ``o_proj`` the rows part, each to
    the attention of its own step (the chunk's write and block-table flash
    attention, the batch's fused paged update), each roped by its own
    positions. The parts touch disjoint blocks: a sequence is in prefill or
    running, never both. The batch's update goes first, so the chunk's
    attention is the pools' last reader in a layer and nothing copies them.

    Returns (the chunk's last-live-token logits [vocab] f32, the batch's
    logits [R, vocab] f32, *pools)."""
    from ..ops.paged_attention import paged_update_walk
    c = config
    C = ids.shape[0]
    h = _paged_embed(params, jnp.concatenate([ids, row_ids]), c,
                     tp)[None]                              # [1, C + R, H]
    pidx = jnp.concatenate([start + jnp.arange(C, dtype=jnp.int32),
                            positions])
    cos, sin = build_rope_cache(pidx.shape[0], c.head_dim,
                                base=c.rope_theta, position_ids=pidx)
    where = _chunk_window(table_row, start, n_live, C, pools[0].shape[-1])
    walk = paged_update_walk(tables, positions, pools[0].shape[-1])

    def layer_step(h, pools, p, layer):
        x = fused_rms_norm(h, p["input_norm"], c.rms_norm_eps)
        q, k, v = (t[0] for t in _paged_qkv(p, x, cos, sin, c))
        ao_rows, pools = _paged_attend_rows(q[C:], k[C:], v[C:], pools,
                                            walk, layer, c)
        ao_chunk, pools = _paged_attend_chunk(q[:C], k[:C], v[:C], pools,
                                              table_row, start, n_live,
                                              layer, where)
        ao = jnp.concatenate([ao_chunk, ao_rows])[None]
        return _paged_layer_tail(p, h, ao, c, tp), pools

    h, pools = _scan_paged_layers(layer_step, h, pools, params)
    h_last = lax.dynamic_slice_in_dim(h[0], n_live - 1, 1, 0)
    heads = jnp.concatenate([h_last, h[0, C:]])[:, None]    # [1 + R, 1, H]
    logits = llama_logits(params, heads, config,
                          out_dtype=jnp.float32)[:, 0]
    return (logits[0], logits[1:]) + pools


# ---------------------------------------------------------------------------
# speculative decoding (PR 18): draft model + batched paged verification
# ---------------------------------------------------------------------------

def make_draft_model(params, config: LlamaConfig, num_layers: int = 1):
    """Default draft model for speculative decoding: the base model's
    FIRST ``num_layers`` decoder layers, sharing the embedding, final
    norm and lm head by reference (no copy — the stacked-leaf layout
    makes the truncation a view-style slice per leaf).

    A truncated self-draft needs no extra training to correlate with
    the base argmax, and the PARITY contract makes its quality a pure
    latency knob: verification re-derives every emitted token from the
    base model, so ANY draft — this one, separately trained weights, or
    garbage — yields bit-identical streams. Returns (draft_params,
    draft_config)."""
    dl = max(1, min(int(num_layers), config.num_hidden_layers))
    dcfg = dataclasses.replace(config, num_hidden_layers=dl)
    dparams = {
        "embed": params["embed"],
        "layers": jax.tree_util.tree_map(lambda a: a[:dl],
                                         params["layers"]),
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        dparams["lm_head"] = params["lm_head"]
    return dparams, dcfg


def llama_paged_verify_step(params, pools, tables, qstart, t_live, fed,
                            config: LlamaConfig, tp=None):
    """Score T fed tokens per sequence in ONE base-model pass over a
    paged cache (``pools``: (k, v), or the int8 (k, v, k_scale, v_scale)),
    greedily accept/reject, and commit only accepted KV.

    fed [B, T] i32 — fed[:, 0] is each row's last emitted token (its KV
    is NOT yet cached), fed[:, 1:] the draft's proposals; qstart [B]
    i32 cached token counts (fed[:, j] sits at position qstart + j);
    t_live [B] i32 live fed counts (1 = plain decode through this
    path, 0 = padding row: tables at null block 0, qstart 0).

    Attention splits into the cached prefix — the multi-token paged
    kernel returns online-softmax partials — and the tiny [T, T] causal
    fed block computed here in XLA, merged exactly
    (ops/paged_attention.merge_verify_partials). The greedy accept rule
    takes the longest prefix where the base argmax equals the draft
    proposal, then the base's correction token: out[:, j] is the base's
    next-token argmax after position qstart + j, and
    commit_len = accepted proposals + 1 counts the fed tokens whose KV
    is committed (the correction token's KV is NOT cached — it is the
    next iteration's fed[:, 0], exactly like sequential decode).

    Returns (out [B, T] i32, commit_len [B] i32, fin_ok [B] bool,
    *pools) — the emitted tokens for row b are
    out[b, :commit_len[b]]; fin_ok flags rows whose logits were all
    finite (the engine's poison screen — it never sees logits). With
    int8 pools the fed columns quantize OUTSIDE the kernels via
    kv_quant_columns and the fed-block attention reads the DEQUANTIZED
    values, so both the committed bytes and the numerics each token sees
    match sequential int8 decode."""
    from ..ops.paged_attention import (_LOG2E, kv_quant_columns,
                                       merge_verify_partials,
                                       paged_attention_verify,
                                       paged_attention_verify_quant,
                                       paged_verify_commit,
                                       paged_verify_commit_quant)
    c = config
    B, T = fed.shape
    hd = c.head_dim
    h = _paged_embed(params, fed, c, tp)                        # [B,T,H]
    pos2d = qstart[:, None] + jnp.arange(T, dtype=jnp.int32)    # [B,T]
    cos, sin = build_rope_cache(T, hd, base=c.rope_theta,
                                position_ids=pos2d)             # [B,T,hd/2]
    # dead-row guard: a padding row's kernel outputs are unwritten, so
    # zero its cached-side partials (anchor -1e30 rescales to exactly 0)
    live3 = (qstart > 0)[:, None, None]

    def layer_step(carry, xs):
        # pools are closure-captured read-only here (the commit below is
        # the single writer), so the carry holds just the hidden state
        h, = carry
        p, layer = xs
        x = fused_rms_norm(h, p["input_norm"], c.rms_norm_eps)
        q, k, v = _paged_qkv(p, x, cos, sin, c)
        nh, nkv = q.shape[2], k.shape[2]
        kvd = nkv * hd
        layer_i = jnp.asarray(layer, jnp.int32)
        rep = nh // nkv
        # t-major block-diagonal rows: row t*NH + i is fed token t's
        # head-i query against whole [KVD, bs] slab fragments
        qg = q.reshape(B, T, nkv, rep, hd)
        eye = jnp.eye(nkv, dtype=qg.dtype)
        q_bd = jnp.einsum("btgrd,ge->btgred", qg, eye) \
            .reshape(B, T * nh, kvd)
        qs = (q_bd.astype(jnp.float32)
              * (_LOG2E / (hd ** 0.5))).astype(q_bd.dtype)
        if len(pools) == 2:
            acc_c, m_c, l_c = paged_attention_verify(
                qs, *pools, tables, qstart, layer_i)
            # fed columns AS STORED (pool dtype round-trip): the exact
            # values sequential decode would read back from the cache
            k_st = k.reshape(B, T, kvd).astype(pools[0].dtype)
            v_st = v.reshape(B, T, kvd).astype(pools[1].dtype)
            kf = k_st.astype(jnp.float32)
            vf = v_st.astype(jnp.float32)
            ys = (k_st, v_st)
        else:
            kq, ksq = kv_quant_columns(k.reshape(B * T, kvd), nkv)
            vq, vsq = kv_quant_columns(v.reshape(B * T, kvd), nkv)
            kq = kq.reshape(B, T, kvd)
            vq = vq.reshape(B, T, kvd)
            ksq = ksq.reshape(B, T, nkv)
            vsq = vsq.reshape(B, T, nkv)
            acc_c, m_c, l_c = paged_attention_verify_quant(
                qs, *pools, tables, qstart, layer_i)
            kf = (kq.astype(jnp.float32).reshape(B, T, nkv, hd)
                  * ksq[..., None]).reshape(B, T, kvd)
            vf = (vq.astype(jnp.float32).reshape(B, T, nkv, hd)
                  * vsq[..., None]).reshape(B, T, kvd)
            ys = (kq, vq, ksq, vsq)
        # fed-token causal attention in XLA: block-diagonal q rows make
        # the GQA head selection automatic in the [KVD] dot
        s_f = jnp.einsum("brk,buk->bru", qs.astype(jnp.float32), kf)
        t_row = jnp.arange(T * nh, dtype=jnp.int32) // nh      # [R]
        causal = (jnp.arange(T, dtype=jnp.int32)[None, :]
                  <= t_row[:, None])                           # [R,T]
        s_f = jnp.where(causal[None], s_f, jnp.float32(-1e30))
        m_f = s_f.max(axis=-1, keepdims=True)
        p_f = jnp.exp2(s_f - m_f)
        l_f = p_f.sum(axis=-1, keepdims=True)
        acc_f = jnp.einsum("bru,buk->brk", p_f, vf)
        attn_rows = merge_verify_partials(
            jnp.where(live3, acc_c, 0.0),
            jnp.where(live3, m_c[:, :, :1], jnp.float32(-1e30)),
            jnp.where(live3, l_c[:, :, :1], 0.0),
            acc_f, m_f, l_f)                                   # [B,R,KVD]
        attn = jnp.einsum("btgred,ge->btgrd",
                          attn_rows.reshape(B, T, nkv, rep, nkv, hd),
                          eye.astype(attn_rows.dtype)).astype(c.dtype)
        h = _paged_layer_tail(p, h, attn.reshape(B, T, nh * hd), c, tp)
        return (h,), ys

    xs = (params["layers"],
          jnp.arange(pools[0].shape[0], dtype=jnp.int32))
    (h,), cols = lax.scan(layer_step, (h,), xs)
    logits = llama_logits(params, h, config, out_dtype=jnp.float32)
    if tp is not None:
        # full-vocab logits on every rank (exact concat) so the argmax /
        # accept / commit_len below — and hence the commit kernel each
        # rank drives on its pool shard — are rank-identical
        logits = _tp_gather_logits(logits, tp)
    # per-row finite screen: the engine sees tokens, not logits, so the
    # poison/quarantine contract needs the flag computed here
    out, fin_ok = greedy_head(logits)                          # [B,T] each
    fin_ok = fin_ok.all(axis=1)                                # [B]
    # longest prefix where base argmax == draft proposal (both within
    # the live window), then the base's correction token
    if T > 1:
        match = ((out[:, :-1] == fed[:, 1:])
                 & (jnp.arange(1, T, dtype=jnp.int32)[None, :]
                    < t_live[:, None]))
        accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                           axis=1)
    else:
        accepted = jnp.zeros((B,), jnp.int32)
    commit_len = jnp.where(t_live > 0, accepted + 1, 0).astype(jnp.int32)
    commit = (paged_verify_commit if len(pools) == 2
              else paged_verify_commit_quant)
    return (out, commit_len, fin_ok,
            *commit(*cols, *pools, tables, qstart, commit_len))


# ---------------------------------------------------------------------------
# what InferenceEngine asks of a model: the paged programs, placement, draft
# ---------------------------------------------------------------------------

# KV/scale pools [L, NP, NKV*HD|NKV, bs] shard their kv-head-major axis
# 2 across 'mp' — each rank runs the unchanged paged kernels (and their
# shape-priced _fit_* fitters) on its head shard with the SAME
# rank-replicated block tables, so BlockPool / PrefixCache / the commit
# schedule stay host-side and rank-agnostic.
_TP_POOL_SPEC = P(None, None, "mp", None)


def _tp_specs(config: LlamaConfig, mesh: Mesh):
    """(param pspec tree, ``tp`` tuple) for a serving island: weights
    sliced per param_pspecs over 'mp' alone (no fsdp inside the serving
    mesh). The trees only match PLAIN param arrays — ``LlamaServing.place``
    rejects fused/int8 weight dicts under TP."""
    n = int(mesh.shape["mp"])
    return param_pspecs(config, ParallelConfig(mp=n)), ("mp", n)


# kind: (the step ``fn(params, pools, *inputs, config, tp)``, the jitted
# name's stem, how many inputs follow the cache, how many logits arrays
# lead the step's outputs: the jitted program puts the greedy head on each
# (``ops/sampling.py``), so a token and a finite flag a row are what it
# returns; verify holds the head inside itself, its accept rule needs the
# tokens; and where the rows' tokens sit among the inputs: what the serving
# program's board replaces, ``ops/sampling.py`` ``with_board``)
_PAGED_STEPS = {
    "decode": (llama_paged_decode_step, "paged_decode_step", 3, 1, 2),
    "prefill": (llama_paged_prefill_chunk, "paged_prefill_chunk", 4, 1,
                None),
    "prefill+decode": (llama_paged_prefill_chunk_with_decode,
                       "paged_prefill_chunk_with_decode", 7, 2, 6),
    "verify": (llama_paged_verify_step, "paged_verify_step", 4, 0, None),
}


@functools.lru_cache(maxsize=128)
def _jitted_paged_step(kind, frozen, quant, mesh, board=False):
    """The jitted paged program of ``kind`` (a key of ``_PAGED_STEPS``),
    ``fn(params, *pools, *inputs) -> (*heads, *pools)`` with the pools
    donated: two pools, or with ``quant`` the int8 four (``_int8`` in the
    jitted name). ``heads`` are never logits: ``decode`` returns (tokens
    [B] i32, finite [B] bool), ``prefill`` (token [], finite []),
    ``prefill+decode`` the chunk's pair and then the rows', ``verify`` (out,
    commit_len, fin_ok). With ``board`` (never ``verify``) the serving
    program the engine launches: it also takes the previous program's token
    board and returns its own last (``ops/sampling.py`` ``with_board``).
    Under a ``mesh`` (``_tp``) the step runs inside one fully-manual
    shard_map island (the paged Pallas kernels cannot be auto-partitioned
    under GSPMD), every pool sharded by ``_TP_POOL_SPEC``;
    the island gathers its vocab-sharded logits (exact concat, as verify
    does) before the head, so the tokens are rank-identical, those of one
    chip's argmax, and leave replicated.
    Call it with its arguments by position: they are the cache's key."""
    step, stem, n_inputs, n_logits, toks_at = _PAGED_STEPS[kind]
    config = LlamaConfig(*frozen)
    n_pools = 4 if quant else 2
    pspecs, tp = (None, None) if mesh is None else _tp_specs(config, mesh)

    def run(params, *args):
        out = step(params, args[:n_pools], *args[n_pools:], config, tp)
        if tp is not None:
            out = (*(_tp_gather_logits(x, tp) for x in out[:n_logits]),
                   *out[n_logits:])
        out = sampled(out, n_logits)
        return out[:-n_pools], out[-n_pools:]

    if mesh is not None:
        pool_specs = (_TP_POOL_SPEC,) * n_pools
        run = shard_map(
            run, mesh=mesh,
            in_specs=(pspecs, *pool_specs, *(P(),) * n_inputs),
            out_specs=(P(), pool_specs), check_vma=False)

    def fn(params, *args):
        heads, pools = run(params, *args)
        return (*heads, *pools)
    if board:
        fn = with_board(kind, fn, n_pools, toks_at)
    fn.__name__ = (stem + ("_int8" if quant else "")
                   + ("_tp" if mesh is not None else ""))
    return jax.jit(fn, donate_argnums=tuple(range(1, 1 + n_pools)))


# chipbench/families/llama.py ``aot_programs`` reads these two names; they go
# once a benchmark PR points it at _jitted_paged_step (ROADMAP D1)
_jitted_paged_decode = lambda frozen: _jitted_paged_step(  # noqa: E731
    "decode", frozen, False, None)
_jitted_paged_prefill = lambda frozen: _jitted_paged_step(  # noqa: E731
    "prefill", frozen, False, None)


class LlamaServing:
    """What ``InferenceEngine`` asks of a model, chosen by the config's type
    (``engine._serving_for``): the frozen config its jitted programs are keyed
    by, the cache arrays (a tuple, each indexed by block id on axis 1), and
    the jitted programs ``fn(params, *cache, ...) -> (*heads, *cache[,
    counts])`` by ``kind``. **No program hands the engine logits**: the
    greedy head (``ops/sampling.py`` ``greedy_head``: first index of the
    maximum, and whether the row's logits are all finite) runs inside the
    jitted program, and ``heads`` are, for ``prefill`` (one chunk of one
    prompt): (token [] i32, finite [] bool) of the last live token;
    ``decode`` (one token a running row): (tokens [B] i32, finite [B]
    bool); ``verify`` (speculation): (out, commit_len, fin_ok); and, where
    the model offers it, ``prefill+decode`` (a chunk with the decode batch
    riding it: ``fn(params, *cache, <the chunk's inputs>, <the
    batch's>)``): the chunk's pair, then the rows'. Those three, as
    ``step_fn`` hands them out, also take the previous program's token
    board (and, with rows, each row's index into it) after their inputs
    and return their own board last (``ops/sampling.py`` ``with_board``);
    ``board=False`` gives the plain program (speculation's draft). The
    un-jitted step functions return logits, for the parity tests.
    ``step_fn`` returns None for a kind it
    does not offer, and the engine then runs the iteration with the
    programs it has. ``work`` names the registry counters a model adds to
    the engine's ``_WORK_TOTALS`` (none here). A model that does not
    ``refuse`` them also answers ``place`` (``mp > 1``) and ``draft``
    (speculation). ``prepare(params, config)`` gives the tree the programs
    run on (on one chip; ``place`` takes the tree as given). This one is
    Llama's: every program is ``_jitted_paged_step``'s; an int8 cache is
    (k, v, k_scale, v_scale)."""

    work: Dict[str, str] = {}

    @staticmethod
    def refuse(**features) -> None:
        """Raise for a serving feature this model cannot run: none."""

    @staticmethod
    def freeze(config: LlamaConfig):
        return _freeze_config(config)

    @staticmethod
    def init_cache(config, num_blocks: int, block_size: int,
                   kv_dtype: str) -> Tuple:
        kv = init_paged_kv_pool(config, num_blocks, block_size,
                                kv_dtype=kv_dtype)
        if kv_dtype == "int8":
            kv += init_paged_kv_scales(config, num_blocks, block_size)
        return kv

    @staticmethod
    def prepare(params, config: LlamaConfig):
        """The tree the paged programs run on, made once when the engine
        takes the weights: ``_decode_weights``' form, the q/k/v stacks
        fused and transposed to ``qkv_proj: {"wT": [L, (nh + 2 nkv) hd,
        H]}`` (three ``wT`` leaves where the head ratio does not split;
        int8 ``{"w", "s"}`` become ``{"wT", "s"}``) in one jitted call that
        donates nothing, every other leaf the caller's own array. Sliced out
        of an [L, in, out] stack, each layer's projection was relaid into
        the layout its matmul reads in every program. A tree that is
        prepared already, or lacks one of the three, comes back as is."""
        layers = params.get("layers", {}) if isinstance(params, dict) else {}
        if _in_decode_form(layers) or not all(n in layers for n in _QKV):
            return params
        ratio = config.num_attention_heads // config.num_key_value_heads
        rest = {k: v for k, v in layers.items() if k not in _QKV}
        return dict(params, layers={
            **rest, **_relay_qkv({n: layers[n] for n in _QKV}, ratio)})

    @staticmethod
    def step_fn(kind: str, frozen, quant: bool, mesh, board: bool = True):
        # ROADMAP S10: the builder makes the chunk that carries the batch for
        # every cache and mesh, but only the plain cache on one chip has a
        # cell that prices it; this line goes in the PR that brings the others'
        if kind == "prefill+decode" and (quant or mesh is not None):
            return None
        return _jitted_paged_step(kind, frozen, quant, mesh,
                                  board and kind != "verify")

    # the default draft of speculation, (draft_params, draft_config): its
    # frozen config and its model-dtype cache come from the two above
    draft = staticmethod(make_draft_model)

    @staticmethod
    def place(mp: int, params, config: LlamaConfig, kv: Tuple,
              draft_params=None, draft_config=None, kv_draft: Tuple = ()):
        """Build the ('mp',) serving mesh and place weights and pools on it.

        Weight slicing follows ``param_pspecs`` over 'mp' alone
        (column-parallel q/k/v/gate/up, row-parallel o/down, vocab-
        parallel embed + lm_head); every KV/scale pool — fp16, int8 and
        draft — shards its kv-head-major axis 2. Rejects geometries the
        contiguous-head slicing cannot express (see PARITY.md PR 19).
        Returns (mesh, params, kv, draft_params, kv_draft)."""
        c = config
        for dim, name in ((c.num_attention_heads, "num_attention_heads"),
                          (c.num_key_value_heads, "num_key_value_heads"),
                          (c.vocab_size, "vocab_size"),
                          (c.intermediate_size, "intermediate_size")):
            if dim % mp:
                raise ValueError(
                    f"ServeConfig.mp={mp} needs {name} % mp == 0 "
                    f"(got {dim}): heads/vocab/ffn slice contiguously "
                    f"across ranks")
        ndev = len(jax.devices())
        if ndev < mp:
            raise ValueError(f"ServeConfig.mp={mp} needs {mp} devices, "
                             f"have {ndev}")
        if "qkv_proj" in params.get("layers", {}):
            raise ValueError(
                "tensor-parallel serving needs split q/k/v projections; "
                "fused qkv_proj weights interleave heads and cannot "
                "slice contiguously over 'mp'")
        for tree in (params, draft_params or {}):
            for leaf in jax.tree_util.tree_leaves(
                    tree, is_leaf=lambda x: isinstance(x, dict) and
                    ("w" in x or "wT" in x)):
                if isinstance(leaf, dict):
                    raise ValueError(
                        "tensor-parallel serving takes plain weight "
                        "arrays; int8/transposed weight dicts don't "
                        "carry the param_pspecs tree")
        mesh = make_mesh(ParallelConfig(mp=mp))

        def put(tree, cfg):
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), _tp_specs(cfg, mesh)[0],
                is_leaf=lambda x: isinstance(x, P))
            return jax.device_put(tree, shardings)

        pool_sh = NamedSharding(mesh, _TP_POOL_SPEC)
        return (mesh, put(params, c),
                tuple(jax.device_put(a, pool_sh) for a in kv),
                draft_params and put(draft_params, draft_config),
                tuple(jax.device_put(a, pool_sh) for a in kv_draft))


def generate_scan(params, cache, first_token, num_tokens,
                  config: LlamaConfig):
    """Generate ``num_tokens`` greedily INSIDE one jit: lax.scan over decode
    steps, so a whole generation is a single device dispatch (no host
    round-trip per token).

    first_token: [B, 1] int32 (normally argmax of the prefill logits).
    Returns (tokens [B, num_tokens], cache).
    """
    params = _decode_weights(params, config)

    def step(carry, _):
        cache, tok = carry
        logits, cache = llama_decode_step(params, cache, tok, config)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return (cache, nxt), nxt[:, 0]

    (cache, _), toks = lax.scan(step, (cache, first_token),
                                None, length=num_tokens)
    return toks.T, cache


def sample_logits(logits, key, temperature=1.0, top_k=0, top_p=1.0):
    """One sampling step on [B, vocab] fp32 logits (ref: the reference's
    sampling decode — paddle top_k/top_p generation). top_k=0 disables the
    k cut; top_p=1.0 disables the nucleus cut; both compose (k first, then
    p over the surviving mass, reference order). Runs INSIDE jit (all
    branches static)."""
    z = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k < z.shape[-1]:
        kth = jnp.sort(z, axis=-1)[:, -top_k][:, None]
        z = jnp.where(z < kth, -jnp.inf, z)
    # nucleus cut, traced-top_p-safe: keep the smallest prefix with mass
    # >= top_p (the token crossing the threshold stays — reference
    # semantics); top_p >= 1.0 keeps everything (cut lands on -inf tail)
    sorted_z = jnp.sort(z, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_z, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cut = jnp.sum(cum < top_p, axis=-1, keepdims=True)
    cut = jnp.minimum(cut, z.shape[-1] - 1)
    thresh = jnp.take_along_axis(sorted_z, cut, axis=-1)
    z = jnp.where(z < thresh, -jnp.inf, z)
    return jax.random.categorical(key, z, axis=-1).astype(jnp.int32)


def sample_scan(params, cache, first_logits, num_tokens, config, key,
                temperature=1.0, top_k=0, top_p=1.0):
    """Sampling counterpart of generate_scan: the whole continuation is one
    device dispatch; the PRNG key splits per step inside the scan."""
    params = _decode_weights(params, config)

    def step(carry, _):
        cache, tok, key = carry
        key, sub = jax.random.split(key)
        logits, cache = llama_decode_step(params, cache, tok, config)
        nxt = sample_logits(logits, sub, temperature, top_k, top_p)[:, None]
        return (cache, nxt, key), nxt[:, 0]

    key, sub = jax.random.split(key)
    first = sample_logits(first_logits, sub, temperature, top_k,
                          top_p)[:, None]
    (cache, _, _), toks = lax.scan(step, (cache, first, key),
                                   None, length=num_tokens - 1)
    return jnp.concatenate([first, toks.T], axis=1), cache


def sample_generate(params, prompt_ids, config: LlamaConfig, max_new_tokens,
                    temperature=1.0, top_k=0, top_p=1.0, seed=0,
                    max_len=None):
    """Sampling generation with the same one-dispatch structure as
    greedy_generate (prefill fills the cache, the continuation is a single
    compiled scan). Deterministic for a fixed seed."""
    bucket = generate_scan_bucket(max_new_tokens + 1)  # all sampled steps
    prompt, logits, cache, frozen = _prefill_for_generate(
        params, prompt_ids, config, max_new_tokens, max_len,
        bucket, "sample_generate")
    if logits is None:
        return np.zeros((prompt.shape[0], 0), np.int32)
    key = jax.random.PRNGKey(seed)
    # temperature/top_p ride as TRACED scalars (shape-neutral): varying
    # them per request reuses one compiled scan; only top_k is static
    # (it sizes the sort cut)
    toks, _ = _jitted_sample(frozen, bucket, int(top_k))(
        params, cache, logits, key, jnp.float32(temperature),
        jnp.float32(top_p))
    return np.asarray(toks)[:, :max_new_tokens]


@functools.lru_cache(maxsize=32)
def _jitted_sample(frozen, num_tokens, top_k):
    config = LlamaConfig(*frozen)

    def sample_scan_fn(params, cache, first_logits, key, temperature, top_p):
        return sample_scan(params, cache, first_logits, num_tokens, config,
                           key, temperature, top_k, top_p)
    sample_scan_fn.__name__ = "sample_scan"
    return jax.jit(sample_scan_fn, donate_argnums=(1,))


def _prefill_for_generate(params, prompt_ids, config, max_new_tokens,
                          max_len, extra_len, caller):
    """Shared generation preamble: validation, cache sizing, prefill.
    Returns (prompt, logits, cache, frozen) or a [B, 0] early result."""
    prompt = np.asarray(prompt_ids)
    b, plen = prompt.shape
    if plen == 0:
        raise ValueError(f"{caller}: prompt must be non-empty")
    if max_new_tokens <= 0:
        return prompt, None, None, None
    max_len = max_len or (plen + max_new_tokens)
    if max_len < plen + max_new_tokens:
        raise ValueError(
            f"{caller}: max_len={max_len} < prompt {plen} + "
            f"max_new_tokens {max_new_tokens}; the cache would overflow")
    frozen = _freeze_config(config)
    # 128-ALIGNED cache extents: the fused Pallas attend+update decode
    # kernel (ops/decode_attention.py) needs them, and its pos-clamped
    # DMA never reads the padding. (The XLA einsum FALLBACK prefers
    # ragged extents — aligned ones re-introduce a V-slice relayout
    # copy, 1.90 vs 2.52 ms/step at hd64 b8 — but the fallback only
    # runs when a caller forces a non-128-multiple max_len. PARITY.md
    # r5 decode notes have the full story.)
    cache_len = -(-max(max_len, plen + extra_len) // 128) * 128
    cache = init_kv_cache(config, b, cache_len)
    logits, cache = _jitted_prefill(frozen)(params, cache,
                                            jnp.asarray(prompt))
    return prompt, logits, cache, frozen


def greedy_generate(params, prompt_ids, config: LlamaConfig, max_new_tokens,
                    max_len=None):
    """Greedy decoding: one batched prefill pass fills the KV cache (one
    compile per distinct prompt length), then the whole continuation runs as
    a single compiled lax.scan dispatch (generate_scan). num_tokens is
    bucketed to powers of two so sweeping max_new_tokens doesn't recompile
    per value; both jitted wrappers donate the cache for in-place k/v."""
    n_cont = max_new_tokens - 1
    bucket = generate_scan_bucket(max_new_tokens)
    prompt, logits, cache, frozen = _prefill_for_generate(
        params, prompt_ids, config, max_new_tokens, max_len,
        bucket, "greedy_generate")
    if logits is None:
        return np.zeros((prompt.shape[0], 0), np.int32)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    if max_new_tokens == 1:
        return np.asarray(first)
    toks, cache = _jitted_generate(frozen, bucket)(params, cache, first)
    return np.concatenate([np.asarray(first), np.asarray(toks)[:, :n_cont]],
                          axis=1)


def generate_scan_bucket(max_new_tokens: int) -> int:
    """Number of decode-scan steps greedy_generate compiles for: the
    continuation length (max_new_tokens - 1, the first token comes from
    prefill) rounded UP to a power of two, so nearby values share one
    executable; extra steps run past the last wanted token (sequential
    scan) and the output is sliced. Benchmarks divide the scan's device
    time by this."""
    n_cont = max_new_tokens - 1
    return 1 << (n_cont - 1).bit_length() if n_cont > 0 else 0


def _freeze_config(config):
    return dataclasses.astuple(config)


@functools.lru_cache(maxsize=32)
def _jitted_prefill(frozen):
    config = LlamaConfig(*frozen)

    # a NAMED wrapper (not functools.partial, which loses __name__): the
    # profiler device span must read jit_llama_prefill / jit_generate_scan
    # so benchmarks can time the phases separately (bench.run_decode)
    def llama_prefill_fn(params, cache, ids):
        return llama_prefill(params, cache, ids, config=config)
    llama_prefill_fn.__name__ = "llama_prefill"
    return jax.jit(llama_prefill_fn, donate_argnums=(1,))


@functools.lru_cache(maxsize=32)
def _jitted_generate(frozen, num_tokens):
    config = LlamaConfig(*frozen)

    def generate_scan_fn(params, cache, first):
        return generate_scan(params, cache, first, num_tokens, config)
    generate_scan_fn.__name__ = "generate_scan"
    return jax.jit(generate_scan_fn, donate_argnums=(1,))


# ---------------------------------------------------------------------------
# compiled SPMD train step
# ---------------------------------------------------------------------------

def make_mesh(parallel: ParallelConfig, devices=None) -> Mesh:
    from ..distributed.fleet.topology import _pick_devices
    n = parallel.total
    devs = list(devices) if devices is not None else _pick_devices(n)
    arr = np.array(devs[:n]).reshape(parallel.dp, parallel.pp,
                                     parallel.sharding, parallel.sep,
                                     parallel.mp)
    return Mesh(arr, axis_names=("dp", "pp", "sharding", "sep", "mp"))


def _adamw_init(params, multi_precision=True):
    """multi_precision=True (reference default) keeps f32 moments for
    every param; False stores moments in each param's own dtype, halving
    optimizer HBM streaming on bf16 stacks. The update always COMPUTES
    in f32 (see _adamw_update) — only the stored state narrows."""
    def mdtype(p):
        return jnp.float32 if multi_precision else p.dtype
    return {
        "m": jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, mdtype(p)), params),
        "v": jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, mdtype(p)), params),
        "t": jnp.zeros((), jnp.float32),
    }


def _adamw_update(params, grads, state, lr, b1=0.9, b2=0.95, eps=1e-8, wd=0.1,
                  masks=None):
    """``masks`` (optional) is a pytree shaped like ``params`` whose leaves
    are either None (update normally) or a bool array broadcastable over
    the leaf's LEADING dims — False rows freeze: param AND moments pass
    through bitwise-unchanged (select, not a zero-grad update, so frozen
    moments do not decay and no moment read-modify-write bandwidth is
    spent on them under XLA's fusion). PR 10 uses this with the
    per-expert ``moe_expert_rows`` stats so only experts that actually
    routed tokens this step stream their f32 AdamW moments; touched rows
    are bitwise-identical to the unmasked update. The shared step count
    ``t`` (and thus the bias-correction powers) still advances globally —
    the standard lazy/sparse-Adam semantics."""
    t = state["t"] + 1

    def upd(p, g, m, v, mask):
        g32 = g.astype(jnp.float32)
        # compute in f32; store back in the state's dtype (f32 under
        # multi_precision — a no-op cast, bit-identical to the old path)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * g32
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
        m_hat = m_new / (1 - b1 ** t)
        v_hat = v_new / (1 - b2 ** t)
        p32 = p.astype(jnp.float32)
        p_new = p32 - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p32)
        if mask is not None:
            keep = mask.reshape(mask.shape + (1,) * (p.ndim - mask.ndim))
            # select (not multiply): frozen rows must be BITWISE the old
            # values (f32<->storage round-trips are exact)
            p_new = jnp.where(keep, p_new, p32)
            m_new = jnp.where(keep, m_new, m.astype(jnp.float32))
            v_new = jnp.where(keep, v_new, v.astype(jnp.float32))
        return p_new.astype(p.dtype), m_new.astype(m.dtype), \
            v_new.astype(v.dtype)

    flat_p, tree = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(state["m"])
    flat_v = jax.tree_util.tree_leaves(state["v"])
    if masks is None:
        flat_k = [None] * len(flat_p)
    else:
        flat_k = jax.tree_util.tree_flatten(
            masks, is_leaf=lambda x: x is None)[0]
    out = [upd(p, g, m, v, kp) for p, g, m, v, kp
           in zip(flat_p, flat_g, flat_m, flat_v, flat_k)]
    new_p = jax.tree_util.tree_unflatten(tree, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(tree, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(tree, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "t": t}


def build_train_step(config: LlamaConfig, parallel: ParallelConfig,
                     mesh: Optional[Mesh] = None, lr: float = 3e-4,
                     seed: int = 0):
    """Returns (step_fn, params, opt_state). step_fn(params, opt, ids, labels)
    -> (params, opt, loss), jit-compiled over the mesh with full dp/mp/
    sharding/sep/pp shardings. ids/labels: [B, S] int32 host arrays.
    """
    if mesh is None and parallel.total > 1:
        mesh = make_mesh(parallel)
    use_flash = parallel.use_flash
    if use_flash is None:
        from ..ops._common import interpret_mode
        use_flash = not interpret_mode()

    if parallel.sep > 1:
        # validate the strategy (env or config field) BEFORE any tracing so
        # a typo'd PADDLE_TPU_SEP_STRATEGY fails with the variable named,
        # not deep inside the shard_map island
        from ..parallel.ulysses_attention import resolve_sep_strategy
        if (resolve_sep_strategy(parallel.sep_strategy) == "ulysses"
                and config.num_attention_heads % parallel.sep):
            raise ValueError(
                f"ulysses sep strategy needs num_heads % sep == 0 for the "
                f"all-to-all head split; got num_heads="
                f"{config.num_attention_heads}, sep={parallel.sep}. Pick a "
                f"sep degree dividing the head count or select the ring "
                f"strategy (sep_strategy='ring' / PADDLE_TPU_SEP_STRATEGY="
                f"ring).")

    params = init_llama_params(config, seed)
    pspecs = param_pspecs(config, parallel)

    if parallel.pp > 1:
        return _build_pp_train_step(config, parallel, mesh, params, pspecs,
                                    lr, use_flash)

    opt_specs = opt_state_pspecs(config, parallel, pspecs)
    if mesh is not None:
        params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, pspecs, is_leaf=lambda x: not isinstance(x, dict))
    opt_state = _adamw_init(params)
    if mesh is not None:
        opt_state["m"] = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            opt_state["m"], opt_specs, is_leaf=lambda x: not isinstance(x, dict))
        opt_state["v"] = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            opt_state["v"], opt_specs, is_leaf=lambda x: not isinstance(x, dict))

    needs_shard_map = parallel.sep > 1

    def loss_fn(p, ids, labels):
        if needs_shard_map:
            from jax import shard_map
            # FULLY manual island: 'sep' (ring attention does explicit
            # ppermute) and the batch axes carry real sharding; a dp-
            # sharded batch entering a manual region on an AUTO axis
            # CHECK-fails XLA's SPMD group expansion (spmd_partitioner_
            # util.cc:495, seen at the dp2·sep2·mp2 factoring), and any
            # leftover auto axis turns lax.axis_index into a PartitionId
            # instruction the SPMD partitioner rejects as UNIMPLEMENTED.
            # mp-sharded params enter on P() specs, i.e. gathered at the
            # boundary and computed replicated across mp inside — the
            # sep>1 factorings trade TP inside this island for a working
            # partition (the pp path keeps explicit TP via tp_axis).
            batch_axes = _act_spec(parallel)[0]
            if isinstance(batch_axes, str):  # P collapses 1-tuples
                batch_axes = (batch_axes,)
            manual = frozenset(mesh.axis_names)
            sep_only = jax.tree_util.tree_map(
                lambda _: P(), pspecs, is_leaf=lambda x: isinstance(x, P))
            smap = shard_map(
                functools.partial(llama_loss, config=config, parallel=parallel,
                                  mesh=None, use_flash=use_flash,
                                  in_shard_map=True,
                                  loss_psum_axes=("sep",) + tuple(batch_axes)),
                mesh=mesh,
                in_specs=(sep_only, P(batch_axes, "sep"),
                          P(batch_axes, "sep")),
                out_specs=P(),
                axis_names=manual,
                check_vma=False)
            with _obs.comm_span("llama.sep_island",
                                nbytes=ids.size * ids.dtype.itemsize,
                                site="llama.sep_island"):
                return smap(p, ids, labels)
        return llama_loss(p, ids, labels, config, parallel, mesh,
                          use_flash=use_flash)

    def step(p, opt, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
        if mesh is not None:
            # pin grads to the PARAM specs: the backward layer-scan
            # otherwise accumulates stacked-layer grads in whatever
            # sharding propagation picked (an L-dim split, observed as
            # "[SPMD] Involuntary full rematerialization ... %fake_
            # parameter f32[L,H,H]" in the r4 dryrun) and pays a
            # replicate-and-reslice at the optimizer boundary; the
            # constraint propagates into the while-loop state so the
            # accumulator is laid out like the update wants it
            grads = jax.tree_util.tree_map(
                lambda g, s: lax.with_sharding_constraint(
                    g, NamedSharding(mesh, s)),
                grads, pspecs, is_leaf=lambda x: not isinstance(x, dict))
        new_p, new_opt = _adamw_update(p, grads, opt, lr)
        return new_p, new_opt, loss

    batch_sharding = (NamedSharding(mesh, P(_act_spec(parallel)[0], None))
                      if mesh is not None else None)
    jit_step = jax.jit(step, donate_argnums=(0, 1))

    def step_fn(p, opt, ids, labels):
        ids = jnp.asarray(ids, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        if batch_sharding is not None:
            ids = jax.device_put(ids, batch_sharding)
            labels = jax.device_put(labels, batch_sharding)
        return jit_step(p, opt, ids, labels)

    # the compiled program itself, for callers that lower it (AOT compile
    # checks, "is the kernel in the HLO" assertions)
    step_fn.jitted = jit_step
    return step_fn, params, opt_state


def _build_pp_train_step(config, parallel, mesh, params, pspecs, lr, use_flash):
    """Pipeline path: stage-stacked params sharded over 'pp', collective
    schedule via shard_map + ppermute (parallel/pipeline.py design), every
    mesh axis manual inside the island (batch axes handled by explicit loss
    psums — see manual_axes below)."""
    from jax import shard_map
    c = config
    S = parallel.pp
    L = c.num_hidden_layers
    assert L % S == 0, (L, S)
    per = L // S
    M = max(parallel.microbatches, S)

    # reshape stacked layers [L, ...] -> [S, per, ...] and shard axis0 on 'pp'
    def restage(a):
        return a.reshape((S, per) + a.shape[1:])

    params = dict(params)
    params["layers"] = jax.tree_util.tree_map(restage, params["layers"])
    layer_specs = jax.tree_util.tree_map(
        lambda s: P(*(("pp",) + tuple(s))), pspecs["layers"],
        is_leaf=lambda x: isinstance(x, P))
    pspecs = dict(pspecs)
    pspecs["layers"] = layer_specs

    if mesh is not None:
        params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, pspecs, is_leaf=lambda x: not isinstance(x, dict))
    opt_state = _adamw_init(params)
    if mesh is not None:
        for key in ("m", "v"):
            opt_state[key] = jax.tree_util.tree_map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                opt_state[key], pspecs, is_leaf=lambda x: not isinstance(x, dict))

    act = _act_spec(parallel)
    batch_axes = act[0]
    if isinstance(batch_axes, str):  # P collapses 1-tuples
        batch_axes = (batch_axes,)
    tp_axis = "mp" if parallel.mp > 1 else None
    sep_on = parallel.sep > 1
    loss_psum_axes = (("sep",) if sep_on else ()) + tuple(batch_axes)

    def stage_fn(stage_params, h, cos, sin):
        body = functools.partial(decoder_layer, config=c, parallel=parallel,
                                 mesh=None, use_flash=use_flash,
                                 tp_axis=tp_axis, in_shard_map=sep_on)
        def scan_body(hh, p):
            return body(p, hh, cos, sin), None
        if parallel.remat:
            scan_body = jax.checkpoint(scan_body, policy=_remat_policy(parallel))
        h, _ = lax.scan(scan_body, h, stage_params)
        return h

    def pipelined_loss(p, ids, labels):
        # inside shard_map: manual over 'pp' (and batch axes for psums).
        # With sep>1 ids/labels arrive sequence-sharded: [B, S_local].
        b, s = ids.shape
        s_total = s * (parallel.sep if sep_on else 1)
        cos, sin = build_rope_cache(s_total, c.head_dim, base=c.rope_theta)
        if sep_on:
            idx = lax.axis_index("sep") * s
            cos = lax.dynamic_slice_in_dim(cos, idx, s, 0)
            sin = lax.dynamic_slice_in_dim(sin, idx, s, 0)
        h = jnp.take(p["embed"], ids, axis=0).astype(c.dtype)
        from ..parallel.pipeline import microbatch, pipeline_apply, last_stage_value
        h_mb = microbatch(h, M)

        pipe = pipeline_apply(
            lambda sp, hh: stage_fn(sp, hh, cos, sin), S, M, "pp",
            remat=False,  # remat already inside stage scan
            overlap_p2p=parallel.overlap_p2p)
        out_mb = pipe(p["layers"], h_mb)
        h_out = out_mb.reshape(b, s, c.hidden_size)
        logits = llama_logits(p, h_out, c).astype(jnp.float32)
        loss = masked_ce_loss(logits, labels, psum_axes=loss_psum_axes)
        return last_stage_value(loss, S, "pp")

    # FULLY manual island: 'pp' (ppermute schedule), 'mp' (explicit Megatron
    # psums), 'sep' (ring attention's ppermute), AND the batch axes. Mixing
    # manual and auto axes fails twice over: auto mp/sep collectives crash
    # XLA's SPMD group expansion (spmd_partitioner_util CHECK at 32 devices),
    # and ANY leftover auto axis makes lax.axis_index lower to a PartitionId
    # instruction the SPMD partitioner rejects as UNIMPLEMENTED. The batch
    # axes are handled like the sep path above: ids/labels enter batch-
    # sharded and masked_ce_loss psums token sum/count across them.
    manual_axes = frozenset(mesh.axis_names)

    def manual_spec(full_spec, lead_pp: bool):
        parts = ["pp"] if lead_pp else []
        for ax in (tuple(full_spec)[1:] if lead_pp else tuple(full_spec)):
            parts.append(ax if (ax == "mp" and tp_axis) else None)
        return P(*parts)

    pp_manual = jax.tree_util.tree_map(
        lambda s: manual_spec(s, lead_pp=False), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    pp_manual["layers"] = jax.tree_util.tree_map(
        lambda s: manual_spec(s, lead_pp=True), pspecs["layers"],
        is_leaf=lambda x: isinstance(x, P))
    # embed/final_norm/lm_head compute replicated across mp in the manual
    # region (their heavy math is outside the layer stack)
    pp_manual["embed"] = P()
    pp_manual["final_norm"] = P()
    if "lm_head" in pp_manual:
        pp_manual["lm_head"] = P()
    ids_spec = P(batch_axes, "sep" if sep_on else None)
    in_specs = (pp_manual, ids_spec, ids_spec)
    smap_loss = shard_map(pipelined_loss, mesh=mesh, in_specs=in_specs,
                          out_specs=P(), axis_names=manual_axes,
                          check_vma=False)

    def step(p, opt, ids, labels):
        def island(pp_, i, l):
            with _obs.comm_span("llama.pp_island",
                                nbytes=i.size * i.dtype.itemsize,
                                site="llama.pp_island"):
                return smap_loss(pp_, i, l)
        loss, grads = jax.value_and_grad(island)(p, ids, labels)
        new_p, new_opt = _adamw_update(p, grads, opt, lr)
        return new_p, new_opt, loss

    jit_step = jax.jit(step, donate_argnums=(0, 1))
    batch_sharding = NamedSharding(
        mesh, P(batch_axes, "sep" if sep_on else None))

    def step_fn(p, opt, ids, labels):
        ids = jax.device_put(jnp.asarray(ids, jnp.int32), batch_sharding)
        labels = jax.device_put(jnp.asarray(labels, jnp.int32), batch_sharding)
        return jit_step(p, opt, ids, labels)

    # the compiled program itself, for callers that lower it (AOT compile
    # checks, "is the kernel in the HLO" assertions)
    step_fn.jitted = jit_step
    return step_fn, params, opt_state


def count_params(config: LlamaConfig) -> int:
    c = config
    per_layer = (c.hidden_size * (c.num_attention_heads +
                                  2 * c.num_key_value_heads) * c.head_dim
                 + c.num_attention_heads * c.head_dim * c.hidden_size
                 + 3 * c.hidden_size * c.intermediate_size
                 + 2 * c.hidden_size)
    total = c.num_hidden_layers * per_layer + c.vocab_size * c.hidden_size \
        + c.hidden_size
    if not c.tie_word_embeddings:
        total += c.hidden_size * c.vocab_size
    return total


def train_flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """~6N + attention flops per token (fwd+bwd), for MFU accounting."""
    n = count_params(config)
    attn = 12 * config.num_hidden_layers * config.hidden_size * seq_len
    return 6.0 * n + attn


def beam_search_scan(params, cache, first_logits, num_tokens, config,
                     num_beams, length_penalty=0.0, eos_token_id=None):
    """Beam search INSIDE one jit (ref: the reference's BeamSearchDecoder /
    generation beam_search): beams ride the batch dim (B*K rows), the KV
    cache is gathered to each step's surviving parents, and the token/
    parent history is emitted per step and assembled by the gather_tree
    backtrack at the end. Returns (sequences [B, K, num_tokens], scores
    [B, K]) sorted best-first per batch row.

    first_logits: [B, V] prefill logits. cache: prefilled for B rows;
    expanded to B*K here. eos_token_id: finished beams are extended only
    with EOS at zero extra cost and their score frozen (length_penalty
    applies as score / (len ** penalty), GNMT-style, at the end)."""
    b, v = first_logits.shape
    k = num_beams
    neg = jnp.float32(-1e9)

    # seed: top-k tokens of the prefill logits start the k beams
    logp0 = jax.nn.log_softmax(first_logits.astype(jnp.float32), axis=-1)
    cum, tok0 = lax.top_k(logp0, k)                      # [B, K] each
    # expand cache to B*K rows (beam-major within each batch row)
    def tile(a):
        return jnp.repeat(a, k, axis=1)
    cache = {"k": tile(cache["k"]), "v": tile(cache["v"]),
             "pos": cache["pos"]}

    def step(carry, _):
        cache, cum, tok, alive_len = carry
        logits, cache = llama_decode_step(
            params, cache, tok.reshape(b * k, 1).astype(jnp.int32), config)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logp = logp.reshape(b, k, v)
        if eos_token_id is not None:
            finished = tok == eos_token_id                 # [B, K]
            # finished beams: only EOS continues, at no cost
            only_eos = jnp.full((v,), neg).at[eos_token_id].set(0.0)
            logp = jnp.where(finished[..., None], only_eos[None, None], logp)
            alive_len = alive_len + (~finished)
        else:
            alive_len = alive_len + 1
        total = cum[..., None] + logp                      # [B, K, V]
        cum, flat = lax.top_k(total.reshape(b, k * v), k)  # [B, K]
        parent = (flat // v).astype(jnp.int32)             # [B, K]
        tok = (flat % v).astype(jnp.int32)
        # gather cache rows to the surviving parents
        rows = (jnp.arange(b, dtype=jnp.int32)[:, None] * k
                + parent).reshape(-1)
        cache = {"k": jnp.take(cache["k"], rows, axis=1),
                 "v": jnp.take(cache["v"], rows, axis=1),
                 "pos": cache["pos"]}
        alive_len = jnp.take_along_axis(alive_len, parent, axis=1)
        return (cache, cum, tok, alive_len), (tok, parent)

    alive0 = jnp.ones((b, k), jnp.int32)
    (cache, cum, _, alive_len), (toks, parents) = lax.scan(
        step, (cache, cum, tok0.astype(jnp.int32), alive0),
        None, length=num_tokens - 1)

    # assemble: history [T, B, K]; step 0's parents are the identity
    all_toks = jnp.concatenate([tok0.astype(jnp.int32)[None], toks], 0)
    id0 = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None, None],
                           (1, b, k))
    all_parents = jnp.concatenate([id0, parents], 0)
    from ..nn.functional.common import _gather_tree_impl
    seqs = _gather_tree_impl(all_toks, all_parents)        # [T, B, K]
    scores = cum / jnp.maximum(alive_len.astype(jnp.float32),
                               1.0) ** length_penalty
    # re-sort: the scan keeps beams ordered by raw cumulative logprob, but
    # the length penalty can reorder them (short finished vs long alive)
    order = jnp.argsort(-scores, axis=-1)
    scores = jnp.take_along_axis(scores, order, axis=-1)
    seqs = jnp.transpose(seqs, (1, 2, 0))                  # [B, K, T]
    seqs = jnp.take_along_axis(seqs, order[..., None], axis=1)
    return seqs, scores


def beam_search_generate(params, prompt_ids, config: LlamaConfig,
                         max_new_tokens, num_beams=4, length_penalty=0.0,
                         eos_token_id=None, max_len=None):
    """Beam-search generation: prefill once, then the whole search is a
    single compiled scan. Returns (sequences [B, num_beams,
    max_new_tokens], scores [B, num_beams]) best-first."""
    prompt, logits, cache, frozen = _prefill_for_generate(
        params, prompt_ids, config, max_new_tokens, max_len, 0,
        "beam_search_generate")
    if logits is None:
        b = prompt.shape[0]
        return (np.zeros((b, num_beams, 0), np.int32),
                np.zeros((b, num_beams), np.float32))
    # NO pow2 bucketing here: beam scores are sums over the emitted
    # sequence, so extra padded steps would change both scores and which
    # beams survive — each max_new_tokens compiles exactly
    seqs, scores = _jitted_beam(frozen, int(max_new_tokens),
                                int(num_beams), float(length_penalty),
                                eos_token_id)(params, cache, logits)
    return np.asarray(seqs), np.asarray(scores)


@functools.lru_cache(maxsize=32)
def _jitted_beam(frozen, num_tokens, num_beams, length_penalty,
                 eos_token_id):
    config = LlamaConfig(*frozen)

    def beam_scan_fn(params, cache, first_logits):
        return beam_search_scan(params, cache, first_logits, num_tokens,
                                config, num_beams, length_penalty,
                                eos_token_id)
    beam_scan_fn.__name__ = "beam_scan"
    # no donation: the cache is re-tiled to B*K rows inside the jit, so no
    # output matches the donated buffer (donating only warns uselessly)
    return jax.jit(beam_scan_fn)


def generate(params, prompt_ids, config: LlamaConfig, max_new_tokens=64,
             decode_strategy="greedy_search", temperature=1.0, top_k=0,
             top_p=1.0, num_beams=4, length_penalty=0.0, eos_token_id=None,
             seed=0, max_len=None):
    """Unified generation entry (ref: the reference generate API's
    decode_strategy dispatch): 'greedy_search' | 'sampling' |
    'beam_search'. Greedy/sampling return [B, max_new_tokens] token ids;
    beam search returns the best beam per batch row (use
    beam_search_generate directly for all beams + scores).
    eos_token_id is supported by the beam path only (the greedy/sampling
    scans have a fixed trip count) — passing it elsewhere raises rather
    than silently generating past EOS."""
    if eos_token_id is not None and decode_strategy != "beam_search":
        raise ValueError(
            "eos_token_id is only supported with "
            "decode_strategy='beam_search'")
    if decode_strategy == "greedy_search":
        return greedy_generate(params, prompt_ids, config, max_new_tokens,
                               max_len=max_len)
    if decode_strategy == "sampling":
        return sample_generate(params, prompt_ids, config, max_new_tokens,
                               temperature=temperature, top_k=top_k,
                               top_p=top_p, seed=seed, max_len=max_len)
    if decode_strategy == "beam_search":
        seqs, _ = beam_search_generate(params, prompt_ids, config,
                                       max_new_tokens, num_beams=num_beams,
                                       length_penalty=length_penalty,
                                       eos_token_id=eos_token_id,
                                       max_len=max_len)
        return seqs[:, 0]
    raise ValueError(
        f"unknown decode_strategy {decode_strategy!r}; expected "
        "'greedy_search', 'sampling', or 'beam_search'")
