"""ERNIE-style MoE transformer with expert parallelism (BASELINE config 5:
"ERNIE-MoE with Fleet expert-parallel + PipelineLayer").

Ref: the reference composes incubate MoELayer (gshard gate +
global_scatter/global_gather all-to-all) with fleet PP. TPU-native: the same
functional-core design as models/llama.py, with every even layer's FFN
replaced by a top-2 MoE block whose expert stack is sharded over the 'ep'
submesh — the dispatch einsum becomes XLA all-to-all over ICI.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.moe import (RATIO_STAT_KEYS, default_dispatch_mode,
                            moe_dispatch_combine, zero_routing_stats)
from ..ops.rms_norm import fused_rms_norm
from .llama import _adamw_init, _adamw_update


@dataclasses.dataclass
class ErnieMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    num_experts: int = 8
    moe_topk: int = 2
    # shared experts (fine-grained MoE, PR 10): dense FFN expert(s) every
    # token passes through IN ADDITION to its routed top-k experts —
    # one fused [H, n_shared*I] matmul pair, replicated across the mesh
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    moe_every: int = 2           # every k-th layer is MoE
    aux_loss_weight: float = 0.01
    max_position_embeddings: int = 512
    layer_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # "capacity" (reference drop parity, default) | "ragged" (dropless
    # grouped-GEMM, ep-replicated tokens + combine psum) | "ragged_a2a"
    # (dropless + tokens sharded over ep with the ragged all-to-all
    # dispatch, PR 10) | None -> PADDLE_TPU_MOE_DROPLESS env default
    dispatch_mode: Optional[str] = None

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def ernie_moe_tiny():
    return ErnieMoEConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                          num_hidden_layers=4, num_attention_heads=4,
                          num_experts=4, max_position_embeddings=128,
                          dtype=jnp.float32)


def ernie_moe_fine():
    """Fine-grained + shared-expert preset (PR 10): many SMALL experts
    (E=32, top-4, expert I = H/2) plus one always-on shared expert — the
    regime where routing skew is the norm and the ragged a2a dispatch
    matters most. Dispatches via "ragged_a2a" (tokens sharded over ep)."""
    return ErnieMoEConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=512, num_hidden_layers=8,
                          num_attention_heads=16, num_experts=32,
                          moe_topk=4, num_shared_experts=1,
                          max_position_embeddings=1024,
                          dtype=jnp.bfloat16, dispatch_mode="ragged_a2a")


def ernie_moe_fine_tiny():
    """CPU-sized ernie_moe_fine: same shape family (fine-grained experts,
    one shared expert, ragged_a2a dispatch) at dryrun/test scale."""
    return ErnieMoEConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=32, num_hidden_layers=4,
                          num_attention_heads=4, num_experts=8, moe_topk=2,
                          num_shared_experts=1, max_position_embeddings=128,
                          dtype=jnp.float32, dispatch_mode="ragged_a2a")


def init_params(config: ErnieMoEConfig, seed: int = 0):
    c = config
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 12)
    d = c.dtype
    std = 0.02
    L = c.num_hidden_layers
    E = c.num_experts

    def rnd(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(d)

    def shared_block(n):
        # keys derive from the (previously unused) ks[11] so adding
        # shared experts never perturbs the existing parameter draws
        sk1, sk2 = jax.random.split(ks[11])
        si = c.num_shared_experts * c.intermediate_size
        return {"s_w1": rnd(sk1, (n, c.hidden_size, si)),
                "s_w2": rnd(sk2, (n, si, c.hidden_size))}

    def attn_block(n, k1, k2):
        return {
            "ln1": jnp.ones((n, c.hidden_size), d),
            "qkv": rnd(k1, (n, c.hidden_size, 3 * c.hidden_size)),
            "o": rnd(k2, (n, c.hidden_size, c.hidden_size)),
            "ln2": jnp.ones((n, c.hidden_size), d),
        }

    if _split_stacks(c):
        # SPLIT stacks: dense layers carry ONLY dense FFN weights, MoE
        # layers ONLY expert weights. The old single [L, ...] layout
        # allocated e_w1/e_w2 for every layer (537M dead params at the
        # bench shape) whose f32 AdamW moments streamed ~15 GB of HBM
        # per step — the r4 "dispatch dominates" diagnosis was half the
        # story; the optimizer streaming dead state was the other half.
        n = L // 2
        layers = {
            "dense": {**attn_block(n, ks[1], ks[2]),
                      "w1": rnd(ks[3], (n, c.hidden_size,
                                        c.intermediate_size)),
                      "w2": rnd(ks[4], (n, c.intermediate_size,
                                        c.hidden_size))},
            "moe": {**attn_block(n, ks[9], ks[10]),
                    "gate": rnd(ks[5], (n, c.hidden_size, E))
                    .astype(jnp.float32),
                    "e_w1": rnd(ks[6], (n, E, c.hidden_size,
                                        c.intermediate_size)),
                    "e_w2": rnd(ks[7], (n, E, c.intermediate_size,
                                        c.hidden_size)),
                    **(shared_block(n) if c.num_shared_experts else {})},
        }
    else:
        layers = {
            **attn_block(L, ks[1], ks[2]),
            "w1": rnd(ks[3], (L, c.hidden_size, c.intermediate_size)),
            "w2": rnd(ks[4], (L, c.intermediate_size, c.hidden_size)),
            "gate": rnd(ks[5], (L, c.hidden_size, E)).astype(jnp.float32),
            "e_w1": rnd(ks[6], (L, E, c.hidden_size, c.intermediate_size)),
            "e_w2": rnd(ks[7], (L, E, c.intermediate_size, c.hidden_size)),
            **(shared_block(L) if c.num_shared_experts else {}),
        }
    return {
        "embed": rnd(ks[0], (c.vocab_size, c.hidden_size)),
        "pos": rnd(ks[8], (c.max_position_embeddings, c.hidden_size)),
        "layers": layers,
        "final_ln": jnp.ones((c.hidden_size,), d),
    }


def _split_stacks(config):
    """Split dense/moe layer stacks (see init_params) — the standard
    every-other-layer ERNIE layout."""
    return config.moe_every == 2 and config.num_hidden_layers % 2 == 0


def param_pspecs(config, ep_degree: int, dp_degree: int = 1):
    ep = "ep" if ep_degree > 1 else None
    attn = {
        "ln1": P(None, None),
        "qkv": P(None, None, None),
        "o": P(None, None, None),
        "ln2": P(None, None),
    }
    dense = {"w1": P(None, None, None), "w2": P(None, None, None)}
    moe = {
        "gate": P(None, None, None),
        "e_w1": P(None, ep, None, None),   # experts sharded over 'ep'
        "e_w2": P(None, ep, None, None),
    }
    if config.num_shared_experts:
        # shared experts run on every token on every rank: replicated
        moe["s_w1"] = P(None, None, None)
        moe["s_w2"] = P(None, None, None)
    if _split_stacks(config):
        layers = {"dense": {**attn, **dense}, "moe": {**attn, **moe}}
    else:
        layers = {**attn, **dense, **moe}
    return {"embed": P(None, None), "pos": P(None, None), "layers": layers,
            "final_ln": P(None)}


def _attn_and_norm(p, h, config: ErnieMoEConfig):
    c = config
    b, s, hid = h.shape
    nh, hd = c.num_attention_heads, c.head_dim
    x = fused_rms_norm(h, p["ln1"], c.layer_norm_eps)
    qkv = (x @ p["qkv"]).reshape(b, s, 3, nh, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    from ..ops._common import interpret_mode
    if interpret_mode():
        from ..nn.functional.attention import _xla_sdpa
        attn = _xla_sdpa(q, k, v, is_causal=True)
    else:
        from ..ops.flash_attention import flash_attention_bshd
        attn = flash_attention_bshd(q, k, v, causal=True)
    h = h + attn.reshape(b, s, hid) @ p["o"]
    return h, fused_rms_norm(h, p["ln2"], c.layer_norm_eps)


def _moe_ffn(p, x_, config: ErnieMoEConfig, use_onehot=False,
             mesh=None, with_stats=False, dispatch_mode="capacity"):
    c = config
    hid = x_.shape[-1]
    tokens = x_.reshape(-1, hid)

    def expert_fn(params, toks):
        w1, w2 = params
        return jax.nn.gelu(toks @ w1) @ w2

    if use_onehot and mesh is not None:
        # ep>1 with the SLOT schedule (r5): a fully-manual shard_map
        # island over (dp, ep) — each shard routes its local tokens,
        # gathers only its local experts' slots, and the combine psums
        # [T,D] partials over 'ep'. Capacity is per-dp-shard (the
        # reference's MoE also sizes capacity from the local batch);
        # with no drops this is numerically identical to serial, which
        # the ep-vs-serial tests assert. dispatch_mode="ragged" swaps
        # the local expert compute for the DROPLESS grouped-GEMM path
        # (moe_ragged_dispatch_local) — the combine psum is unchanged.
        # dispatch_mode="ragged_a2a" (PR 10) shards the TOKENS over ep
        # too and moves only each destination's actual rows via the
        # ragged all-to-all — no token replication, no combine psum.
        # The one-hot einsum fallback below stays for mesh-less callers.
        from jax import shard_map
        from ..parallel.moe import (moe_ragged_dispatch_a2a,
                                    moe_ragged_dispatch_local,
                                    moe_slot_dispatch_local)
        a2a = dispatch_mode == "ragged_a2a"
        tok_spec = P(("dp", "ep"), None) if a2a else P("dp", None)

        def island(tok, gate, w1, w2):
            logits = tok.astype(jnp.float32) @ gate
            if a2a:
                res = moe_ragged_dispatch_a2a(
                    tok, logits, w1, w2, c.num_experts,
                    axis_name="ep", k=c.moe_topk,
                    return_stats=with_stats)
            elif dispatch_mode == "ragged":
                res = moe_ragged_dispatch_local(
                    tok, logits, w1, w2, c.num_experts,
                    axis_name="ep", k=c.moe_topk,
                    return_stats=with_stats)
            else:
                res = moe_slot_dispatch_local(
                    tok, logits, expert_fn, (w1, w2), c.num_experts,
                    axis_name="ep", k=c.moe_topk,
                    capacity_factor=c.capacity_factor,
                    return_stats=with_stats)
            # aux is computed from LOCAL tokens: average over the axes
            # the tokens shard over so the P() out-spec is genuinely
            # replicated (per-shard balance loss, averaged)
            aux_axes = ("dp", "ep") if a2a else "dp"
            if with_stats:
                out, aux, st = res
                # stats are per-dp-shard (the local paths replicate
                # them over ep; the a2a path psums over ep inside):
                # counts sum over dp (whole-batch totals), ratio keys
                # average over dp
                st = {k_: (lax.pmean(v, "dp") if k_ in RATIO_STAT_KEYS
                           else lax.psum(v, "dp"))
                      for k_, v in st.items()}
                return out, lax.pmean(aux, aux_axes), st
            out, aux = res
            return out, lax.pmean(aux, aux_axes)

        stats_spec = jax.tree_util.tree_map(
            lambda _: P(), zero_routing_stats(dispatch_mode,
                                              c.num_experts))
        out_specs = ((tok_spec, P(), stats_spec) if with_stats
                     else (tok_spec, P()))
        res = shard_map(
            island, mesh=mesh,
            in_specs=(tok_spec, P(None, None),
                      P("ep", None, None), P("ep", None, None)),
            out_specs=out_specs,
            check_vma=False)(tokens, p["gate"], p["e_w1"], p["e_w2"])
        out, aux = res[0], res[1]
        stats = res[2] if with_stats else None
    else:
        logits = tokens.astype(jnp.float32) @ p["gate"]
        # mesh-less / ep=1 "ragged_a2a" degenerates to the serial ragged
        # path (the a2a combine is bitwise-equal to it by construction);
        # zero wire stats keep the key set consistent
        serial_mode = ("ragged" if dispatch_mode == "ragged_a2a"
                       else dispatch_mode)
        res = moe_dispatch_combine(tokens, logits, expert_fn,
                                   (p["e_w1"], p["e_w2"]),
                                   c.num_experts, k=c.moe_topk,
                                   capacity_factor=c.capacity_factor,
                                   use_onehot=use_onehot,
                                   return_stats=with_stats,
                                   dispatch_mode=serial_mode)
        out, aux = res[0], res[1]
        stats = res[2] if with_stats else None
        if stats is not None and dispatch_mode == "ragged_a2a":
            z = jnp.zeros((), jnp.float32)
            stats = {**stats, "moe_a2a_wire_rows": z,
                     "moe_a2a_buffer_rows": z}
    if c.num_shared_experts:
        # shared expert(s): a dense FFN every token passes through, added
        # to the routed combine (fine-grained MoE; replicated weights)
        shared = jax.nn.gelu(tokens @ p["s_w1"]) @ p["s_w2"]
        out = out + shared.astype(out.dtype)
    out = out.reshape(x_.shape).astype(x_.dtype)
    if with_stats:
        return out, aux.astype(jnp.float32), stats
    return out, aux.astype(jnp.float32)


def _dense_ffn(p, x_, config: ErnieMoEConfig, with_stats=False,
               dispatch_mode="capacity"):
    out = (jax.nn.gelu(x_ @ p["w1"]) @ p["w2"]).astype(x_.dtype)
    if with_stats:
        # zero stats must match the MoE branch's key set (lax.cond pytree)
        return out, jnp.zeros((), jnp.float32), zero_routing_stats(
            dispatch_mode, config.num_experts)
    return out, jnp.zeros((), jnp.float32)


def _layer_static(p, h, is_moe, config: ErnieMoEConfig, use_onehot=False,
                  mesh=None, with_stats=False, dispatch_mode="capacity"):
    """One decoder layer with a STATIC moe/dense choice (no lax.cond)."""
    h, x = _attn_and_norm(p, h, config)
    res = (_moe_ffn(p, x, config, use_onehot, mesh, with_stats,
                    dispatch_mode) if is_moe
           else _dense_ffn(p, x, config, with_stats, dispatch_mode))
    if with_stats:
        ffn_out, aux, stats = res
        return h + ffn_out, aux, stats
    ffn_out, aux = res
    return h + ffn_out, aux


def _layer(p, h, layer_idx, config: ErnieMoEConfig, use_onehot=False,
           mesh=None, with_stats=False, dispatch_mode="capacity"):
    c = config

    def moe_branch(x_):
        return _moe_ffn(p, x_, c, use_onehot, mesh, with_stats,
                        dispatch_mode)

    def dense_branch(x_):
        return _dense_ffn(p, x_, c, with_stats, dispatch_mode)

    h, x = _attn_and_norm(p, h, c)
    is_moe = (layer_idx % c.moe_every) == (c.moe_every - 1)
    # layer_idx is a traced scan counter: lax.cond keeps one compiled body
    res = lax.cond(is_moe, moe_branch, dense_branch, x)
    if with_stats:
        ffn_out, aux, stats = res
        return h + ffn_out, aux, stats
    ffn_out, aux = res
    return h + ffn_out, aux


def moe_loss(params, ids, labels, config: ErnieMoEConfig,
             use_onehot=False, mesh=None, with_stats=False,
             dispatch_mode="capacity", active_rows=False):
    # use_onehot marks ep>1: WITH a mesh the slot-schedule shard_map
    # island runs (see _moe_ffn); the one-hot einsum only serves
    # mesh-less callers as a fallback
    #
    # with_stats=True: the aux output becomes (lm_loss, stats) where stats
    # aggregates per-layer routing_stats over the MoE layers — counts
    # (dropped/routed) sum, ratios (imbalance/util) average. Stats are
    # lax.stop_gradient'd so the loss/grads are bit-identical either way.
    #
    # active_rows=True (PR 10): additionally return the PER-LAYER
    # [n_moe_layers, E] routed-row counts (un-summed moe_expert_rows) as
    # the last aux element, for the active-only optimizer masking in
    # build_train_step. Requires a ragged dispatch mode whose stats
    # carry moe_expert_rows.
    c = config
    ws = with_stats or active_rows
    b, s = ids.shape
    h = (jnp.take(params["embed"], ids, axis=0)
         + params["pos"][:s][None]).astype(c.dtype)

    # remat per scan step: the capacity-bucketed dispatch one-hots are
    # large and per-layer; recomputing them in the backward trades cheap
    # FLOPs for the activation memory that OOMed real-sized configs
    if _split_stacks(c):
        # the moe/dense pattern is STATIC: scan over (dense, moe) layer
        # PAIRS with both bodies inline — the traced-idx lax.cond was the
        # single largest span in the profiled step (it blocks fusion
        # across the ffn boundary and carries both branches). Stacks are
        # SPLIT (see init_params): each kind streams only its own weights.
        def pair_body(h, lp):
            p0, p1 = lp
            h, aux0 = _layer_static(p0, h, False, c)
            res = _layer_static(p1, h, True, c, use_onehot, mesh,
                                ws, dispatch_mode)
            if ws:
                h, aux1, stats = res
                return h, (aux0 + aux1,
                           jax.lax.stop_gradient(stats))
            h, aux1 = res
            return h, aux0 + aux1

        # checkpoint_dots: matmul outputs survive the remat boundary, so
        # the backward's re-forward is elementwise-only (measured -3 ms
        # per step vs full remat at the bench shape; the saved dot
        # residuals are well within HBM at these sizes)
        h, ys = lax.scan(
            jax.checkpoint(pair_body,
                           policy=jax.checkpoint_policies.checkpoint_dots),
            h, (params["layers"]["dense"], params["layers"]["moe"]))
    else:
        def body(carry, inp):
            h = carry
            idx, layer_params = inp
            res = _layer(layer_params, h, idx, c, use_onehot, mesh,
                         ws, dispatch_mode)
            if ws:
                h, aux, stats = res
                return h, (aux, jax.lax.stop_gradient(stats))
            h, aux = res
            return h, aux

        idxs = jnp.arange(c.num_hidden_layers)
        h, ys = lax.scan(jax.checkpoint(body), h,
                         (idxs, params["layers"]))
    rows_pl = None
    if ws:
        auxes, layer_stats = ys
        if active_rows:
            if "moe_expert_rows" not in layer_stats:
                raise ValueError(
                    "active_rows requires a dispatch mode whose stats "
                    "carry moe_expert_rows (ragged / ragged_a2a), got "
                    f"{dispatch_mode!r}")
            rows_pl = layer_stats["moe_expert_rows"]  # [n_moe_layers, E]
        n_moe = jnp.maximum(
            (layer_stats["moe_routed_tokens"]
             + layer_stats["moe_dropped_tokens"] > 0)
            .astype(jnp.float32).sum(), 1.0)
        # generic over the key set (capacity vs ragged): counts sum over
        # layers, ratio keys average over the layers that actually routed
        stats = {k: (v.sum(0) / n_moe if k in RATIO_STAT_KEYS
                     else v.sum(0))
                 for k, v in layer_stats.items()}
    else:
        auxes = ys
    x = fused_rms_norm(h, params["final_ln"], c.layer_norm_eps)
    logits = (x @ params["embed"].T).astype(jnp.float32)
    mask = labels != -100
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    lm_loss = jnp.sum(jnp.where(mask, -picked, 0.0)) / jnp.maximum(mask.sum(), 1)
    total = lm_loss + c.aux_loss_weight * auxes.sum()
    base = (lm_loss, stats) if with_stats else lm_loss
    if active_rows:
        return total, (base, rows_pl)
    return total, base


def _none_like(tree):
    if isinstance(tree, dict):
        return {k: _none_like(v) for k, v in tree.items()}
    return None


def _expert_row_masks(params, rows_pl):
    """Masks pytree for ``_adamw_update(masks=)`` (PR 10): experts with
    zero routed tokens this step keep their params and AdamW moments
    bitwise-frozen (lazy/sparse-Adam; see llama._adamw_update).

    ``rows_pl`` is the per-layer [n_moe_layers, E] routed-row counts from
    ``moe_loss(active_rows=True)`` — its leading dim lines up with the
    stacked expert weights, so ``rows_pl > 0`` broadcasts as the row
    mask for ``e_w1``/``e_w2``. Every other leaf stays None (unmasked).
    """
    active = rows_pl > 0
    masks = _none_like(params)
    if "moe" in params["layers"]:  # split dense/moe pair stacks
        masks["layers"]["moe"]["e_w1"] = active
        masks["layers"]["moe"]["e_w2"] = active
    else:
        masks["layers"]["e_w1"] = active
        masks["layers"]["e_w2"] = active
    return masks


def build_train_step(config: ErnieMoEConfig, ep_degree: int = 1,
                     dp_degree: int = 1, mesh: Optional[Mesh] = None,
                     lr: float = 3e-4, seed: int = 0,
                     with_stats: bool = False,
                     dispatch_mode: Optional[str] = None,
                     multi_precision: bool = True,
                     active_only_moments: bool = False):
    """EP x DP training step; experts sharded over 'ep', batch over 'dp'.

    with_stats=True: the step's 4th output becomes a dict
    ``{"lm_loss": ..., **routing_stats}`` of on-device f32 values
    (aggregated over layers and the dp axis) instead of the bare
    lm_loss — routing telemetry rides the step outputs, no extra sync.
    The stats key set follows the dispatch mode (capacity: drops /
    routed / imbalance / capacity-util scalars; ragged: explicit
    drops=0, live/padded rows, [E] per-expert group sizes).

    dispatch_mode: "capacity" (default), "ragged" (dropless grouped
    GEMM), or None -> config.dispatch_mode -> PADDLE_TPU_MOE_DROPLESS
    env default.

    multi_precision: True (reference default) keeps f32 AdamW moments;
    False stores moments in each param's dtype — on a bf16 expert stack
    that halves the optimizer HBM streaming the r5 verdict flagged.

    active_only_moments: True (PR 10) masks the AdamW moment
    read-modify-write for experts that routed ZERO tokens this step
    (mask from the moe_expert_rows routing stats; requires a ragged
    dispatch mode). Touched experts update bitwise-identically to the
    full pass; untouched experts keep params AND moments frozen —
    under skew this skips the moment streaming for cold experts."""
    if dispatch_mode is None:
        dispatch_mode = config.dispatch_mode
    if dispatch_mode is None:
        dispatch_mode = default_dispatch_mode()
    if mesh is None and ep_degree * dp_degree > 1:
        from ..distributed.fleet.topology import _pick_devices
        devs = _pick_devices(ep_degree * dp_degree)
        mesh = Mesh(np.array(devs).reshape(dp_degree, ep_degree),
                    axis_names=("dp", "ep"))

    params = init_params(config, seed)
    pspecs = param_pspecs(config, ep_degree, dp_degree)
    if mesh is not None:
        params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, pspecs, is_leaf=lambda x: not isinstance(x, dict))
    opt = _adamw_init(params, multi_precision=multi_precision)

    use_onehot = ep_degree > 1
    moe_mesh = mesh if ep_degree > 1 else None

    def step(p, o, ids, labels):
        (loss, aux), grads = jax.value_and_grad(
            moe_loss, has_aux=True)(p, ids, labels, config, use_onehot,
                                    moe_mesh, with_stats, dispatch_mode,
                                    active_only_moments)
        masks = None
        if active_only_moments:
            aux, rows_pl = aux
            masks = _expert_row_masks(p, rows_pl)
        new_p, new_o = _adamw_update(p, grads, o, lr, masks=masks)
        if with_stats:
            lm_loss, stats = aux
            return new_p, new_o, loss, {"lm_loss": lm_loss, **stats}
        return new_p, new_o, loss, aux

    jit_step = jax.jit(step, donate_argnums=(0, 1))
    batch_sharding = (NamedSharding(mesh, P("dp", None))
                      if mesh is not None else None)

    def step_fn(p, o, ids, labels):
        ids = jnp.asarray(ids, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        if batch_sharding is not None:
            ids = jax.device_put(ids, batch_sharding)
            labels = jax.device_put(labels, batch_sharding)
        return jit_step(p, o, ids, labels)

    return step_fn, params, opt
