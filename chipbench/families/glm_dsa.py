"""The GLM-5.2 family (``model_type`` ``glm_moe_dsa``) on the serving path:
latent attention and sigmoid-routed experts as ``families/deepseek.py`` has
them, with LEARNED SPARSE ATTENTION on top: an indexer scores every cached
position of a row, and the row attends the ``index_topk`` best alone. What
``paddle_tpu.models.deepseek`` runs through ``InferenceEngine`` for a
``DeepSeekConfig`` with ``indexer_types``. The same six answers
``families/llama.py`` gives; ``m`` is a configuration's dict with the
published key names.

Equations (x_t: a layer's normed input; positions s <= t; the program and
this file's reference both compute them). Pre-norm decoder, RMSNorm, untied
head; per layer x <- x + Attn(norm(x)), x <- x + FFN(norm(x)).

Queries: cQ_t = RMSNorm(x_t W_qa); q_{t,h} = cQ_t W_qb[h] (nope + rope
dims), rope on the rope dims at ``rope_parameters.rope_theta``, no scaling.
Latent: [cKV_t ; kR_t] = x_t W_kva; cKV_t through RMSNorm, kR_t through
rope, one key for all heads; k_{s,h} = [cKV_s W_UK[h] ; kR_s], v_{s,h} =
cKV_s W_UV[h] (``v_head_dim`` wide, wider than the nope part).

Indexer, in a layer whose ``indexer_types`` entry is ``full``: qI_{t,j} =
cQ_t W_Iq[j] (``index_n_heads`` heads of ``index_head_dim``), kI_s =
LayerNorm(x_s W_Ik) (one head; scale and bias), rope on the FIRST
``qk_rope_head_dim`` dimensions of both; w_t = x_t W_Iw *
index_n_heads^-1/2 * index_head_dim^-1/2; I_{t,s} = sum_j w_{t,j}
ReLU(qI_{t,j} . kI_s); S_t = the min(t + 1, ``index_topk``) positions s <= t
of largest I_{t,s} (among equal scores the lower position, as ``lax.top_k``
takes them). A ``shared`` layer holds no indexer and uses the S_t of the
nearest ``full`` layer before it.

Attention: u_{t,h} = sum_{s in S_t} softmax_{s in S_t}(q_{t,h} . k_{s,h} *
(nope + rope)^-1/2) v_{s,h}; out = concat_h(u_{t,h}) W_o. The reference
computes dense scores in the expanded form and masks what S_t leaves out;
the program attends in the absorbed form over its latent cache, under the
same mask.

DEPARTURES, in the program and here alike: rope dimensions pair in the
half-rotation layout (i with i + d/2) where ``rope_interleave`` pairs
adjacent elements: one model up to a permutation of the rope columns of
W_qb, W_kva, W_Iq and W_Ik, which seeded weights do not tell apart. Index
keys are bf16 (the published inference code keeps them in fp8 behind a
rotation, which is orthogonal and changes no score).

Expert layer and the chip's share: ``families/deepseek.py``'s, with ONE
group (``n_group`` 1: the group-limited step keeps every expert).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import flops, reference
from chipbench.families import deepseek as ds
from chipbench.families.deepseek import (  # noqa: F401  (the family's answers)
    HEAD_BLOCK, _blocks, attn_params, dense_sublayer, expert_params,
    head_params, held_experts_per_token, latent_bytes, moe_experts_call,
    moe_sublayer, routed_experts, train_kernels)
from chipbench.harness import say
from chipbench.reference import F32, HI, _mm, _round, rms_norm
from chipbench.weights import Leaf, is_leaf

INDEX_NORM_EPS = 1e-6
ROW_BLOCK = 512         # query rows at a time, in attention and the indexer
REF_BUCKET = 4096       # the reference's shortest compiled length


# -- 1. the program's entry ------------------------------------------------------

def glm_config(m: dict):
    """The program's own configuration object from the published keys."""
    from paddle_tpu.models.deepseek import DeepSeekConfig
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
        rms_norm_eps=m["rms_norm_eps"],
        rope_theta=float(m["rope_parameters"]["rope_theta"]), rope_factor=1.0,
        index_n_heads=m["index_n_heads"], index_head_dim=m["index_head_dim"],
        index_topk=m["index_topk"], indexer_types=tuple(m["indexer_types"]),
        dtype=jnp.dtype(m.get("torch_dtype", "bfloat16")))


def train_step(m: dict, t: dict):
    raise SystemExit("chipbench: the glm_dsa family has no training path "
                     "(ROADMAP.md M5: training through a learned selection "
                     "waits): no train cell can run it")


first_moment = train_step


def serve_engine(weights, m: dict, e: dict):
    """The program's own serving engine for a ``serve_open`` mix's
    ``engine`` block; the window calls ``submit()`` and ``step()`` itself."""
    from paddle_tpu.inference import InferenceEngine, ServeConfig
    return InferenceEngine(
        weights, glm_config(m),
        ServeConfig(block_size=e["block_size"], num_blocks=e["num_blocks"],
                    max_batch=e["max_batch"],
                    prefill_chunk=e["prefill_chunk"],
                    max_seq_len=e["max_seq_len"]))


# -- 2. the weights' tree --------------------------------------------------------

def leaves(m) -> dict:
    """The tree ``InferenceEngine`` takes for this model
    (``paddle_tpu.models.deepseek.param_shapes``): the leading dense layers
    in a list, the expert layers stacked on axis 0, a ``full`` layer's
    indexer under ``indexer`` (the expert layers' stacked in their order).
    Norm scales start at one; the router's correction bias and the index
    keys' LayerNorm bias start from the seed like a matrix, so that the
    terms do something."""
    from paddle_tpu.models.deepseek import param_shapes

    def leaf(path, shape):
        name = str(getattr(path[-1], "key", ""))
        return Leaf(tuple(shape), "one" if name.endswith("norm") else "normal")
    return jax.tree_util.tree_map_with_path(
        leaf, param_shapes(glm_config(m)),
        is_leaf=lambda x: isinstance(x, tuple))


# -- 3. the plain reference ------------------------------------------------------

def _rope_first(x, positions, m):
    """x [S, heads, D]: rope on the first ``qk_rope_head_dim`` of D."""
    dr = m["qk_rope_head_dim"]
    return jnp.concatenate(
        [reference.rope(x[..., :dr], positions, m["rope_theta"]),
         x[..., dr:]], axis=-1)


def index_keys(ip, y, pos, m, mode):
    """kI [S, index_head_dim]: LayerNorm(y W_Ik) with scale and bias, the
    first ``qk_rope_head_dim`` dimensions roped."""
    ki = _mm(y, ip["wk"], mode)
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                        + INDEX_NORM_EPS) * ip["k_norm"].astype(F32) \
        + ip["k_bias"].astype(F32)
    return _rope_first(ki[:, None, :], pos, m)[:, 0]


def select_sublayer(ip, y, cq, m, mode):
    """One indexer over the whole sequence: y [S, H] the layer's normed
    input, cq [S, q_lora_rank]. I_{t,s} in full, ``ROW_BLOCK`` rows and
    ``HEAD_BLOCK`` index heads at a time; each row's own ``lax.top_k`` as a
    mask over positions, packed eight to a byte: [S, S / 8] uint8."""
    s = y.shape[0]
    hi, di = m["index_n_heads"], m["index_head_dim"]
    k = min(m["index_topk"], s)
    pos = jnp.arange(s)
    qi = _rope_first(_mm(cq, ip["wq_b"], mode).reshape(s, hi, di), pos, m)
    ki = _round(index_keys(ip, y, pos, m, mode), mode)
    w = _mm(y, ip["weights_proj"], mode) * (hi ** -0.5 * di ** -0.5)
    hb = hi // _blocks(hi, HEAD_BLOCK)
    nr = _blocks(s, ROW_BLOCK)
    r = s // nr

    def rows(args):
        qb, wb, row0 = args                     # [r, hi, di], [r, hi]

        def heads(acc, xs):
            qh, wh = xs                         # [r, hb, di], [r, hb]
            sc = jnp.einsum("rhd,td->hrt", _round(qh, mode), ki, precision=HI)
            return acc + jnp.sum(jnp.maximum(sc, 0.0) * wh.T[:, :, None],
                                 axis=0), None
        scores, _ = lax.scan(
            heads, jnp.zeros((r, s), F32),
            (qb.reshape(r, hi // hb, hb, di).transpose(1, 0, 2, 3),
             wb.reshape(r, hi // hb, hb).transpose(1, 0, 2)))
        causal = (row0 + jnp.arange(r))[:, None] >= pos[None, :]
        _, idx = lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
        mask = jnp.zeros((r, s), bool).at[
            jnp.arange(r)[:, None], idx].set(True) & causal
        return jnp.packbits(mask, axis=-1)

    bits = lax.map(rows, (qi.reshape(nr, r, hi, di), w.reshape(nr, r, hi),
                          jnp.arange(nr) * r))
    return bits.reshape(s, -1)


def attn_sublayer(p, x, m, mode, bits, ip=None):
    """x [S, H] -> (x + MLA(norm(x)) over each row's selected positions,
    the selection): expanded form, dense scores under the selection's mask,
    ``HEAD_BLOCK`` heads and ``ROW_BLOCK`` query rows at a time. ``ip``: the
    layer's indexer (a ``full`` layer selects anew); without one the layer
    attends the ``bits`` it is handed."""
    s, _ = x.shape
    nh, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"])
    rank, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    pos = jnp.arange(s)
    y = rms_norm(x, p["input_norm"], eps)
    cq = rms_norm(_mm(y, p["q_a"], mode), p["q_a_norm"], eps)
    if ip is not None:
        bits = select_sublayer(ip, y, cq, m, mode)
    kv = _mm(y, p["kv_a"], mode)
    ckv = rms_norm(kv[:, :rank], p["kv_a_norm"], eps)
    k_pe = reference.rope(kv[:, None, rank:], pos, m["rope_theta"])[:, 0]
    hb = nh // _blocks(nh, HEAD_BLOCK)
    nb = nh // hb
    q_b = p["q_b"].reshape(-1, nb, hb * (dn + dr)).transpose(1, 0, 2)
    kv_b = p["kv_b"].reshape(rank, nb, hb * (dn + dv)).transpose(1, 0, 2)
    o_w = p["o_proj"].reshape(nb, hb * dv, -1)
    nr = _blocks(s, ROW_BLOCK)
    scale = (dn + dr) ** -0.5
    ckv_r, kpe_r = _round(ckv, mode), _round(k_pe, mode)

    def heads(out, w):
        wq, wkv, wo = w
        q = _mm(cq, wq, mode).reshape(s, hb, dn + dr)
        q_nope = q[..., :dn]
        q_pe = reference.rope(q[..., dn:], pos, m["rope_theta"])
        kvh = jnp.matmul(ckv_r, _round(wkv.astype(F32), mode),
                         precision=HI).reshape(s, hb, dn + dv)
        k_nope, v = _round(kvh[..., :dn], mode), _round(kvh[..., dn:], mode)

        @jax.checkpoint
        def rows(args):
            qn, qp, sel = args
            sc = (jnp.einsum("shd,thd->hst", _round(qn, mode), k_nope,
                             precision=HI)
                  + jnp.einsum("shd,td->hst", _round(qp, mode), kpe_r,
                               precision=HI)) * scale
            mask = jnp.unpackbits(sel, axis=-1).astype(bool)
            pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hst,thd->shd", _round(pr, mode), v,
                              precision=HI)
        o = lax.map(rows, (q_nope.reshape(nr, s // nr, hb, dn),
                           q_pe.reshape(nr, s // nr, hb, dr),
                           bits.reshape(nr, s // nr, -1)))
        return out + _mm(o.reshape(s, hb * dv), wo, mode), None

    out, _ = lax.scan(heads, jnp.zeros_like(x), (q_b, kv_b, o_w))
    return x + out, bits


@functools.partial(jax.jit, static_argnames=("m_items", "mode", "last"))
def _logits_jit(weights, ids, start, *, m_items, mode, last):
    m = dict(m_items)
    kinds = m.pop("indexer_types_items")
    nd = len(weights["dense"])
    x = jnp.take(weights["embed"], ids[0], axis=0).astype(F32)
    bits = None
    for p in weights["dense"]:
        x, bits = attn_sublayer(p, x, m, mode, bits, p.get("indexer"))
        x = dense_sublayer(p, x, m, mode)
    moe = dict(weights["moe"])
    indexers = moe.pop("indexer", None)
    full = [k == "full" for k in kinds[nd:]]
    # which of the stacked indexers an expert layer holds (none: the last
    # one's number again, never read)
    which = jnp.asarray([max(sum(full[:i + 1]) - 1, 0)
                         for i in range(len(full))], jnp.int32)

    def layer(carry, xs):
        x, bits = carry
        p, is_full, f = xs
        if indexers is None:
            x, bits = attn_sublayer(p, x, m, mode, bits)
        else:
            ip = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, f, 0, keepdims=False),
                indexers)
            x, bits = lax.cond(
                is_full,
                lambda x, bits: attn_sublayer(p, x, m, mode, bits, ip),
                lambda x, bits: attn_sublayer(p, x, m, mode, bits), x, bits)
        return (moe_sublayer(p, x, m, mode), bits), None

    (x, _), _ = lax.scan(layer, (x, bits), (moe, jnp.asarray(full), which))
    x = lax.dynamic_slice_in_dim(x, start, last, axis=0)
    x = rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
    return _mm(x, weights["lm_head"], mode)


def _hashable(m):
    """The configuration as ``_logits_jit``'s static argument: its scalars,
    the rope's base and the layers' kinds."""
    return reference._hashable(m) + (
        ("rope_theta", float(m["rope_parameters"]["rope_theta"])),
        ("indexer_types_items", tuple(m["indexer_types"])))


def own_padded(n: int, padded: int) -> int:
    """The length the dense forward is compiled at for a request of ``n``
    tokens: ``REF_BUCKET`` doubled until it holds them, or the mix's
    ``padded`` where that is less than twice as long. Scores are dense, so a
    forward at the mix's longest (33,792 tokens: 34 s on the chip) costs a
    median request of 8k sixteen times its own; four lengths compile where
    the mix has one."""
    own = REF_BUCKET
    while own < n:
        own *= 2
    return own if 2 * own <= padded else padded


def logits_after(weights, m, tokens, last: int, padded: int, last_max: int,
                 mode="f32"):
    """``reference.logits_after`` through this family's forward pass, at the
    request's own length bucket. The float32 reading also says, on standard
    error, where the request's widest gap lies (every served token but the
    last, which ``tokens`` does not hold): the harness keeps one number a
    run, and a fault that grows with the context shows request by request."""
    fwd = lambda w, ids, start, *, m_items, mode, last: _logits_jit(
        w, ids, start, m_items=_hashable(m), mode=mode, last=last)
    rows = reference.logits_after(fwd, weights, m, tokens, last,
                                  own_padded(len(tokens), padded), last_max,
                                  mode)
    if mode == "f32" and last > 1:
        seen = rows[:last - 1]
        served = np.asarray(tokens[len(tokens) - last + 1:])
        gap = (seen.max(-1) - seen[np.arange(last - 1), served]) / seen.std(-1)
        say(f"reference: context {len(tokens) - last + 1} + {last} served: "
            f"widest gap {gap.max():.3f} std at +{int(gap.argmax())}, mean "
            f"{gap.mean():.4f}, {int((gap > 0).sum())} tokens not the "
            f"reference's first")
    return rows


# -- 4. the work -----------------------------------------------------------------
# Required means what the algorithm needs once (``chipbench/flops.py``).

def indexer_params(m) -> int:
    """One indexer's matmul parameters: W_Iq, W_Ik, W_Iw."""
    return (m["q_lora_rank"] * m["index_n_heads"] * m["index_head_dim"]
            + m["hidden_size"] * (m["index_head_dim"] + m["index_n_heads"]))


def n_full(m) -> int:
    return list(m["indexer_types"]).count("full")


def matmul_params(m) -> float:
    """Parameters that take part in a matmul for a token: the layers' as
    ``families/deepseek.py`` counts them (a token finds 8 x 16 / 256 = 0.5
    routed experts held), and the indexers'."""
    return ds.matmul_params(m) + n_full(m) * indexer_params(m)


def index_pair_flops(m) -> int:
    """One (row, key) pair of one indexer: a dot of ``index_head_dim`` a
    head."""
    return 2 * m["index_n_heads"] * m["index_head_dim"]


def attend_pair_flops(m) -> int:
    """One (row, selected token) pair of one layer's attention, absorbed:
    2 x heads x ((rank + rope) + rank)."""
    return ds.pair_flops(m, True)


def reach(start: int, n: int):
    """Positions at or before each of ``n`` rows after ``start`` cached."""
    return [start + i + 1 for i in range(n)]


def step_flops(m, reach_, logit_rows: int) -> float:
    """What one pass over rows that reach ``reach_`` positions each requires:
    the layers' matmuls, every indexer's pairs over all a row reaches, every
    layer's attention over the min(reach, ``index_topk``) it selects, the
    head for ``logit_rows`` rows."""
    pairs = float(sum(reach_))
    picked = float(sum(min(r, m["index_topk"]) for r in reach_))
    return (2.0 * (matmul_params(m) - head_params(m)) * len(reach_)
            + n_full(m) * index_pair_flops(m) * pairs
            + m["num_hidden_layers"] * attend_pair_flops(m) * picked
            + 2.0 * head_params(m) * logit_rows)


def forward_flops(m, new_tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """The harness's call (``serve.trace_counters``): a decode step
    (``logit_rows == new_tokens``) or a chunk of ``new_tokens`` rows whose
    ``context_sum`` is n x start + n (n + 1) / 2, from which the start
    follows. A decode step's rows are taken as reaching ``context_sum /
    new_tokens`` each; ``mfu.longdoc`` reads ``glm_required_flops``
    (``serve_kernels``), which has every row's own reach."""
    n = new_tokens
    if logit_rows == n:
        return step_flops(m, [context_sum / n] * n, n)
    start = (context_sum - n * (n + 1) // 2) // n
    return step_flops(m, reach(start, n), logit_rows)


# kernels: one call of one layer

def dsa_index_call(m, reach_, block_size: int, shared_keys: bool):
    """(flops, bytes) of ONE indexer's scores for rows that reach ``reach_``
    positions each: the pairs' dots; the index keys' blocks (bf16) once for
    a chunk's rows, which walk one sequence (``shared_keys``), and once a
    row for a decode batch's; each row's queries and weights in, a float32
    score a pair out."""
    hi, di = m["index_n_heads"], m["index_head_dim"]
    pairs = float(sum(reach_))
    blocks = lambda t: -(-int(t) // block_size) * block_size * di * 2
    keys = blocks(max(reach_)) if shared_keys \
        else sum(blocks(r) for r in reach_)
    by = keys + len(reach_) * hi * (2 * di + 4) + 4 * pairs
    return index_pair_flops(m) * pairs, float(by)


def dsa_attend_call(m, reach_, block_size: int, shared_keys: bool):
    """(flops, bytes) of ONE layer's attention over the selected tokens
    alone: min(reach, ``index_topk``) pairs a row at the absorbed rate; each
    pair's latent column (rank + rope, bf16), but for a chunk's rows no more
    than the sequence's latent blocks once; queries in, latent outputs
    out."""
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    w = rank + m["qk_rope_head_dim"]
    picked = [min(r, m["index_topk"]) for r in reach_]
    cols = float(sum(picked)) * w * 2
    if shared_keys:
        cols = min(cols, latent_bytes(m, max(reach_), block_size))
    by = cols + len(reach_) * nh * (w * 2 + rank * 4)
    return attend_pair_flops(m) * float(sum(picked)), by


def serve_kernels(m, e, iterations, peak) -> dict:
    """The counters the cell's readers take, over the traced iterations:
    the least seconds the indexers' score kernels (``dsa_index``: once a
    ``full`` layer) and the attention over the selection (``dsa_attend``:
    once a layer) could take, a chunk's rows and a decode batch's each as
    the call they are, whichever program ran them; and
    ``glm_required_flops``, what the iterations required in all. (The
    experts' count is read from the program's own registry by
    ``readers_deepseek.py``.)"""
    bs, n_layers = e["block_size"], m["num_hidden_layers"]
    index = attend = need = 0.0
    for r in iterations:
        parts = []
        if r["prefill"]:
            start, n, first = r["prefill"]
            parts.append((reach(start, n), True, first))
        if r["decode_ctx"]:
            parts.append((list(r["decode_ctx"]), False, len(r["decode_ctx"])))
        for reach_, shared, logit_rows in parts:
            index += n_full(m) * flops.min_seconds(
                *dsa_index_call(m, reach_, bs, shared), peak)
            attend += n_layers * flops.min_seconds(
                *dsa_attend_call(m, reach_, bs, shared), peak)
            need += step_flops(m, reach_, logit_rows)
    return {"dsa_index": {"least_s": index} if index else None,
            "dsa_attend": {"least_s": attend} if attend else None,
            "glm_required_flops": need}


# -- 5. the rehearsal's size -----------------------------------------------------

TINY = dict(ds.TINY, num_hidden_layers=4, v_head_dim=24, n_group=1,
            topk_group=1, index_n_heads=4, index_head_dim=16, index_topk=16,
            indexer_types=["full", "shared", "full", "shared"],
            mlp_layer_types=["dense", "sparse", "sparse", "sparse"])


def rehearsal(m: dict) -> dict:
    """The configuration at a size the CPU runs in seconds, every kind of
    layer in it: one dense ``full`` layer, expert layers ``shared``, ``full``,
    ``shared``, 8 of 32 experts held, an indexer that keeps 16 positions.
    The rehearsal proves control flow, never a number."""
    out = dict(m, **TINY)
    out["published"] = dict(m.get("published", {}), n_routed_experts=32)
    return out


# -- 6. compiled for a described chip (``aot_check.py``) -------------------------

def aot_shapes(m, t, one):
    """(the program's config, params, the cache's two pools, max_nb) as
    shapes placed by the sharding ``one``."""
    config = glm_config(m)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.tree_util.tree_map(
        lambda leaf: sds(leaf.shape, jnp.bfloat16), leaves(m),
        is_leaf=is_leaf)
    e = t["engine"]
    pools = (sds((config.num_hidden_layers, e["num_blocks"],
                  config.latent_width, e["block_size"]), jnp.bfloat16),
             sds((config.n_index_layers, e["num_blocks"],
                  config.index_head_dim, e["block_size"]), jnp.bfloat16))
    return config, params, pools, -(-e["max_seq_len"] // e["block_size"])


def aot_programs(m, t, one, with_reference):
    """(name, compile) of every program a cell of this family needs at its
    real size, from shapes placed by the sharding ``one``."""
    from paddle_tpu.models import deepseek as D
    config, params, pools, max_nb = aot_shapes(m, t, one)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    i32 = jnp.int32
    e = t["engine"]
    chunk = (sds((max_nb,), i32), sds((), i32),
             sds((e["prefill_chunk"],), i32), sds((), i32))
    rows = lambda b: (sds((b, max_nb), i32), sds((b,), i32), sds((b,), i32))
    for b in (1, e["max_batch"]):       # the smallest and largest bucket
        yield f"decode, batch {b}", lambda b=b: ds._compiled(
            lambda: D._jitted_paged_step("decode", config).lower(
                params, *pools, *rows(b)))
    yield f"prefill chunk {e['prefill_chunk']}", lambda: ds._compiled(
        lambda: D._jitted_paged_step("prefill", config).lower(
            params, *pools, *chunk))
    yield (f"prefill chunk {e['prefill_chunk']} carrying batch "
           f"{e['max_batch']}"), lambda: ds._compiled(
        lambda: D._jitted_paged_step("prefill+decode", config).lower(
            params, *pools, *chunk, *rows(e["max_batch"])))
    if with_reference:
        from chipbench.serve import check_shape
        padded, last_max = check_shape(t)
        yield f"reference forward at {padded} tokens", lambda: \
            _logits_jit.lower(params, sds((1, padded), i32), sds((), i32),
                              m_items=_hashable(m), mode="f32",
                              last=last_max).compile()


# -- 7. how far the program's selection lies from the reference's ---------------

def selection_overlap(weights, m, ids, rows):
    """The first layer's selection as the program computes it (bf16 inputs,
    queries and keys, float32 scores: ``models/deepseek.py``
    ``indexer_project``) against this file's float32 one, for the sequence
    ``ids`` at the positions ``rows``: the share of a row's ``index_topk``
    positions that both select. The first layer's input is the embedding,
    the same on both sides, so what parts them is rounding alone. Printed by
    ``chipbench/overlap.py``; nothing is compared with a limit."""
    from paddle_tpu.models import deepseek as D
    c = glm_config(m)
    p = weights["dense"][0]
    ip = p["indexer"]
    s, k = len(ids), min(m["index_topk"], len(ids))
    hi, di = m["index_n_heads"], m["index_head_dim"]
    pos = jnp.arange(s)
    rows = jnp.asarray(rows)
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(F32)
    mm = dict(m, rope_theta=float(m["rope_parameters"]["rope_theta"]))

    def top(qi, w, ki):
        """qi [R, hi, di], w [R, hi], ki [S, di] float32 -> [R, k] ids."""
        def heads(acc, xs):
            qh, wh = xs
            sc = jnp.einsum("rd,td->rt", qh, ki, precision=HI)
            return acc + jnp.maximum(sc, 0.0) * wh[:, None], None
        scores, _ = lax.scan(heads, jnp.zeros((len(rows), s), F32),
                             (qi.transpose(1, 0, 2), w.T))
        causal = rows[:, None] >= pos[None, :]
        return lax.top_k(jnp.where(causal, scores, -jnp.inf), k)[1]

    # the reference's
    y = rms_norm(x, p["input_norm"], m["rms_norm_eps"])
    cq = rms_norm(_mm(y, p["q_a"], "f32"), p["q_a_norm"], m["rms_norm_eps"])
    qi = _rope_first(_mm(cq[rows], ip["wq_b"], "f32").reshape(-1, hi, di),
                     rows, mm)
    ki = index_keys(ip, y, pos, mm, "f32")
    w = _mm(y[rows], ip["weights_proj"], "f32") * (hi ** -0.5 * di ** -0.5)
    ref = top(qi, w, ki)
    # the program's: every position's key as the pool holds it, the rows'
    # queries
    xb = D.rms_norm(x, p["input_norm"], c.rms_norm_eps).astype(c.dtype)
    cqb = D.latent_queries(p, xb, c)
    _, _, kb = D.indexer_project(ip, xb, cqb, *D.yarn_cos_sin(c, pos), c)
    qb, wb, _ = D.indexer_project(ip, xb[rows], cqb[rows],
                                  *D.yarn_cos_sin(c, rows), c)
    prog = top(qb.astype(F32), wb, kb.astype(c.dtype).astype(F32))
    both = ((ref[:, :, None] == prog[:, None, :]).any(-1)
            & (ref <= rows[:, None])).sum(-1)
    return both / jnp.minimum(rows + 1, k)
