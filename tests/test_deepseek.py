"""DeepSeek-V3 on the serving path, at a tiny size on the CPU with seeded
weights (Pallas in the interpreter): the absorbed latent attention equals
the expanded form and the float32 reference; the router follows a plain loop
written from the equations; the parts that all shares of an expert layer
give add up to the uncut reference; chunked prefill and then decode through
``InferenceEngine`` agree with the reference's full forward on logits; the
chunk that carries the decode batch is the chunk followed by the decode step,
and the engine's streams are those of the two-program path; what is out of
scope raises at construction.

Tolerances, and why. The program's residual stream, weights and latent
cache are bfloat16 (8 bits of mantissa: 0.4 % a rounding), the reference
float32 ``highest``. A logit here is a sum of a few hundred bf16 products
of standard deviation about 1, so program and reference differ by about a
hundredth of the logits' standard deviation. A served token that is not
the reference's best lost a near-tie: over the prompts here the widest such
gap reads 0.084 standard deviations and the fp8 reference's choices lie
farther off (tested); ``LOGIT_TOL`` = 0.15 lies between. Over a whole row of logits the program reads 0.014-0.04
root mean square (a near-tie in the router, decided the other way in bf16,
moves single logits by up to 0.17) and the fp8 reference 0.17-0.70:
``LOGIT_RMS_TOL`` = 0.08 lies between. The same reference computed with fp8
(e4m3) operands, or the reference in bfloat16 set beside the float32 one
at the tighter ``REF_TOL``, lies outside it (tested): a lower precision in
the program's place does not pass."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from chipbench import spec
from chipbench.families import deepseek as fam
from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.models import deepseek as D

LOGIT_TOL = 0.15        # of the logits' standard deviation, bf16 program
LOGIT_RMS_TOL = 0.08    # the same unit, root mean square over the vocabulary
REF_TOL = 0.004         # float32 against float32, written two ways

TINY_M = fam.rehearsal(json.load(open(os.path.join(
    spec.HERE, "configs", "deepseek-v3.json"))))
TINY_M["vocab_size"] = 256


def tiny(std=0.3, **over):
    """(m, the program's config, seeded weights). The weights' scale is
    larger than the benchmark's 0.02 so that at width 64 scores, routing and
    logits are far from uniform."""
    m = dict(TINY_M, **over)
    c = fam.deepseek_config(m)
    return m, c, D.init_deepseek_params(c, seed=3, std=std)


def rms_gap(logits, ref):
    """Root mean square of logits - ref, in ref's standard deviations."""
    return float(np.sqrt(np.mean((logits - ref) ** 2)) / ref.std())


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def layer0(params):
    return {k: v[0] for k, v in params["moe"].items()
            if not isinstance(v, dict)}


def test_yarn_frequencies_and_scale_are_the_published_ones():
    c = D.DeepSeekConfig()
    inv = np.asarray(D.yarn_inv_freq(c))
    base = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    # the correction range of (32, 1) rotations at 4096 is dims 10 .. 23:
    # original below it, interpolated by 40 above it, a ramp between
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    assert c.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    m = json.load(open(os.path.join(spec.HERE, "configs",
                                    "deepseek-v3.json")))
    np.testing.assert_allclose(np.asarray(fam.yarn_inv_freq(m)), inv,
                               rtol=1e-6)
    assert fam.softmax_scale(m) == pytest.approx(c.softmax_scale)


def test_expanded_equals_absorbed_equals_reference():
    """One layer's attention three ways on one sequence: the reference
    (float32, expanded, in blocks), the program's expanded form in plain
    jnp, and the program's absorbed form through both paged kernels."""
    from paddle_tpu.ops.paged_attention import (mla_paged_decode,
                                                mla_paged_prefill,
                                                mla_update_walk)
    m, c, params = tiny()
    c32 = dataclasses.replace(c, dtype=jnp.float32)
    p = f32(layer0(params))
    s, bs = 48, 128
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((s, c.hidden_size)), jnp.float32)
    ref = fam.attn_sublayer(dict(p, input_norm=jnp.ones(c.hidden_size)),
                            x, m, "f32") - x
    # the reference norms x first; feed the others the normed x
    from chipbench.reference import rms_norm
    y = rms_norm(x, jnp.ones(c.hidden_size), c.rms_norm_eps)
    cos, sin = D.yarn_cos_sin(c, jnp.arange(s))
    expanded = D.mla_expanded(p, y, cos, sin, c32)
    np.testing.assert_allclose(np.asarray(expanded), np.asarray(ref),
                               rtol=REF_TOL, atol=REF_TOL * np.std(ref))
    # absorbed, through the kernels: a prefill chunk of 32, then 16 decodes
    q_nope, q_pe, lat = D.mla_project(p, y, cos, sin, c32)
    q = D.absorbed_queries(p, q_nope, q_pe, c32)
    pool = jnp.zeros((1, 3, c.latent_width, bs), jnp.float32)
    table = jnp.asarray([2, 1], jnp.int32)
    pool = pool.at[0, 2, :, :32].set(lat[:32].T)
    o = mla_paged_prefill(q[:32], pool, table, jnp.int32(0), jnp.int32(32),
                          0, rank=c.kv_lora_rank)
    outs = [D.latent_out(p, o, c32)]
    for t in range(32, s):
        walk = mla_update_walk(table[None], jnp.asarray([t], jnp.int32),
                                 bs)
        o, pool = mla_paged_decode(q[t:t + 1], lat[t:t + 1], pool, walk, 0,
                                   rank=c.kv_lora_rank)
        outs.append(D.latent_out(p, o, c32))
    absorbed = jnp.concatenate(outs)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(ref),
                               rtol=REF_TOL, atol=REF_TOL * np.std(ref))


def loop_route(y, router, bias, m):
    """The router as a plain loop over tokens, from the equations."""
    e, g, k = router.shape[1], m["n_group"], m["num_experts_per_tok"]
    idx, w = [], []
    for row in np.asarray(y, np.float64):
        s = 1.0 / (1.0 + np.exp(-(row @ np.asarray(router, np.float64))))
        sel = s + np.asarray(bias, np.float64)
        groups = sel.reshape(g, e // g)
        score = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-score)[:m["topk_group"]]
        masked = np.full(e, -np.inf)
        for gi in kept:
            masked[gi * (e // g):(gi + 1) * (e // g)] = groups[gi]
        top = np.argsort(-masked)[:k]
        idx.append(top)
        w.append(s[top] / s[top].sum() * m["routed_scaling_factor"])
    return np.asarray(idx), np.asarray(w)


GROUPS = {"4 groups, 2 kept": dict(n_group=4, topk_group=2),
          "one group (GLM-5.2's)": dict(n_group=1, topk_group=1)}


@pytest.mark.parametrize("groups", list(GROUPS))
def test_router_follows_the_equations(groups):
    m, c, params = tiny(**GROUPS[groups])
    p = f32(layer0(params))
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.standard_normal((40, c.hidden_size)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(32) * 0.3, jnp.float32)
    want_i, want_w = loop_route(y, p["router"], bias, m)
    for route in (lambda: D.route(y, p["router"], bias, c),
                  lambda: fam.route(y, p["router"], bias, m)):
        idx, w = (np.asarray(a) for a in route())
        assert np.array_equal(np.sort(idx, 1), np.sort(want_i, 1))
        order = np.argsort(idx, 1)
        np.testing.assert_allclose(
            np.take_along_axis(w, order, 1),
            np.take_along_axis(want_w, np.argsort(want_i, 1), 1), rtol=1e-5)
        # normalised over all selected, then scaled
        np.testing.assert_allclose(w.sum(1), m["routed_scaling_factor"],
                                   rtol=1e-5)
        # at most topk_group groups hold the selected experts
        assert all(len(set(r // (32 // m["n_group"]))) <= m["topk_group"]
                   for r in idx)
    # the bias moves the selection and not the weights: with it the choice
    # differs from the unbiased one somewhere, and every weight is still the
    # unbiased score's share
    idx0, _ = (np.asarray(a) for a in D.route(y, p["router"],
                                              jnp.zeros(32), c))
    idx, w = (np.asarray(a) for a in D.route(y, p["router"], bias, c))
    assert not np.array_equal(np.sort(idx, 1), np.sort(idx0, 1))
    s = 1 / (1 + np.exp(-np.asarray(y, np.float64)
                        @ np.asarray(p["router"], np.float64)))
    picked = np.take_along_axis(s, idx, 1)
    np.testing.assert_allclose(
        w, picked / picked.sum(1, keepdims=True) * 2.5, rtol=1e-5)


def uncut_moe(p, x, m):
    """The whole expert layer, all 32 experts, token by token."""
    from chipbench.reference import rms_norm
    y = np.asarray(rms_norm(x, p["post_norm"], m["rms_norm_eps"]),
                   np.float64)
    idx, w = loop_route(y, p["router"], p["router_bias"], m)
    ex = {k: np.asarray(v, np.float64) for k, v in p["experts"].items()}
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    silu = lambda a: a / (1 + np.exp(-a))
    ffn = lambda r, g, u, d: (silu(r @ g) * (r @ u)) @ d
    out = np.asarray(x, np.float64).copy()
    for t, row in enumerate(y):
        out[t] += ffn(row, sh["gate"], sh["up"], sh["down"])
        for e, we in zip(idx[t], w[t]):
            out[t] += we * ffn(row, ex["gate"][e], ex["up"][e],
                               ex["down"][e])
    return out


@pytest.mark.parametrize("groups", list(GROUPS))
def test_all_shares_add_up_to_the_uncut_layer(groups):
    """Four chips hold 8 of the 32 experts each. Each gives its partial
    result with the shared expert; the routed parts of all four, and the
    shared expert counted once, are the uncut layer: in the reference, and
    in the program's layer told which experts it holds."""
    m, c, params = tiny(n_routed_experts=32,        # a tree with all 32
                        **GROUPS[groups])
    p = f32({k: (v[0] if not isinstance(v, dict)
                 else {a: b[0] for a, b in v.items()})
             for k, v in params["moe"].items()})
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, c.hidden_size)), jnp.float32)
    whole = uncut_moe(p, x, m)

    def share_of(p, lo):
        return dict(p, experts={k: v[lo:lo + 8]
                                for k, v in p["experts"].items()})
    m8 = dict(m, n_routed_experts=8)
    # the reference's share: x + routed part + shared expert
    parts = [np.asarray(fam.moe_sublayer(share_of(p, lo), x, m8, "f32",
                                         held=(lo, 8)), np.float64)
             for lo in range(0, 32, 8)]
    no_routed = np.asarray(fam.moe_sublayer(
        dict(share_of(p, 0), experts={k: v[:0] for k, v in
                                      p["experts"].items()}),
        x, dict(m, n_routed_experts=0), "f32", held=(0, 0)), np.float64)
    total = no_routed + sum(part - no_routed for part in parts)
    np.testing.assert_allclose(total, whole, rtol=REF_TOL,
                               atol=REF_TOL * whole.std())
    # a share alone is not the layer
    assert np.abs(parts[0] - whole).max() > 0.05 * whole.std()
    # the program's layer, one share at a time
    from chipbench.reference import rms_norm
    y = rms_norm(x, p["post_norm"], c.rms_norm_eps)
    live = jnp.ones(24, bool)
    routed, pairs = 0.0, 0
    shared = np.asarray(D.swiglu(y, *[p["shared"][k] for k in
                                      ("gate", "up", "down")]), np.float64)
    for lo in range(0, 32, 8):
        c8 = dataclasses.replace(c, dtype=jnp.float32, expert_offset=lo,
                                 n_local_experts=8)
        experts = {k: v[None] for k, v in share_of(p, lo)["experts"].items()}
        f, stats = D.moe_ffn(y, live, p, experts, jnp.int32(0), c8)
        routed = routed + (np.asarray(f, np.float64) - shared)
        pairs += int(stats[1])
        assert int(stats[0]) == 24 * 4 and int(stats[2]) <= 8
    assert pairs == 24 * 4          # every selected pair is held somewhere
    np.testing.assert_allclose(np.asarray(x, np.float64) + routed + shared,
                               whole, rtol=REF_TOL,
                               atol=REF_TOL * whole.std())


def serve(params, c, prompts, new, **serve_over):
    eng = InferenceEngine(params, c, ServeConfig(**dict(dict(
        block_size=128, num_blocks=10, max_batch=4, prefill_chunk=32,
        max_seq_len=384), **serve_over)))
    for i, p in enumerate(prompts):
        assert eng.submit(Request(p, new, request_id=i)).accepted
    while not eng.idle():
        eng.step()
    return eng, {s.req.request_id: s.generated for s in eng.finished}


def logit_gaps(m, params, prompt, out, mode="f32"):
    """The reference's logits at every served position, and how far below
    the reference's best the served token lies, in standard deviations."""
    toks = list(prompt) + list(out[:-1])
    ref = fam.logits_after(params, m, toks, len(out), 512, 16, mode)
    rows = np.arange(len(out))
    return ref, (ref.max(-1) - ref[rows, np.asarray(out)]) / ref.std(-1)


def test_serving_object_offers_the_three_programs_and_no_other():
    """``step_fn`` answers None for a kind it has no builder for; the engine
    chooses its iteration by that answer. The chunk that carries the batch
    goes by the chunk's name first, which is what the benchmark's metrics
    match in a device trace."""
    _, c, _ = tiny()
    assert D.DeepSeekServing.step_fn("verify", c, False, None) is None
    for kind in ("prefill", "decode", "prefill+decode"):
        assert callable(D.DeepSeekServing.step_fn(kind, c, False, None))
    names = {kind: D.DeepSeekServing.step_fn(kind, c, False, None).__name__
             for kind in ("prefill", "decode", "prefill+decode")}
    assert names == {"prefill": "paged_prefill_chunk_mla",
                     "decode": "paged_decode_step_mla",
                     "prefill+decode": "paged_prefill_chunk_mla_with_decode"}


# -- the chunk that carries the decode batch against the two it replaces -------

P_BS, P_NB, P_MAX_NB, P_C, P_R = 16, 24, 6, 16, 4

# rows as (blocks, position): a row alone; a full batch with one row whose new
# token opens its second block (position == block size); a batch half padding
BATCHES = {
    "one_row": [([1, 2, 3], 35)],
    "full_with_boundary": [([1, 2, 3], 35), ([4, 5], 16), ([6], 3),
                           ([11, 12, 13], 47)],
    "half_padding": [([4, 5], 16), ([6], 0)],
}


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("start, n_live", [(0, 16), (8, 11), (13, 1)])
def test_program_equals_chunk_then_decode(start, n_live, batch):
    """Chunk logits, row logits, the pool and the counts of the one step
    against ``deepseek_paged_prefill_chunk`` followed by
    ``deepseek_paged_decode_step`` on the same inputs, over a pool with
    something in every block. Every block but the null one (which padding
    rows and dead chunk slots scribble on in either order) comes out the
    same; the logits to float32 rounding of matmuls whose row count changed
    (C + B rows where they were C, then B). Pairs add up; the experts hit
    and the busiest's rows are of the union of the rows."""
    _, c, params = tiny()
    rows = BATCHES[batch]
    tables = np.zeros((P_R, P_MAX_NB), np.int32)
    positions = np.zeros((P_R,), np.int32)
    ids_r = np.zeros((P_R,), np.int32)
    for i, (blocks, pos) in enumerate(rows):
        tables[i, :len(blocks)] = blocks
        positions[i], ids_r[i] = pos, 5 + 7 * i
    table_row = np.zeros((P_MAX_NB,), np.int32)
    table_row[:4] = [7, 8, 9, 10]
    ids_c = np.random.default_rng(0).integers(
        0, c.vocab_size, P_C).astype(np.int32)
    chunk_in = (jnp.asarray(table_row), np.int32(start), jnp.asarray(ids_c),
                np.int32(n_live))
    rows_in = (jnp.asarray(tables), jnp.asarray(positions),
               jnp.asarray(ids_r))

    def pool():
        shape = D.init_latent_pool(c, P_NB, P_BS).shape
        return jax.random.normal(jax.random.PRNGKey(1), shape, c.dtype)

    def step(kind):
        """The step function itself, which keeps its logits."""
        fn = D._PAGED_STEPS[kind][0]
        return jax.jit(lambda p, pool, *a: fn(p, (pool,), *a, c))

    want_c, mid, counts_c = step("prefill")(params, pool(), *chunk_in)
    want_r, want_pool, counts_r = step("decode")(params, mid, *rows_in)
    got_c, got_r, got_pool, counts = step("prefill+decode")(
        params, pool(), *chunk_in, *rows_in)
    # the program the engine calls: the greedy head of those logits, the
    # same pool and counts, never logits
    tok_c, fin_c, tok_r, fin_r, pool2, counts2 = D.DeepSeekServing.step_fn(
        "prefill+decode", c, False, None)(params, pool(), *chunk_in, *rows_in)
    assert tok_c.shape == () and tok_r.shape == (P_R,)
    assert tok_r.dtype == jnp.int32 and fin_r.dtype == jnp.bool_
    assert int(tok_c) == int(np.argmax(got_c)) and bool(fin_c)
    np.testing.assert_array_equal(tok_r, np.argmax(got_r, axis=-1))
    assert bool(np.all(fin_r))
    np.testing.assert_array_equal(np.asarray(pool2, np.float32),
                                  np.asarray(got_pool, np.float32))
    np.testing.assert_array_equal(counts2, counts)
    assert got_c.shape == (c.vocab_size,) and got_c.dtype == jnp.float32
    assert got_r.shape == (P_R, c.vocab_size) and got_r.dtype == jnp.float32
    tol = 1e-5 * float(np.std(np.asarray(want_c)))
    np.testing.assert_allclose(got_c, want_c, rtol=1e-5, atol=tol)
    n = len(rows)
    np.testing.assert_allclose(got_r[:n], want_r[:n], rtol=1e-5, atol=tol)
    np.testing.assert_array_equal(np.asarray(got_pool[:, 1:], np.float32),
                                  np.asarray(want_pool[:, 1:], np.float32))
    counts, counts_c, counts_r = (np.asarray(a) for a in
                                  (counts, counts_c, counts_r))
    assert counts.shape == (c.n_moe_layers, 4)
    np.testing.assert_array_equal(counts[:, :2],
                                  counts_c[:, :2] + counts_r[:, :2])
    assert np.all(counts[:, 0] == (n_live + n) * c.num_experts_per_tok)
    for col in (2, 3):      # held experts hit, the busiest's rows: the union's
        assert np.all(counts[:, col] >= np.maximum(counts_c[:, col],
                                                   counts_r[:, col]))
        assert np.all(counts[:, col] <= counts_c[:, col] + counts_r[:, col])


def test_counted_names_each_part_of_a_program():
    counts = (jnp.asarray([[8, 4, 3, 2], [8, 5, 2, 3]], jnp.int32),)
    both = D.DeepSeekServing.counted("prefill+decode", counts, [300],
                                     [41, 7])
    assert both == {"pairs": 16, "local_pairs": 9, "experts_hit": 5,
                    "busiest_rows": 5, "mla_prefill_ctx": 300,
                    "mla_decode_ctx": 48}
    assert D.DeepSeekServing.counted("decode", counts, [41, 7])[
        "mla_decode_ctx"] == 48
    with pytest.raises(ValueError):
        D.DeepSeekServing.counted("prefill+decode", counts, [300])


class _TwoPrograms(D.DeepSeekServing):
    """DeepSeek's serving object, offering no chunk that carries the batch."""

    @staticmethod
    def step_fn(kind, frozen, quant, mesh):
        if kind == "prefill+decode":
            return None
        return D.DeepSeekServing.step_fn(kind, frozen, quant, mesh)


def test_engine_streams_are_those_of_the_two_program_path():
    """Three prompts (one chunk, three, five) through an engine whose chunks
    carry the running rows and through one whose serving object offers no
    such program: the same tokens; the carrying engine launched the one
    program where there was work of both kinds; pairs and latent contexts
    add up to the same totals either way (each token goes through the
    layers once and attends to the same columns, whichever program runs
    it)."""
    from paddle_tpu.inference import engine as engine_mod
    _, c, params = tiny()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 70, 150)]
    one, out1 = serve(params, c, prompts, 8)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_serving_for", lambda config: _TwoPrograms)
        two, out2 = serve(params, c, prompts, 8)
    assert out1 == out2 and sorted(out1) == [0, 1, 2]
    assert one._chunk_carries and not two._chunk_carries
    assert ("prefill+decode", 32, 4) in one._compiled
    assert all(k[0] != "prefill+decode" for k in two._compiled)
    w1, w2 = one.work_totals, two.work_totals
    assert w1["prefill_chunks_total"] == w2["prefill_chunks_total"] == 9
    # every chunk but the first of all has a row running beside it
    assert w1["prefill_chunks_with_decode_total"] == 8
    assert w2["prefill_chunks_with_decode_total"] == 0
    for name in ("decode_rows_total", "prefill_tokens_total",
                 "moe_pairs_total", "moe_local_pairs_total",
                 "mla_decode_ctx_tokens_total",
                 "mla_prefill_ctx_tokens_total"):
        assert w1[name] == w2[name] > 0, name
    # an expert both parts hit is read, and counted, once
    assert 0 < w1["moe_expert_hits_total"] <= w2["moe_expert_hits_total"]
    assert one.registry.snapshot()["mla_decode_ctx_tokens_total"] \
        == w1["mla_decode_ctx_tokens_total"]


def test_engine_prefill_then_decode_against_the_reference():
    """Prompts of 5, 70 (three chunks) and 150 tokens (five chunks, two
    blocks) through ``submit()`` / ``step()``, eight new tokens each: every
    served token is the reference's best or within ``LOGIT_TOL`` of it, and
    the program's own logits of a whole prompt lie within it too."""
    m, c, params = tiny()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 70, 150)]
    eng, out = serve(params, c, prompts, 8)
    assert sorted(out) == [0, 1, 2] and all(len(o) == 8 for o in out.values())
    for i, p in enumerate(prompts):
        _, gaps = logit_gaps(m, params, p, out[i])
        assert gaps.max() <= LOGIT_TOL, (i, gaps)
    # the first chunk of all runs alone and a decode step follows it; every
    # later chunk carries the rows that run by then: three programs
    assert eng._chunk_carries
    assert {("prefill", 32), ("decode", 1), ("prefill+decode", 32, 4)} \
        <= set(eng._compiled)
    assert all(k[0] in ("prefill", "decode", "prefill+decode")
               for k in eng._compiled)
    assert eng.work_totals["prefill_chunks_total"] == 1 + 3 + 5
    assert eng.work_totals["prefill_chunks_with_decode_total"] == 3 + 5
    # the counters the model's steps return: pairs = tokens x 4 x 2 layers
    w = eng.work_totals
    tokens = w["prefill_tokens_total"] + w["decode_rows_total"]
    assert w["moe_pairs_total"] == tokens * 4 * 2
    assert 0 < w["moe_local_pairs_total"] < w["moe_pairs_total"]
    assert 0 < w["moe_expert_hits_total"] <= w["moe_local_pairs_total"]
    assert w["mla_decode_ctx_tokens_total"] > 0 \
        and w["mla_prefill_ctx_tokens_total"] >= 5 + 70 + 150
    snap = eng.registry.snapshot()
    assert snap["moe_local_pairs_total"] == w["moe_local_pairs_total"]
    # logits, not tokens: one chunk's logits against the reference's row
    # (the step function's: the jitted program returns their greedy head)
    pool = D.init_latent_pool(c, 4, 128)
    ids = np.zeros(32, np.int32)
    ids[:5] = prompts[0]
    chunk = (jnp.asarray([1, 0, 0], jnp.int32), np.int32(0),
             jnp.asarray(ids), np.int32(5))
    logits, _, _ = D.deepseek_paged_prefill_chunk(params, (pool,), *chunk, c)
    ref = fam.logits_after(params, m, prompts[0], 1, 512, 16)[0]
    assert rms_gap(np.asarray(logits), ref) <= LOGIT_RMS_TOL
    token, finite, _, _ = D._jitted_paged_prefill(c)(params, pool, *chunk)
    assert int(token) == int(np.argmax(logits)) and bool(finite)
    fp8 = fam.logits_after(params, m, prompts[0], 1, 512, 16, mode="fp8")[0]
    assert rms_gap(fp8, ref) > LOGIT_RMS_TOL


def test_a_lower_precision_does_not_pass():
    """The reference in fp8 in the program's place picks tokens that lie
    outside ``LOGIT_TOL``; the reference in bfloat16 lies outside ``REF_TOL``
    of the float32 one."""
    m, c, params = tiny()
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 256, 60).tolist()
    _, out = serve(params, c, [prompt], 12)
    ref, gaps = logit_gaps(m, params, prompt, out[0])
    assert gaps.max() <= LOGIT_TOL
    toks = prompt + out[0][:-1]
    low = fam.logits_after(params, m, toks, 12, 512, 16, mode="fp8")
    rows = np.arange(12)
    fp8_gap = (ref.max(-1) - ref[rows, low.argmax(-1)]) / ref.std(-1)
    assert fp8_gap.max() > LOGIT_TOL
    b16 = fam.logits_after(params, m, toks, 12, 512, 16, mode="bf16")
    assert np.abs(b16 - ref).max() > REF_TOL * ref.std()


def test_prefix_cache_shares_latent_blocks():
    """Block hashing over the table: a second request with the same first
    block hits it, and serves what it serves without the cache."""
    m, c, params = tiny()
    rng = np.random.default_rng(7)
    head = rng.integers(0, 256, 128).tolist()
    prompts = [head + rng.integers(0, 256, 20).tolist() for _ in range(2)]
    _, plain = serve(params, c, prompts, 4, max_batch=1)
    eng, cached = serve(params, c, prompts, 4, max_batch=1,
                        prefix_cache=True)
    assert eng.cache.hits == 1 and eng.cache.hit_tokens == 128
    assert cached == plain


@pytest.mark.parametrize("over, what", [
    (dict(mp=2), "mp > 1"), (dict(kv_dtype="int8"), "int8"),
    (dict(speculative=True), "speculative")])
def test_out_of_scope_raises_at_construction(over, what):
    _, c, params = tiny()
    with pytest.raises(NotImplementedError, match=what):
        InferenceEngine(params, c, ServeConfig(**over))


def test_a_draft_model_is_refused():
    _, c, params = tiny()
    with pytest.raises(NotImplementedError, match="draft"):
        InferenceEngine(params, c, ServeConfig(), draft_params=params,
                        draft_config=c)


def test_the_cache_is_one_latent_pool():
    _, c, params = tiny()
    eng = InferenceEngine(params, c, ServeConfig(num_blocks=6))
    assert len(eng.kv) == 1
    assert eng.kv[0].shape == (3, 6, c.kv_lora_rank + c.qk_rope_head_dim, 128)
    full = D.DeepSeekConfig()
    assert full.latent_width == 576
