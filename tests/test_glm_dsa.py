"""GLM-5.2's learned sparse attention on the serving path, at a tiny size on
the CPU with seeded weights (Pallas in the interpreter): ``index_topk`` 16,
contexts to 208, layers of both kinds (one dense ``full`` layer, expert
layers ``shared``, ``full``, ``shared``), 8 of 32 experts held, one group.
Through ``InferenceEngine.submit()`` / ``step()``: chunked prefill, decode
and the chunk that carries the decode batch against the float32 reference of
``chipbench/families/glm_dsa.py``; the sparse path against the dense latent
path where the selection is everything; a freed block given to another
sequence; copy-on-write over both pools; what is out of scope.

Tolerances, and why. The selection is a hard choice: a row attends exactly
16 positions, and which they are hangs on the order of float scores. In
FLOAT32 the program and the reference make the same choices (their scores
differ by 1e-6 of the scores' spread; no near-tie was decided the other way
on these prompts), so the float32 engine's served tokens are the reference's
best (``F32_TOL``) through all three programs at contexts 13 times
``index_topk``: that proves the wiring (what is selected, by whom, for which
layers, over which cache). The bfloat16 program's index scores differ from
the reference's by 0.5 % of their spread, which at 200 candidates decides
one near-tie of the 16th score the other way in 6 % of the rows of a layer;
with random weights at width 64 the swapped token carries a random sixteenth
of the attention and the logits move by up to 2 standard deviations, as far
as the fp8 control's. So in bfloat16 the engine is held to the reference
where the selection is everything (contexts up to ``index_topk``:
``LOGIT_TOL``, between the program's widest gap there, 0.10, and the fp8
control's, over 0.4), and there it serves what the dense latent path
serves. At the cell's size a swapped token carries a 2048th of a nearly
uniform attention; the cell's own limit is set on the chip (PERF.md)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from chipbench import spec
from chipbench.families import glm_dsa as fam
from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.inference import engine as engine_mod
from paddle_tpu.models import deepseek as D
from paddle_tpu.ops import _common

F32_TOL = 1e-3          # of the logits' standard deviation, float32 program
LOGIT_TOL = 0.2         # the same unit, bfloat16 program, selection dense

TINY_M = fam.rehearsal(json.load(open(os.path.join(
    spec.HERE, "configs", "glm-5.2.json"))))
TINY_M["vocab_size"] = 256


def tiny(dtype="bfloat16", std=0.3, **over):
    """(m, the program's config, seeded weights)."""
    m = dict(TINY_M, torch_dtype=dtype, **over)
    c = fam.glm_config(m)
    return m, c, D.init_deepseek_params(c, seed=3, std=std)


def serve(params, c, prompts, new, **serve_over):
    eng = InferenceEngine(params, c, ServeConfig(**dict(dict(
        block_size=128, num_blocks=12, max_batch=4, prefill_chunk=32,
        max_seq_len=384), **serve_over)))
    for i, p in enumerate(prompts):
        assert eng.submit(Request(p, new, request_id=i)).accepted
    while not eng.idle():
        eng.step()
    return eng, {s.req.request_id: s.generated for s in eng.finished}


def gaps(m, params, prompt, out, mode=None):
    """How far below the reference's best each served token lies, in
    standard deviations of its position's logits; with ``mode``, the tokens
    that precision of the reference puts first in the served ones' place."""
    toks = list(prompt) + list(out[:-1])
    ref = fam.logits_after(params, m, toks, len(out), 512, 16)
    if mode is not None:
        out = fam.logits_after(params, m, toks, len(out), 512, 16,
                               mode=mode).argmax(-1)
    return (ref.max(-1) - ref[np.arange(len(out)), np.asarray(out)]) \
        / ref.std(-1)


def test_config_is_the_published_kind():
    _, c, params = tiny()
    assert c.n_group == 1 and c.rope_factor == 1.0
    assert c.softmax_scale == (16 + 8) ** -0.5          # no YaRN term
    assert c.v_head_dim > c.qk_nope_head_dim
    assert c.indexer_types == ("full", "shared", "full", "shared")
    assert c.n_index_layers == 2
    # a full layer holds an indexer, a shared one none; the expert layers'
    # are stacked in their order
    assert set(params["dense"][0]["indexer"]) == set(D.indexer_shapes(c))
    assert params["moe"]["indexer"]["wq_b"].shape == (1, 48, 4 * 16)
    assert params["moe"]["q_a"].shape[0] == 3
    full = fam.glm_config(json.load(open(os.path.join(
        spec.HERE, "configs", "glm-5.2.json"))))
    assert (full.latent_width, full.index_head_dim, full.index_topk) \
        == (576, 128, 2048)
    assert full.softmax_scale == 256 ** -0.5


@pytest.mark.parametrize("kinds", [("shared", "full", "full", "shared"),
                                   ("full", "full", "full"),
                                   ("full", "shared", "full", "dense")])
def test_layers_are_named_full_or_shared_and_the_first_is_full(kinds):
    with pytest.raises(ValueError, match="indexer_types"):
        dataclasses.replace(D.glm_dsa_tiny(), indexer_types=kinds)


def test_the_cache_is_two_pools_under_one_table():
    _, c, params = tiny()
    eng = InferenceEngine(params, c, ServeConfig(num_blocks=6))
    assert [a.shape for a in eng.kv] == [
        (4, 6, c.kv_lora_rank + c.qk_rope_head_dim, 128),   # every layer
        (2, 6, c.index_head_dim, 128)]                      # the full ones
    assert eng.stats()["pool_bytes_per_rank"] == 2 * (4 * 6 * 40 * 128
                                                      + 2 * 6 * 16 * 128)


def test_float32_engine_agrees_with_the_reference_through_all_programs():
    """Prompts of 5, 70, 150 and 200 tokens, eight new tokens each, through
    chunks of 32: the first chunk runs alone, a decode step follows it, every
    later chunk carries the rows that run by then. Every served token is the
    reference's best; the fp8 reference's choices are not."""
    m, c, params = tiny("float32")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 70, 150, 200)]
    eng, out = serve(params, c, prompts, 8)
    assert sorted(out) == [0, 1, 2, 3]
    assert {k[0] for k in eng._compiled} == {"prefill", "decode",
                                             "prefill+decode"}
    assert eng.work_totals["prefill_chunks_with_decode_total"] > 0
    for i, p in enumerate(prompts):
        assert gaps(m, params, p, out[i]).max() <= F32_TOL, i
    assert max(gaps(m, params, p, out[i], mode="fp8").max()
               for i, p in enumerate(prompts)) > LOGIT_TOL
    # logits, not tokens: one chunk's logits against the reference's row
    # (the step function's: the jitted program returns their greedy head)
    ids = np.zeros(32, np.int32)
    ids[:5] = prompts[0]
    chunk = (jnp.asarray([1, 0, 0], jnp.int32), np.int32(0),
             jnp.asarray(ids), np.int32(5))
    logits = D.deepseek_paged_prefill_chunk(
        params, D.DeepSeekServing.init_cache(c, 4, 128, "auto"), *chunk,
        c)[0]
    ref = fam.logits_after(params, m, prompts[0], 1, 512, 16)[0]
    assert np.sqrt(np.mean((np.asarray(logits) - ref) ** 2)) \
        <= F32_TOL * ref.std()
    token, finite = D._jitted_paged_step("prefill", c)(
        params, *D.DeepSeekServing.init_cache(c, 4, 128, "auto"), *chunk)[:2]
    assert int(token) == int(np.argmax(logits)) and bool(finite)
    # the counters: every row's reach, at most index_topk of it selected in
    # each of the 4 layers, all of it scored by each of the 2 indexers
    reach = [t + 1 for p in prompts for t in range(len(p) + 7)]
    w = eng.work_totals
    assert w["dsa_ctx_tokens_total"] == 4 * sum(reach)
    assert w["dsa_selected_tokens_total"] == 4 * sum(min(r, 16)
                                                     for r in reach)
    assert w["dsa_index_pairs_total"] == 2 * sum(reach)
    assert eng.registry.snapshot()["dsa_selected_tokens_total"] \
        == w["dsa_selected_tokens_total"]


def test_a_shared_layer_runs_no_indexer():
    """Tracing the three programs: an index kernel once a ``full`` layer (the
    dense one and the run of one ``full`` expert layer), the latent kernel
    in every layer (the dense one and three runs)."""
    _, c, params = tiny()
    pools = D.DeepSeekServing.init_cache(c, 4, 128, "auto")
    i32 = jnp.int32
    chunk = (jnp.zeros(3, i32), i32(0), jnp.zeros(32, i32), i32(5))
    rows = (jnp.zeros((2, 3), i32), jnp.zeros(2, i32), jnp.zeros(2, i32))
    for kind, args, index, attend in (
            ("prefill", chunk, {"paged.dsa_index_prefill": 2},
             {"paged.mla_prefill": 4}),
            ("decode", rows, {"paged.dsa_index_decode": 2},
             {"paged.mla_decode": 4}),
            ("prefill+decode", chunk + rows,
             {"paged.dsa_index_prefill": 2, "paged.dsa_index_decode": 2},
             {"paged.mla_prefill": 4, "paged.mla_decode": 4})):
        mark = _common.snapshot_kernel_costs()
        jax.eval_shape(lambda *a: D._PAGED_STEPS[kind][0](
            params, a[:2], *a[2:], c), *pools, *args)
        calls = {k: v["calls"] for k, v in
                 _common.kernel_costs_since(mark).items()
                 if k.startswith(("paged.dsa", "paged.mla"))}
        assert calls == dict(index, **attend), kind


def test_bfloat16_engine_where_the_selection_is_everything():
    """``index_topk`` 256 over contexts to 157: every position is selected,
    the indexers still run and write their keys. The engine is within
    ``LOGIT_TOL`` of the reference, the fp8 control is not, and the tokens
    are those of the dense latent path (the same weights without indexers
    through the programs DeepSeek-V3 runs)."""
    m, c, params = tiny(index_topk=256)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 60, 150)]
    eng, out = serve(params, c, prompts, 8)
    assert len(eng.kv) == 2 and float(jnp.abs(eng.kv[1]).max()) > 0
    worst = max(gaps(m, params, p, out[i]).max()
                for i, p in enumerate(prompts))
    assert worst <= LOGIT_TOL, worst
    assert max(gaps(m, params, p, out[i], mode="fp8").max()
               for i, p in enumerate(prompts)) > 2 * LOGIT_TOL
    w = eng.work_totals
    assert w["dsa_selected_tokens_total"] == w["dsa_ctx_tokens_total"] > 0
    dense_c = dataclasses.replace(c, indexer_types=())
    dense_p = dict(params, dense=[{k: v for k, v in p.items()
                                   if k != "indexer"}
                                  for p in params["dense"]],
                   moe={k: v for k, v in params["moe"].items()
                        if k != "indexer"})
    dense_eng, dense_out = serve(dense_p, dense_c, prompts, 8)
    assert len(dense_eng.kv) == 1
    assert dense_out == out
    assert dense_eng.work_totals["dsa_ctx_tokens_total"] == 0


def test_a_freed_block_leaks_no_stale_index_key():
    """Three blocks in all: a 200-token request fills two, ends and frees
    them; the next request gets them back with the first one's latent
    columns and index keys still in them, and serves what it serves on a
    fresh engine: a selection never reaches a stale key."""
    _, c, params = tiny()
    rng = np.random.default_rng(8)
    first, second = (rng.integers(0, 256, n).tolist() for n in (200, 150))
    eng, _ = serve(params, c, [first], 4, num_blocks=4, max_batch=1)
    stale = np.asarray(eng.kv[1], np.float32)
    assert np.abs(stale[:, 1:]).max() > 0 and eng.pool.used_blocks == 0
    assert eng.submit(Request(second, 6, request_id=7)).accepted
    while not eng.idle():
        eng.step()
    again = eng.finished[-1].generated
    _, fresh = serve(params, c, [second], 6, num_blocks=4, max_batch=1)
    assert again == fresh[0]


def test_copy_on_write_copies_both_pools():
    """``_cow_span`` on a genuinely shared block: the writer's copy starts
    with the block's latent columns AND its index keys."""
    _, c, params = tiny()
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 256, 300).tolist()
    eng, _ = serve(params, c, [prompt], 3, prefix_cache=True)
    hit = eng.cache.match(prompt, 2)
    assert len(hit) == 2
    eng.pool.acquire(hit)
    eng.pool.acquire(hit)
    b = hit[0]
    before = [np.asarray(a[:, b], np.float32).copy() for a in eng.kv]
    assert all(np.abs(x).max() > 0 for x in before)
    writer = engine_mod._Seq(Request(prompt, max_new_tokens=1,
                                     request_id=99), 0.0)
    writer.blocks = list(hit)
    assert eng._cow_span(writer, 0, 1)
    nb = writer.blocks[0]
    assert nb != b and eng.stats()["prefix_cache"]["cow_copies"] == 1
    for a, old in zip(eng.kv, before):
        np.testing.assert_array_equal(np.asarray(a[:, nb], np.float32), old)
        np.testing.assert_array_equal(np.asarray(a[:, b], np.float32), old)


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
