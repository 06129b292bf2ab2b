"""Laguna on the serving path, at a tiny size on the CPU (Pallas in the
interpreter), every kind of layer in it as the published pattern orders
them: a full dense layer, three window expert layers, a full expert layer;
groups of 6 and 9 query heads a KV head; a window of 16 on blocks of 4 with
chunks of 8; 8 of 32 experts held, 4 a token. Against the float32 reference
of ``chipbench/families/laguna.py`` on seeded weights: on LOGITS through all
three programs (the un-jitted steps' logits, prefill then decode past four
windows, and the chunk that carries the batch), and on the gap of each
served token's reference logit to the reference's best where the engine
serves. Also: the expert share, a ring that does not grow with the context,
what each mechanism moves, what is out of scope, the counters.

``F32_TOL``, and why: the program in float32 and the reference differ by
the order of their sums alone (paged and base-2 softmaxes, experts gathered
by tile), under 1e-5 of the logits' standard deviation here. Each mechanism
taken out of the reference (the window, the gate, the partial rotary)
moves the logits by several standard deviations, hundreds of thousands of
times the tolerance."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from chipbench import spec, weights
from chipbench.families import laguna as fam
from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.models import deepseek as D
from paddle_tpu.models import laguna as La
from paddle_tpu.ops import _common

F32_TOL = 1e-4          # of the logits' standard deviation
BS, CHUNK = 4, 8
SCALE = 5.0             # matrices at 0.1 (the made 0.02 x 5): attention
#                         and routing far from uniform at hidden 64

TINY_M = dict(fam.rehearsal(json.load(open(os.path.join(
    spec.HERE, "configs", "laguna-s-2.1.json")))), vocab_size=256,
    torch_dtype="float32")


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


@pytest.fixture(scope="module")
def model():
    """(m, the program's config, the weights)."""
    m = TINY_M
    w = weights.make_weights(fam.leaves(m), 3, dtype=jnp.float32)
    w = jax.tree_util.tree_map_with_path(
        lambda p, a: a if str(p[-1].key).endswith("norm") else a * SCALE, w)
    # at the starts the engine and the reference take (``fam.starts``; the
    # reference applies it again, which moves a leaf by rounding alone)
    return m, fam.laguna_config(m), fam.starts(w, m)


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 255, size=n).tolist() for n in sizes]


def _reference(model, toks, last, mode="f32"):
    m, _, w = model
    return fam.logits_after(w, m, toks, last, -(-len(toks) // 64) * 64, last,
                            mode=mode)


def _err(got, ref):
    """The widest gap between two rows of logits, in standard deviations of
    the reference's row."""
    return float((np.abs(got - ref).max(-1) / ref.std(-1)).max())


def _caches(c, n_blocks, slots=4):
    pools = La.init_paged_kv_pool(c, n_blocks, BS)
    rings = La.init_rings(c, slots, La.ring_size(c, CHUNK, BS), BS)
    # stale bytes in every ring: a sequence must never read what its slot
    # held before it
    return pools, tuple(jnp.full_like(a, 7.0) for a in rings)


_prefill = jax.jit(La.laguna_paged_prefill_chunk, static_argnums=(8,))
_decode = jax.jit(La.laguna_paged_decode_step, static_argnums=(7,))
_carry = jax.jit(La.laguna_paged_prefill_chunk_with_decode,
                 static_argnums=(12,))


def _chunk_ids(prompt, start):
    n_live = min(CHUNK, len(prompt) - start)
    ids = np.zeros(CHUNK, np.int32)
    ids[:n_live] = prompt[start:start + n_live]
    return jnp.asarray(ids), n_live


def _steps_logits(model, prompt, n_new):
    """Prefill in chunks, then decode through the un-jitted steps, the
    sequence in slot 2 with its blocks in no order and a padding row beside
    it: the logits after the prompt and after each fed token, [1 + n_new,
    V], and the tokens fed."""
    _, c, w = model
    nb = -(-(len(prompt) + n_new) // BS)
    pools, rings = _caches(c, nb + 1)
    table = jnp.asarray(list(range(nb, 0, -1)) + [0, 0], jnp.int32)
    for start in range(0, len(prompt), CHUNK):
        ids, n_live = _chunk_ids(prompt, start)
        logits, *rest = _prefill(w, pools, rings, table, jnp.int32(start),
                                 ids, jnp.int32(n_live), jnp.int32(2), c)
        pools, rings = tuple(rest[:2]), tuple(rest[2:4])
    out, toks = [np.asarray(logits)], list(prompt)
    tables = jnp.stack([table, jnp.zeros_like(table)])
    for _ in range(n_new):
        toks.append(int(out[-1].argmax()))
        logits, *rest = _decode(
            w, pools, rings, tables,
            jnp.asarray([len(toks) - 1, 0], jnp.int32),
            jnp.asarray([toks[-1], 0], jnp.int32),
            jnp.asarray([2, 0], jnp.int32), c)
        pools, rings = tuple(rest[:2]), tuple(rest[2:4])
        out.append(np.asarray(logits)[0])
    return np.stack(out), toks


@pytest.fixture(scope="module")
def served(model):
    """A prompt of 37 tokens, then 40 decoded: past four windows of 16."""
    prompt = _prompts([37])[0]
    return _steps_logits(model, prompt, 40)


def test_prefill_then_decode_match_the_reference_past_four_windows(
        model, served):
    got, toks = served
    assert len(toks) > 4 * 16
    ref = _reference(model, toks, got.shape[0])
    assert np.isfinite(got).all()
    assert _err(got, ref) < F32_TOL


@pytest.mark.parametrize("variant", ["nowindow", "nogate", "fullrotary"])
def test_each_mechanism_moves_the_logits(model, served, variant):
    """The reference without the window (window layers attend the whole
    context), without the gate, or with the full layers' rope over the
    whole head is another model: the program parts from it by far more
    than the tolerance."""
    got, toks = served
    ref = _reference(model, toks, got.shape[0], mode="f32:" + variant)
    assert _err(got, ref) > 100 * F32_TOL


def test_the_chunk_carrying_the_batch_matches_the_reference(model):
    """Sequence A decodes in slot 1 while B's prompt goes through in chunks
    that carry A's row (and a padding row), in slot 3: both streams' logits
    against the reference, A's well past four windows."""
    _, c, w = model
    pa_, pb = _prompts([29, 45], seed=1)
    pools, rings = _caches(c, 41)
    ta = jnp.asarray(list(range(1, 21)) + [0] * 4, jnp.int32)
    tb = jnp.asarray(list(range(40, 20, -1)) + [0] * 4, jnp.int32)
    for start in range(0, len(pa_), CHUNK):
        ids, n_live = _chunk_ids(pa_, start)
        la, *rest = _prefill(w, pools, rings, ta, jnp.int32(start), ids,
                             jnp.int32(n_live), jnp.int32(1), c)
        pools, rings = tuple(rest[:2]), tuple(rest[2:4])
    a_out, a_toks = [np.asarray(la)], list(pa_)
    tables = jnp.stack([ta, jnp.zeros_like(ta)])
    slots = jnp.asarray([1, 0], jnp.int32)

    def row_in():
        a_toks.append(int(a_out[-1].argmax()))
        return (tables, jnp.asarray([len(a_toks) - 1, 0], jnp.int32),
                jnp.asarray([a_toks[-1], 0], jnp.int32))
    for _ in range(30):               # A alone, to past 3.5 windows
        lr, *rest = _decode(w, pools, rings, *row_in(), slots, c)
        pools, rings = tuple(rest[:2]), tuple(rest[2:4])
        a_out.append(np.asarray(lr)[0])
    for start in range(0, len(pb), CHUNK):   # B's chunks carry A's row
        ids, n_live = _chunk_ids(pb, start)
        lb, lr, *rest = _carry(w, pools, rings, tb, jnp.int32(start), ids,
                               jnp.int32(n_live), *row_in(), jnp.int32(3),
                               slots, c)
        pools, rings = tuple(rest[:2]), tuple(rest[2:4])
        a_out.append(np.asarray(lr)[0])
    assert len(a_toks) > 4 * 16
    ref_a = _reference(model, a_toks, len(a_out))
    assert _err(np.stack(a_out), ref_a) < F32_TOL
    ref_b = _reference(model, pb, 1)
    assert _err(np.asarray(lb)[None], ref_b) < F32_TOL


def test_expert_shares_add_up_to_the_uncut_layer(model):
    """Four chips' shares of an expert layer (8 of 32 experts each, the
    shared expert on every chip): their outputs, the shared expert counted
    once, add up to the layer with every expert held, and that is the
    reference's whole layer."""
    m, c, w = model
    p = jax.tree_util.tree_map(lambda a: a[0], {
        k: v for k, v in w["moe"].items() if k != "experts"})
    # a layer whose stack holds all 32 experts: the four chips' draws
    whole = {k: jnp.concatenate([v[0] * (1 + 0.1 * i) for i in range(4)])[None]
             for k, v in w["moe"]["experts"].items()}
    x = jnp.asarray(np.random.RandomState(5).normal(size=(12, c.hidden_size)),
                    jnp.float32)
    y = D.rms_norm(x, p["post_norm"], c.rms_norm_eps)
    live = jnp.ones((12,), bool)
    shared = D.swiglu(y, p["shared"]["gate"], p["shared"]["up"],
                      p["shared"]["down"])
    parts = []
    for chip in range(4):
        share = {k: v[:, 8 * chip:8 * chip + 8] for k, v in whole.items()}
        out, _ = D.moe_ffn(y, live, p, share, 0,
                           dataclasses.replace(c, expert_offset=8 * chip))
        parts.append(np.asarray(out - shared))
    uncut, _ = D.moe_ffn(y, live, p, whole, 0,
                         dataclasses.replace(c, n_local_experts=32))
    np.testing.assert_allclose(sum(parts) + np.asarray(shared),
                               np.asarray(uncut), rtol=1e-5, atol=1e-5)
    ref = fam.moe_sublayer(dict(p, experts={k: v[0] for k, v in
                                            whole.items()}),
                           x, dict(m, num_experts=32), "f32", held=(0, 32))
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(ref - x),
                               rtol=1e-4, atol=1e-4)


def _engine(model, **kw):
    """The engine's blocks are whole lane tiles (128 positions): its window
    layers' rings are 2 blocks, so a sequence past 256 positions wraps."""
    _, c, w = model
    serve = dict(block_size=128, num_blocks=16, max_batch=4,
                 prefill_chunk=32, max_seq_len=512)
    serve.update(kw)
    return InferenceEngine(w, c, ServeConfig(**serve))


def _gap(model, prompt, served):
    toks = list(prompt) + list(served[:-1])
    ref = _reference(model, toks, len(served))
    rows = np.arange(len(served))
    return float(((ref.max(-1) - ref[rows, np.asarray(served)])
                  / ref.std(-1)).max())


def test_the_engine_serves_what_the_reference_puts_first(model):
    """Four requests through the engine (chunks that carry the batch,
    launched ahead, slots reused): every served token is the reference's
    first to rounding."""
    eng = _engine(model)
    prompts = _prompts([237, 12, 60, 21], seed=2)
    eng.run([Request(p, max_new_tokens=n, request_id=i)
             for i, (p, n) in enumerate(zip(prompts, [40, 9, 24, 40]))])
    outs = {s.req.request_id: s.generated for s in eng.finished}
    assert sorted(outs) == [0, 1, 2, 3]
    for i, p in enumerate(prompts):
        assert len(outs[i]) == [40, 9, 24, 40][i]
        assert _gap(model, p, outs[i]) < 1e-3
    assert eng.work_totals["prefill_chunks_with_decode_total"] > 0


def test_a_ring_does_not_grow_with_the_context(model):
    """A sequence's ring bytes are fixed by the window and the chunk: the
    gauge reads the same at 20 tokens of context as past four windows, while
    the full layers' blocks grow with the context."""
    eng = _engine(model)
    eng.submit(Request(_prompts([20])[0], max_new_tokens=300,
                       request_id=0))
    per_slot = sum(a.nbytes for a in eng.state) // eng.slots.num_slots
    # k and v, 3 window layers, 2 blocks of 32 columns x 128, float32
    assert per_slot == 2 * 3 * 2 * 32 * 128 * 4
    readings = []
    while not eng.idle():
        eng.step()
        seq = next(iter(eng.active), None)
        if seq is not None:
            readings.append((seq.n_cached, len(seq.blocks),
                             eng.registry.snapshot()["window_ring_bytes"]))
    assert readings[0][0] <= 32 and readings[-1][0] > 256 + 16
    assert {r[2] for r in readings} == {per_slot}
    assert readings[-1][1] == 3 * readings[0][1]
    assert eng.registry.snapshot()["window_ring_bytes"] == 0


def test_counts_name_the_keys_each_kind_attended(model):
    """One prompt and one token: only chunks ran. Each window layer's
    queries attended min(position + 1, 16) keys, each full layer's all of
    them."""
    eng = _engine(model)
    eng.run([Request(_prompts([45])[0], max_new_tokens=1, request_id=0)])
    reach = np.arange(1, 46)
    snap = eng.registry.snapshot()
    assert snap["window_attend_tokens_total"] == 3 * np.minimum(reach,
                                                                16).sum()
    assert snap["full_attend_tokens_total"] == 2 * reach.sum()
    assert snap["moe_pairs_total"] == 45 * 4 * 4       # tokens x k x layers


@pytest.mark.parametrize("over", [
    dict(mp=2), dict(kv_dtype="int8"), dict(speculative=True),
    dict(prefix_cache=True)], ids=["mp", "int8", "speculative", "prefix"])
def test_out_of_scope_is_refused(model, over):
    with pytest.raises(NotImplementedError, match="Laguna serving"):
        _engine(model, **over)
