"""Falcon-H1 on the serving path, at a tiny size on the CPU with seeded
weights at the family's starts (Pallas in the interpreter): a Mamba-2 mixer
beside a group of 5 query heads a KV head in each of two layers, the state
of the scan in slots beside the paged KV pool. Chunked prefill and decode
against the float32 reference of ``chipbench/families/falcon_h1.py``, on
LOGITS where a program returns them (the un-jitted steps) and on the gap of
each served token's reference logit to the reference's best where the
engine serves (it never holds logits); eviction and recompute; slots given
to other sequences; what is out of scope; the engine's fault contract for a
model whose step is not idempotent.

``F32_TOL``, and why: the program in float32 and the reference differ by
the order of their sums alone (a 16-token piece of the scan at once where
the reference folds token by token; a paged softmax in base 2), 1e-5 of the
logits' standard deviation here. At 1e-3 the same program in bfloat16 fails
(1e-2 and more), and so does the float32 reference with ``S C`` left out of
``y`` (``norecur``: 4e-2) and with the state zeroed at every 512th position
(``reset``)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from chipbench import spec, weights
from chipbench.families import falcon_h1 as fam
from paddle_tpu.inference import (InferenceEngine, PoisonError, Request,
                                  ServeConfig)
from paddle_tpu.models import falcon_h1 as H
from paddle_tpu.ops import _common
from paddle_tpu.testing import faults

F32_TOL = 1e-3          # of the logits' standard deviation

TINY_M = dict(fam.rehearsal(json.load(open(os.path.join(
    spec.HERE, "configs", "falcon-h1-34b.json")))), vocab_size=256)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULTS", "1")
    with _common.interpret_mode(True):
        yield
    faults.disarm()


def tiny(dtype="float32"):
    """(m, the program's config, the made weights, those at their starts)."""
    m = dict(TINY_M, torch_dtype=dtype)
    w = weights.make_weights(fam.leaves(m), 3, dtype=jnp.dtype(dtype))
    return m, fam.falcon_config(m), w, fam.starts(w, m)


@pytest.fixture(scope="module")
def model():
    return tiny()


def _engine(model, **kw):
    _, c, _, ws = model
    serve = dict(block_size=128, num_blocks=12, max_batch=4, prefill_chunk=32,
                 max_seq_len=512)
    serve.update(kw)
    return InferenceEngine(ws, c, ServeConfig(**serve), record_events=True)


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 255, size=n).tolist() for n in sizes]


def _gap(model, prompt, served, mode="f32"):
    """The widest gap of a served stream: how far each served token's
    reference logit lies below the reference's best, in standard
    deviations of the position's logits (``chipbench/serve.py``
    ``token_gaps``)."""
    m, _, w, _ = model
    toks = list(prompt) + list(served[:-1])
    padded = -(-len(toks) // 64) * 64
    ref = fam.logits_after(w, m, toks, len(served), padded, len(served),
                           mode=mode)
    rows = np.arange(len(served))
    return float(((ref.max(-1) - ref[rows, np.asarray(served)])
                  / ref.std(-1)).max())


# -- logits: the steps against the reference -------------------------------------

def _steps_logits(model, prompt, n_new, chunk=32):
    """Prefill in chunks then decode through the un-jitted steps, one
    sequence in slot 2, its blocks in no order: the logits after the prompt and
    after each fed token, [1 + n_new, V]."""
    _, c, _, ws = model
    nb = -(-(len(prompt) + n_new) // 128)
    pools = H.init_paged_kv_pool(c, nb + 1, 128)
    state = H.init_state(c, 4)
    # stale bytes in the slot: a first chunk must not read them
    state = tuple(jnp.full_like(a, 7.0) for a in state)
    # the sequence's blocks in no order, the table's tail at the null block
    table = jnp.asarray(list(range(nb, 0, -1)) + [0, 0], jnp.int32)
    prefill = jax.jit(H.falcon_h1_paged_prefill_chunk, static_argnums=(8,))
    decode = jax.jit(H.falcon_h1_paged_decode_step, static_argnums=(7,))
    out = []
    for start in range(0, len(prompt), chunk):
        n_live = min(chunk, len(prompt) - start)
        ids = np.zeros(chunk, np.int32)
        ids[:n_live] = prompt[start:start + n_live]
        logits, *rest = prefill(ws, pools, state, table, jnp.int32(start),
                                jnp.asarray(ids), jnp.int32(n_live),
                                jnp.int32(2), c)
        pools, state = tuple(rest[:2]), tuple(rest[2:4])
    out.append(np.asarray(logits))
    toks = list(prompt)
    for _ in range(n_new):
        toks.append(int(out[-1].argmax()))
        # a padding row rides beside the live one, at the null slot
        logits, *rest = decode(
            ws, pools, state, jnp.stack([table, jnp.zeros_like(table)]),
            jnp.asarray([len(toks) - 1, 0], jnp.int32),
            jnp.asarray([toks[-1], 0], jnp.int32),
            jnp.asarray([2, 0], jnp.int32), c)
        pools, state = tuple(rest[:2]), tuple(rest[2:4])
        out.append(np.asarray(logits[0]))
    return np.stack(out), toks


def _ref(model, toks, last, mode="f32"):
    m, _, w, _ = model
    padded = -(-len(toks) // 64) * 64
    return fam.logits_after(w, m, toks, last, padded, last, mode=mode)


def test_prefill_in_chunks_then_decode_matches_the_reference(model):
    """70 tokens in chunks of 32 (the state and the convolution's columns
    carried over two chunk boundaries, the last chunk 6 live tokens of 32),
    then 6 decode steps: every step's logits against the reference's full
    forward over the whole sequence."""
    prompt = _prompts([70], seed=1)[0]
    got, toks = _steps_logits(model, prompt, 6)
    ref = _ref(model, toks, 7)
    err = np.abs(got - ref).max() / ref.std()
    assert err < F32_TOL, err
    # the comparison would catch a model without its recurrence
    alt = _ref(model, toks, 7, mode="f32:norecur")
    assert np.abs(got - alt).max() / ref.std() > 10 * F32_TOL


def test_bfloat16_in_the_engines_place_fails_the_tolerance():
    m16 = tiny("bfloat16")
    prompt = _prompts([70], seed=1)[0]
    got, toks = _steps_logits(m16, prompt, 3)
    ref = _ref(m16, toks, 4)
    assert np.abs(got - ref).max() / ref.std() > 3 * F32_TOL


def test_state_lost_between_chunks_fails_the_tolerance(model):
    """A sequence past 512 positions: the reference with its state zeroed
    at position 512 (``reset``) is not what the program computes."""
    prompt = _prompts([530], seed=2)[0]
    got, toks = _steps_logits(model, prompt, 2, chunk=64)
    ref = _ref(model, toks, 3)
    assert np.abs(got - ref).max() / ref.std() < F32_TOL
    alt = _ref(model, toks, 3, mode="f32:reset")
    assert np.abs(got - alt).max() / ref.std() > 10 * F32_TOL


# -- the engine ------------------------------------------------------------------

def test_engine_serves_the_references_best(model):
    """``submit()`` / ``step()``: chunked prefill and continuous batching
    of three prompts of different lengths; every served token is the
    reference's best to within ``F32_TOL``, and nothing stays held."""
    eng = _engine(model)
    prompts = _prompts([70, 9, 33], seed=3)
    stats = eng.run([Request(p, max_new_tokens=6) for p in prompts],
                    deterministic=True)
    assert stats["requests"] == 3 and stats["failed"] == 0
    # the second and third prompts' chunks carried the running rows
    assert eng.work_totals["prefill_chunks_with_decode_total"] >= 2
    for seq in eng.finished:
        assert _gap(model, seq.req.prompt, seq.generated) < F32_TOL
    assert eng.pool.used_blocks == 0 and eng.slots.used_slots == 0
    assert stats["state_slots_in_use"] == 0
    assert stats["state_bytes"] == sum(a.nbytes for a in eng.state)
    c = model[1]
    totals = eng.work_totals
    assert totals["ssm_scan_tokens_total"] \
        == c.num_hidden_layers * totals["prefill_tokens_total"]
    assert totals["ssm_state_rows_total"] == c.num_hidden_layers * (
        totals["decode_rows_total"] + totals["prefill_chunks_total"])
    assert "paddle_tpu_serve_state_slots_in_use 0" in eng.render_prometheus()


def test_evicted_request_is_recomputed_from_zeros(model):
    """A running sequence evicted mid-decode loses blocks and slot; its
    readmission re-prefills from position 0 into whatever slot is free
    (stale bytes and all) and serves the stream it would have served."""
    prompts = _prompts([40, 21], seed=4)
    reqs = lambda: [Request(p, max_new_tokens=8) for p in prompts]
    calm = _engine(model)
    calm.run(reqs(), deterministic=True)
    want = {s.req.request_id: s.generated for s in calm.finished}
    eng = _engine(model)
    for r in reqs():
        eng.submit(r)
    while not all(len(s.generated) >= 3 for s in eng.active) \
            or len(eng.active) < 2:
        eng.step()
    held = {s.req.request_id: s.slot for s in eng.active}
    assert sorted(held.values()) == [1, 2]
    assert eng._evict_one()
    victim = eng.waiting[0]
    assert victim.slot is None and victim.n_cached == 0
    assert eng.slots.used_slots == 1
    eng.run([], deterministic=True)
    assert eng.preemptions == 1
    got = {s.req.request_id: s.generated for s in eng.finished}
    assert got == want
    assert eng.pool.used_blocks == 0 and eng.slots.used_slots == 0


def test_two_requests_that_swap_slots(model):
    """The same two requests again and again on ONE engine: each comes to
    sit in the slot the other left (its stale state in it) and serves the
    same stream every time."""
    a, b = (tuple(p) for p in _prompts([37, 50], seed=5))
    eng = _engine(model)
    slots, streams = {a: set(), b: set()}, {a: set(), b: set()}
    for first, second in ((a, b), (a, b), (b, a)):
        for p in (first, second):
            eng.submit(Request(list(p), max_new_tokens=5))
        while len(eng.active) < 2 or any(s.slot is None for s in eng.active):
            eng.step()
        for s in eng.active:
            slots[tuple(s.req.prompt)].add(s.slot)
        eng.run([], deterministic=True)
        for s in eng.finished[-2:]:
            streams[tuple(s.req.prompt)].add(tuple(s.generated))
    assert slots[a] == slots[b] == {1, 2}
    assert len(streams[a]) == len(streams[b]) == 1


def test_the_models_own_seeded_weights_serve():
    """``init_falcon_h1_params`` (the tiny preset, the Mamba-2 starts) gives
    finite streams through the same engine."""
    c = H.falcon_h1_tiny()
    eng = InferenceEngine(H.init_falcon_h1_params(c, seed=1), c, ServeConfig(
        block_size=128, num_blocks=6, max_batch=2, prefill_chunk=32,
        max_seq_len=256))
    stats = eng.run([Request([t % c.vocab_size for t in p], max_new_tokens=4)
                     for p in _prompts([35, 7], seed=11)], deterministic=True)
    assert stats["requests"] == 2 and stats["failed"] == 0


def test_slots_follow_admission_order(model):
    eng = _engine(model)
    a, b = _prompts([12, 14], seed=6)
    eng.submit(Request(a, max_new_tokens=4))
    eng.submit(Request(b, max_new_tokens=4))
    eng.step()
    eng.step()
    assert [s.slot for s in eng.active] == [1, 2]


@pytest.mark.parametrize("kw,what", [
    (dict(mp=2), "mp > 1"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(speculative=True), "speculative"),
    (dict(prefix_cache=True), "prefix_cache"),
])
def test_refuses_what_is_out_of_scope(model, kw, what):
    _, c, _, ws = model
    with pytest.raises(NotImplementedError, match=what):
        InferenceEngine(ws, c, ServeConfig(block_size=128, num_blocks=8,
                                           max_batch=2, **kw))


def test_refuses_a_draft_model(model):
    _, c, _, ws = model
    with pytest.raises(NotImplementedError, match="draft"):
        InferenceEngine(ws, c, ServeConfig(block_size=128, num_blocks=8,
                                           max_batch=2),
                        draft_params=ws, draft_config=c)


def test_other_models_take_the_new_keyword():
    from paddle_tpu.models.deepseek import DeepSeekServing
    from paddle_tpu.models.llama import LlamaServing
    for serving in (LlamaServing, DeepSeekServing):
        serving.refuse(mp=1, kv_dtype="auto", speculative=False, draft=False,
                       prefix_cache=True)


# -- faults: a step that is not idempotent ---------------------------------------

def _survivors_alone(model, prompts, n_new):
    eng = _engine(model)
    eng.run([Request(p, max_new_tokens=n_new) for p in prompts],
            deterministic=True)
    return [s.generated for s in sorted(eng.finished,
                                        key=lambda s: s.req.request_id)]


def _launches(eng):
    """Count an engine's decode batches and the decode program's launches."""
    calls = {"decode": 0, "batches": 0}
    step_fn, batch = eng._step_fn, eng._decode_batch

    def counted_batch(*a, **kw):
        calls["batches"] += 1
        return batch(*a, **kw)
    eng._decode_batch = counted_batch

    def counting(kind, frozen, quant=None):
        fn = step_fn(kind, frozen, quant)
        if kind != "decode" or fn is None:
            return fn

        def run(*a):
            calls["decode"] += 1
            return fn(*a)
        return run
    eng._step_fn = counting
    return calls


@pytest.mark.parametrize("nth", [3, 4], ids=["rode_a_chunk", "decode_batch"])
def test_poison_after_the_program_ran_commits_the_survivors(model, nth):
    """``serve.decode.logits`` raises once the program has RUN (the third
    time beside the chunk that carried two rows, the fourth in a decode
    batch of three): the survivors' states have advanced, so their tokens
    from that run are committed and no program is launched again for them
    (a second launch would advance them twice). Their streams are those of
    a run that never held the poisoned request."""
    bad, ok1, ok2 = _prompts([20, 26, 11], seed=7)

    def boom(ctx):
        raise PoisonError(ctx["rids"][0], "injected after the run")

    eng = _engine(model)
    calls = _launches(eng)
    with faults.scope("serve.decode.logits", "corrupt", nth=nth,
                      corrupt=boom):
        stats = eng.run([Request(p, max_new_tokens=6)
                         for p in (bad, ok1, ok2)], deterministic=True)
    assert eng.work_totals["prefill_chunks_with_decode_total"] == 2
    assert stats["failed"] == 1 and stats["requests"] == 2
    assert eng.failed[0].fail_cause == "injected after the run"
    assert stats["decode_redrives"] == 0
    # one launch a decode batch, the poisoned one included
    assert calls["decode"] == calls["batches"]
    got = [s.generated for s in sorted(eng.finished,
                                       key=lambda s: s.req.request_id)]
    assert got == _survivors_alone(model, [ok1, ok2], 6)
    assert eng.pool.used_blocks == 0 and eng.slots.used_slots == 0


def test_poison_before_the_launch_redrives_as_ever(model):
    """``serve.decode.poison`` raises BEFORE the launch of a decode batch
    (its third firing: the second is beside a chunk that carried a row,
    after that program ran): nothing has moved, the survivors are re-driven
    in the same iteration."""
    bad, ok = _prompts([24, 24], seed=8)

    def boom(ctx):
        raise PoisonError(ctx["rids"][0], "injected decode poison")

    eng = _engine(model)
    calls = _launches(eng)
    with faults.scope("serve.decode.poison", "corrupt", nth=3, corrupt=boom):
        stats = eng.run([Request(bad, max_new_tokens=6),
                         Request(ok, max_new_tokens=6)], deterministic=True)
    assert stats["failed"] == 1 and stats["requests"] == 1
    assert stats["decode_redrives"] == 1
    assert calls["decode"] == calls["batches"]      # the poisoned batch's
    #                       launch never happened; its re-drive's did
    assert eng.finished[0].generated == _survivors_alone(model, [ok], 6)[0]
    assert eng.slots.used_slots == 0


def test_prefill_poison_quarantines_one_and_frees_its_slot(model):
    bad, ok = _prompts([24, 24], seed=9)
    eng = _engine(model)
    with faults.scope("serve.prefill.poison", "raise", nth=1):
        stats = eng.run([Request(bad, max_new_tokens=5),
                         Request(ok, max_new_tokens=5)], deterministic=True)
    assert stats["failed"] == 1 and stats["requests"] == 1
    assert eng.finished[0].generated == _survivors_alone(model, [ok], 5)[0]
    assert eng.pool.used_blocks == 0 and eng.slots.used_slots == 0


def test_a_genuinely_non_finite_row_is_dropped_and_the_rest_stand(model):
    """Token 255's embedding is NaN: the row that feeds it is quarantined
    by the finite flags of the run that advanced everyone, and the others'
    tokens from that run stand."""
    m, c, w, ws = model
    poisoned = dict(ws, embed=ws["embed"].at[255].set(jnp.nan))
    bad, ok = _prompts([18, 22], seed=10)
    eng = InferenceEngine(poisoned, c, ServeConfig(
        block_size=128, num_blocks=12, max_batch=4, prefill_chunk=32,
        max_seq_len=512))
    eng.submit(Request(bad, max_new_tokens=8))
    eng.submit(Request(ok, max_new_tokens=8))
    while len(eng.active) < 2 or not all(s.generated for s in eng.active):
        eng.step()
    next(s for s in eng.active if s.req.request_id == 0).tokens[-1] = 255
    stats = eng.run([], deterministic=True)
    assert stats["failed"] == 1
    assert eng.failed[0].fail_cause == "non-finite decode logits"
    assert eng.finished[0].generated == _survivors_alone(model, [ok], 8)[0]
    assert eng.slots.used_slots == 0


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
