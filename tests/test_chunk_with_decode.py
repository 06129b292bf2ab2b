"""The decode batch rides the prefill chunk (``prefill+decode``).

An iteration with a sequence in prefill and sequences running is one device
program where the model's serving object offers it:
``llama_paged_prefill_chunk_with_decode`` runs one layer scan over the
chunk's rows and the batch's, and ``InferenceEngine.step()`` launches and
waits once. Pinned here on tiny widths with Pallas in interpret mode: the
program against the two it replaces, the engine's streams against an engine
whose serving object offers no such program, the NaN screen per part, the
counters, and the paths this leaves alone.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import (InferenceEngine, PoisonError, Request,
                                  ServeConfig)
from paddle_tpu.inference import engine as engine_mod
from paddle_tpu.models import llama as L
from paddle_tpu.ops import _common
from paddle_tpu.testing import faults

BS, NB, MAX_NB, C, R = 8, 24, 6, 16, 4


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULTS", "1")
    with _common.interpret_mode(True):
        yield
    faults.disarm()


@pytest.fixture(scope="module")
def model():
    cfg = L.llama_tiny(vocab=96, hidden=64, layers=2, heads=4, kv_heads=2,
                       seq=512)
    return cfg, L.init_llama_params(cfg, seed=3)


# -- the program against the two it replaces ----------------------------------

def _pools(cfg):
    """Pools with something in every block, so that a read of a block the
    step should not touch shows."""
    kp, vp = L.init_paged_kv_pool(cfg, NB, BS)
    return (jax.random.normal(jax.random.PRNGKey(1), kp.shape, kp.dtype),
            jax.random.normal(jax.random.PRNGKey(2), vp.shape, vp.dtype))


# rows as (blocks, position): a row alone; a full batch with one row whose new
# token opens its second block (position == block size); a batch half padding
BATCHES = {
    "one_row": [([1, 2, 3], 17)],
    "full_with_boundary": [([1, 2, 3], 17), ([4, 5], 8), ([6], 3),
                           ([11, 12, 13], 23)],
    "half_padding": [([4, 5], 8), ([6], 0)],
}


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("start, n_live", [(0, 16), (8, 11), (13, 1)])
def test_program_equals_chunk_then_decode(model, start, n_live, batch):
    """Chunk logits, row logits and both pools of the one step against
    ``llama_paged_prefill_chunk`` followed by ``llama_paged_decode_step`` on
    the same inputs. The pools and the rows' logits come out the same to
    the bit here; the chunk's logits to float32 rounding (its head row is
    one of R + 1 rows of a matmul, not a row alone). The jitted program
    returns the greedy head of those logits and the same pools."""
    cfg, params = model
    rows = BATCHES[batch]
    tables = np.zeros((R, MAX_NB), np.int32)
    positions = np.zeros((R,), np.int32)
    ids_r = np.zeros((R,), np.int32)
    for i, (blocks, pos) in enumerate(rows):
        tables[i, :len(blocks)] = blocks
        positions[i], ids_r[i] = pos, 5 + 7 * i
    table_row = np.zeros((MAX_NB,), np.int32)
    table_row[:4] = [7, 8, 9, 10]
    ids_c = np.random.default_rng(0).integers(
        0, cfg.vocab_size, C).astype(np.int32)
    chunk_in = (jnp.asarray(table_row), np.int32(start), jnp.asarray(ids_c),
                np.int32(n_live))
    rows_in = (jnp.asarray(tables), jnp.asarray(positions),
               jnp.asarray(ids_r))

    def step(kind):
        """The step function itself, which keeps its logits."""
        fn = L._PAGED_STEPS[kind][0]
        return jax.jit(lambda p, k, v, *a: fn(p, (k, v), *a, cfg))

    want_c, kp, vp = step("prefill")(params, *_pools(cfg), *chunk_in)
    want_r, kp, vp = step("decode")(params, kp, vp, *rows_in)
    got_c, got_r, got_k, got_v = step("prefill+decode")(
        params, *_pools(cfg), *chunk_in, *rows_in)
    assert got_c.shape == (cfg.vocab_size,) and got_c.dtype == jnp.float32
    assert got_r.shape == (R, cfg.vocab_size) and got_r.dtype == jnp.float32
    np.testing.assert_allclose(got_c, want_c, rtol=1e-5, atol=1e-5)
    n = len(rows)
    np.testing.assert_allclose(got_r[:n], want_r[:n], rtol=1e-5, atol=1e-5)
    # every block but the null one, which padding rows and dead chunk slots
    # scribble on in either order
    np.testing.assert_array_equal(got_k[:, 1:], kp[:, 1:])
    np.testing.assert_array_equal(got_v[:, 1:], vp[:, 1:])
    # the program the engine calls: tokens and flags, never logits
    tok_c, fin_c, tok_r, fin_r, k2, v2 = L._jitted_paged_step(
        "prefill+decode", L._freeze_config(cfg), False, None)(
        params, *_pools(cfg), *chunk_in, *rows_in)
    assert tok_c.shape == () and tok_c.dtype == jnp.int32
    assert tok_r.shape == (R,) and fin_r.dtype == jnp.bool_
    assert int(tok_c) == int(np.argmax(got_c)) and bool(fin_c)
    np.testing.assert_array_equal(tok_r, np.argmax(got_r, axis=-1))
    assert bool(np.all(fin_r))
    np.testing.assert_array_equal(k2, got_k)
    np.testing.assert_array_equal(v2, got_v)


# -- the engine: one launch for an iteration that has both --------------------

class _TwoPrograms(L.LlamaServing):
    """Llama's serving object, offering no chunk that carries the batch."""

    @staticmethod
    def step_fn(kind, frozen, quant, mesh):
        if kind == "prefill+decode":
            return None
        return L.LlamaServing.step_fn(kind, frozen, quant, mesh)


def _engine(model, two_programs=False, **kw):
    cfg, params = model
    serve = ServeConfig(**dict(dict(
        block_size=128, num_blocks=12, max_batch=4, prefill_chunk=32,
        max_seq_len=384), **kw))
    if two_programs:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(engine_mod, "_serving_for", lambda config: _TwoPrograms)
            return InferenceEngine(params, cfg, serve, record_events=True)
    return InferenceEngine(params, cfg, serve, record_events=True)


def _mixed(hi=95):
    """Prompts of one chunk, of three, of exactly two (its last chunk is
    full) and short ones that arrive while others decode."""
    rng = np.random.RandomState(7)
    return [Request(rng.randint(1, hi, size=n).tolist(), max_new_tokens=new,
                    arrival=float(at))
            for n, new, at in ((7, 9, 0), (90, 6, 0), (64, 5, 1), (20, 12, 2),
                               (33, 1, 3), (5, 7, 9))]


def _streams(eng):
    return {s.req.request_id: s.generated for s in eng.finished}


@pytest.fixture(scope="module")
def both(model):
    """The mixed requests through an engine that carries the batch on the
    chunk and through one whose serving object offers no such program."""
    out = {}
    with _common.interpret_mode(True):
        for name in ("carried", "two_programs"):
            eng = _engine(model, two_programs=name == "two_programs")
            out[name] = (eng, eng.run(_mixed(), deterministic=True))
    return out


def test_streams_are_those_of_the_two_program_path(both):
    (one, s1), (two, s2) = both["carried"], both["two_programs"]
    assert s1["requests"] == s2["requests"] == 6
    assert _streams(one) == _streams(two)
    assert all(len(t) == r.max_new_tokens
               for t, r in zip(map(_streams(one).get, range(6)), _mixed()))
    assert one.pool.used_blocks == two.pool.used_blocks == 0


def test_one_program_where_there_is_work_of_both_kinds(both):
    one, two = both["carried"][0], both["two_programs"][0]
    assert ("prefill+decode", 32, 4) in one._compiled
    # the chunk alone and the decode buckets stay and still run
    assert ("prefill", 32) in one._compiled
    assert ("decode", 1) in one._compiled
    assert all(k[0] != "prefill+decode" for k in two._compiled)
    assert one._chunk_carries and not two._chunk_carries


def test_the_two_counters_add_up(both):
    one, two = both["carried"][0], both["two_programs"][0]
    w1, w2 = one.work_totals, two.work_totals
    assert w1["prefill_chunks_total"] == w2["prefill_chunks_total"] \
        == 1 + 3 + 2 + 1 + 2 + 1
    assert 0 < w1["prefill_chunks_with_decode_total"] \
        <= w1["prefill_chunks_total"]
    assert w2["prefill_chunks_with_decode_total"] == 0
    # the first chunk of all has nothing running beside it
    assert w1["prefill_chunks_with_decode_total"] \
        < w1["prefill_chunks_total"]
    # the carried rows count as decoded rows do: as many tokens either way
    assert w1["decode_rows_total"] == w2["decode_rows_total"]
    snap = one.metrics_snapshot()
    for name in ("prefill_chunks_total", "prefill_chunks_with_decode_total"):
        assert snap[name] == w1[name]
        assert f"paddle_tpu_serve_{name} " in one.render_prometheus()


def test_a_prompt_that_finishes_on_a_shared_iteration(model):
    """Its first token comes from the shared program; it joins the decode
    batch the iteration after. With two programs it decodes once more in
    the iteration of its last chunk. The streams agree."""
    p_run, p_new = _mixed()[0].prompt, _mixed()[3].prompt
    got = {}
    for two in (False, True):
        eng = _engine(model, two_programs=two)
        eng.submit(Request(p_run, max_new_tokens=8))
        eng.step()                  # a chunk alone, then its first decode
        assert len(eng.active[0].generated) == 2
        eng.submit(Request(p_new, max_new_tokens=4))
        eng.step()                  # the new prompt's only chunk + one row
        new = next(s for s in eng.active if s.req.request_id == 1)
        assert new.state == engine_mod.RUNNING
        assert len(new.generated) == (2 if two else 1)
        assert len(eng.active[0].generated) == 3
        eng.run([], deterministic=True)
        got[two] = _streams(eng)
    assert got[False] == got[True]
    assert [len(got[False][i]) for i in (0, 1)] == [8, 4]


@pytest.fixture(scope="module")
def nan_model(model):
    """Token 95's embedding row is NaN: a prompt or a history that holds it
    reads non-finite logits."""
    cfg, params = model
    return cfg, dict(params, embed=params["embed"].at[95].set(jnp.nan))


def _two_requests(nan_model, bad_prompt: bool):
    """One request decoding, then a second one's chunk beside it; either the
    chunk's prompt or the running row's history holds the NaN token. Returns
    (engine, stats, the healthy one's reference stream)."""
    rng = np.random.RandomState(11)
    p_run, p_new = (rng.randint(1, 95, size=n).tolist() for n in (24, 40))
    healthy = p_run if bad_prompt else p_new
    solo = _engine(nan_model)
    solo.run([Request(healthy, max_new_tokens=6)], deterministic=True)
    eng = _engine(nan_model)
    eng.submit(Request(p_run, max_new_tokens=6))
    eng.step()
    if bad_prompt:
        p_new[10] = 95
    else:
        eng.active[0].tokens[-1] = 95
    eng.submit(Request(p_new, max_new_tokens=6))
    carried = eng.work_totals["prefill_chunks_with_decode_total"]
    eng.step()
    assert eng.work_totals["prefill_chunks_with_decode_total"] == carried + 1
    return eng, eng.run([], deterministic=True), solo.finished[0].generated


def test_quarantined_chunk_beside_healthy_rows(nan_model):
    eng, stats, ref = _two_requests(nan_model, bad_prompt=True)
    assert stats["failed"] == 1 and stats["requests"] == 1
    assert eng.failed[0].req.request_id == 1
    assert eng.failed[0].fail_cause == "non-finite prefill logits"
    assert eng.finished[0].generated == ref
    assert eng.pool.used_blocks == 0


def test_quarantined_row_beside_a_healthy_chunk(nan_model):
    eng, stats, ref = _two_requests(nan_model, bad_prompt=False)
    assert stats["failed"] == 1 and stats["requests"] == 1
    assert eng.failed[0].req.request_id == 0
    assert eng.failed[0].fail_cause == "non-finite decode logits"
    assert eng.finished[0].generated == ref
    assert eng.pool.used_blocks == 0


def _two_running_and_a_new_prompt(model):
    """(engine with requests 0 and 1 running and request 2 submitted, the
    streams 0 and 1 make with no fault)."""
    p0, p2, p1 = (r.prompt for r in _mixed()[:3])
    ref = _engine(model)
    ref.run([Request(p, max_new_tokens=6) for p in (p0, p1)],
            deterministic=True)
    eng = _engine(model)
    for p in (p0, p1):
        eng.submit(Request(p, max_new_tokens=6))
    while sum(s.state == engine_mod.RUNNING for s in eng.active) < 2:
        eng.step()
    eng.submit(Request(p2, max_new_tokens=6))
    return eng, [s.generated for s in ref.finished]


def _sites_fired(monkeypatch):
    """The fault sites the engine passes from here on, in order."""
    sites, inject = [], faults.inject
    monkeypatch.setattr(
        engine_mod.faults, "inject",
        lambda site, **ctx: (sites.append(site), inject(site, **ctx))[1])
    return sites


def test_poisoned_row_is_redriven_through_the_decode_program(
        model, monkeypatch):
    """A ``PoisonError`` for a carried row: that row is quarantined, the
    chunk's commit stands, and the other rows go through the decode program
    in the same iteration (their tokens are what they would have been).
    They count once, as rows of the decode program; the chunk does not count
    as having carried the batch; ``serve.decode.before`` fires once."""
    def boom(ctx):
        raise PoisonError(ctx["rids"][-1], "injected decode poison")

    eng, want = _two_running_and_a_new_prompt(model)
    before = dict(eng.work_totals)
    sites = _sites_fired(monkeypatch)
    with faults.scope("serve.decode.poison", "corrupt", nth=1, corrupt=boom):
        eng.step()
    assert eng.failed[0].req.request_id == 1
    assert eng.failed[0].fail_cause == "injected decode poison"
    assert eng._redrives == 1
    # the chunk's program ran with the batch, then the decode program
    assert eng._iter_work["rows"] == 1 and eng._iter_work["bucket"] == 1
    assert {"serve.prefill", "serve.decode"} <= set(eng._phase_ms)
    moved = {k: v - before[k] for k, v in eng.work_totals.items()
             if not k.startswith("prefill_") or "chunks" in k}
    # fetched: a token (4 B) and a flag (1 B) for the chunk and each of the
    # four row slots, then for the one row re-driven
    assert moved == {
        "prefill_chunks_total": 1, "prefill_chunks_with_decode_total": 0,
        "decode_rows_total": 1, "decode_slots_total": 1,
        "step_fetch_bytes_total": 5 * (1 + 4) + 5}
    assert [s for s in sites if s.startswith("serve.decode.")] == [
        "serve.decode.before", "serve.decode.poison", "serve.decode.poison",
        "serve.decode.logits", "serve.decode.after"]
    stats = eng.run([], deterministic=True)
    assert stats["failed"] == 1 and stats["requests"] == 2
    assert eng.finished[0].generated == want[0]
    assert eng.pool.used_blocks == 0


def test_rows_of_a_program_that_failed_go_through_the_decode_program(
        model, monkeypatch):
    """The chunk's program never returned (a fault at its launch): the
    chunk's request is quarantined, and the rows planned beside it are
    decoded by the decode program in the same iteration, behind its hooks,
    counted once; the chunk does not count as having carried the batch."""
    eng, want = _two_running_and_a_new_prompt(model)
    before = dict(eng.work_totals)
    sites = _sites_fired(monkeypatch)
    with faults.scope("serve.prefill.poison", "raise", nth=1):
        eng.step()
    assert [s.req.request_id for s in eng.failed] == [2]
    assert eng._redrives == 0
    assert eng.work_totals["prefill_chunks_with_decode_total"] \
        == before["prefill_chunks_with_decode_total"]
    assert eng.work_totals["prefill_chunks_total"] \
        == before["prefill_chunks_total"] + 1
    assert eng.work_totals["decode_rows_total"] \
        == before["decode_rows_total"] + 2
    assert eng.work_totals["decode_slots_total"] \
        == before["decode_slots_total"] + 2
    assert [s for s in sites if s.startswith("serve.decode.")] == [
        "serve.decode.before", "serve.decode.poison", "serve.decode.logits",
        "serve.decode.after"]
    stats = eng.run([], deterministic=True)
    assert stats["failed"] == 1 and stats["requests"] == 2
    assert [s.generated for s in eng.finished] == want
    assert eng.pool.used_blocks == 0


@pytest.mark.parametrize("kw", [
    pytest.param({"kv_dtype": "int8"}, id="int8"),
    pytest.param({"speculative": True, "draft_k": 2}, id="speculative"),
    pytest.param({"mp": 2}, id="mp2"),
])
def test_paths_left_alone_keep_two_programs(model, kw):
    """An int8 cache, speculation and tensor parallelism are offered no
    such program, by one line of ``LlamaServing.step_fn``: no knob."""
    cfg, params = model
    if kw.get("mp", 1) > len(jax.devices()):
        pytest.skip("needs two devices")
    eng = _engine(model, **kw)
    assert not eng._chunk_carries
    stats = eng.run(_mixed()[:4], deterministic=True)
    assert stats["requests"] == 4
    assert eng.work_totals["prefill_chunks_with_decode_total"] == 0
    assert eng.work_totals["prefill_chunks_total"] == 1 + 3 + 2 + 1
    assert all(k[0] != "prefill+decode" for k in eng._compiled)



# -- the seam: one builder, and what the serving object offers of it ----------

STEMS = {"decode": "paged_decode_step", "prefill": "paged_prefill_chunk",
         "prefill+decode": "paged_prefill_chunk_with_decode",
         "verify": "paged_verify_step"}
ALIASES = {"decode": "_jitted_paged_decode", "prefill": "_jitted_paged_prefill"}


@pytest.mark.parametrize("mp", [1, 2])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("kind", list(STEMS))
def test_every_program_is_the_one_builders(model, kind, quant, mp):
    """``LlamaServing.step_fn`` hands out the builder's cached program under
    the jitted name the benchmark's per-layer metrics match, and None for the
    three chunks that carry the batch and have no cell yet (ROADMAP S10)."""
    frozen = L._freeze_config(model[0])
    mesh = None if mp == 1 else L.make_mesh(L.ParallelConfig(mp=mp))
    built = L._jitted_paged_step(kind, frozen, quant, mesh)
    assert built.__name__ == (STEMS[kind] + "_int8" * quant
                              + "_tp" * (mesh is not None))
    offered = L.LlamaServing.step_fn(kind, frozen, quant, mesh)
    if kind == "prefill+decode" and (quant or mesh is not None):
        assert offered is None
    else:
        assert offered is built
    if kind in ALIASES and not quant and mesh is None:
        # chipbench/families/llama.py aot_programs reads these two names
        assert getattr(L, ALIASES[kind])(frozen) is built
