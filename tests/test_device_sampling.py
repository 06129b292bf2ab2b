"""The step programs sample on the device (``ops/sampling.py``).

Every jitted paged program ends in the greedy head: a token and a finite
flag a row come back where the float32 logits of the whole vocabulary did,
and the engine reads nothing else of a step's head. Pinned here on tiny
widths with Pallas in interpret mode: the head against numpy (a tie, a NaN,
an infinity); each program of each model and each of Llama's builds (plain,
int8 cache, the ``mp`` island and both) against the un-jitted step's logits
on the same inputs, the cache to the bit; that no program of any build
returns an array as wide as the vocabulary; and an engine's window of chunks
and rows, whose streams are those of ``greedy_generate`` and whose waits
fetched a few bytes a token.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from chipbench import spec
from chipbench.families import deepseek as ds_fam
from chipbench.families import glm_dsa as glm_fam
from paddle_tpu.inference import (InferenceEngine, PoisonError, Request,
                                  ServeConfig)
from paddle_tpu.models import deepseek as D
from paddle_tpu.models import llama as L
from paddle_tpu.ops import _common
from paddle_tpu.ops.sampling import greedy_head, sampled
from paddle_tpu.testing import faults

BS, NB, MAX_NB, C, R = 8, 24, 6, 16, 4
KINDS = ("decode", "prefill", "prefill+decode")


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULTS", "1")
    with _common.interpret_mode(True):
        yield
    faults.disarm()


# -- the head itself -----------------------------------------------------------

def _logits(seed=0, shape=(5, 96)):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


def _tied(x):
    x[2, 70] = x[2, 11] = x[2].max() + 1.0      # the first index wins
    return x


def _with_nan(x):
    x[3, 40] = np.nan                           # counts as the maximum
    return x


def _with_inf(x):
    x[1, 17] = np.inf
    return x


def _with_neg_inf(x):
    x[4, 0] = -np.inf                           # not finite, not the maximum
    return x


@pytest.mark.parametrize("alter, bad_rows", [
    (lambda x: x, []), (_tied, []), (_with_nan, [3]), (_with_inf, [1]),
    (_with_neg_inf, [4]), (lambda x: _with_nan(_with_inf(_tied(x))), [1, 3])],
    ids=["plain", "tie", "nan", "inf", "neg_inf", "all"])
def test_greedy_head_is_numpys_argmax_and_a_flag_a_row(alter, bad_rows):
    x = alter(_logits())
    token, finite = jax.jit(greedy_head)(jnp.asarray(x))
    assert token.dtype == jnp.int32 and finite.dtype == jnp.bool_
    np.testing.assert_array_equal(token, np.argmax(x, axis=-1))
    np.testing.assert_array_equal(finite, np.isfinite(x).all(axis=-1))
    assert [i for i in range(5) if not finite[i]] == bad_rows
    # one row alone, as a chunk's head: scalars
    t1, f1 = jax.jit(greedy_head)(jnp.asarray(x[3]))
    assert t1.shape == f1.shape == ()
    assert int(t1) == int(np.argmax(x[3])) and bool(f1) == (3 not in bad_rows)
    # rows of positions, as verify's: one flag a position
    t3, f3 = greedy_head(jnp.asarray(x.reshape(5, 2, 48)))
    np.testing.assert_array_equal(t3, np.argmax(x.reshape(5, 2, 48), -1))
    assert f3.shape == (5, 2)


def test_sampled_puts_the_head_on_the_leading_logits_alone():
    a, b, pool = _with_nan(_logits(1))[3], _tied(_logits(2)), jnp.ones((2, 3))
    tok_a, fin_a, tok_b, fin_b, rest = sampled(
        (jnp.asarray(a), jnp.asarray(b), pool), 2)
    assert int(tok_a) == 40 and not bool(fin_a)
    assert int(tok_b[2]) == 11 and bool(fin_b.all())
    assert rest is pool
    assert sampled((pool,), 0) == (pool,)


# -- Llama's programs, every build --------------------------------------------

@pytest.fixture(scope="module")
def llama():
    cfg = L.llama_tiny(vocab=96, hidden=64, layers=2, heads=4, kv_heads=2,
                       seq=512)
    return cfg, L.init_llama_params(cfg, seed=3)


def _llama_pools(cfg, quant):
    """Pools with something in every block (int8: bytes and scales)."""
    kp, vp = L.init_paged_kv_pool(cfg, NB, BS,
                                  kv_dtype="int8" if quant else "auto")
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    if not quant:
        return tuple(jax.random.normal(k, p.shape, p.dtype)
                     for k, p in zip(keys, (kp, vp)))
    ks, vs = L.init_paged_kv_scales(cfg, NB, BS)
    return (*(jax.random.randint(k, p.shape, -127, 128, jnp.int8)
              for k, p in zip(keys, (kp, vp))),
            *(jax.random.uniform(k, s.shape, s.dtype, 0.002, 0.02)
              for k, s in zip(keys[2:], (ks, vs))))


def _inputs(vocab, kind, bad_id=None):
    """The chunk's inputs, the batch's, or both: rows at different depths,
    one slot padding; ``bad_id`` in the chunk and in row 1's new token."""
    tables = np.zeros((R, MAX_NB), np.int32)
    positions = np.zeros((R,), np.int32)
    ids_r = np.zeros((R,), np.int32)
    for i, (blocks, pos) in enumerate(
            [([1, 2, 3], 17), ([4, 5], 8), ([6], 3)]):
        tables[i, :len(blocks)] = blocks
        positions[i], ids_r[i] = pos, 5 + 7 * i
    table_row = np.zeros((MAX_NB,), np.int32)
    table_row[:4] = [7, 8, 9, 10]
    ids_c = np.random.default_rng(0).integers(
        0, vocab - 1, C).astype(np.int32)
    if bad_id is not None:
        ids_c[4], ids_r[1] = bad_id, bad_id
    chunk = (jnp.asarray(table_row), np.int32(8), jnp.asarray(ids_c),
             np.int32(11))
    rows = (jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(ids_r))
    return {"decode": rows, "prefill": chunk,
            "prefill+decode": chunk + rows}[kind]


# the exact concat of an island's vocab-sharded logits, as the builder's
# out-specs stated it while the programs still returned them
ISLAND_LOGITS = {"decode": (P(None, "mp"),), "prefill": (P("mp"),),
                 "prefill+decode": (P("mp"), P(None, "mp"))}


def _llama_step(kind, cfg, quant, mesh):
    """The un-jitted step function, which keeps its logits, under ``jit``
    (inside the island's ``shard_map`` under a mesh): (*logits, *pools)."""
    step, _, n_inputs, _ = L._PAGED_STEPS[kind]
    n_pools = 4 if quant else 2
    if mesh is None:
        return jax.jit(lambda p, *a: step(p, a[:n_pools], *a[n_pools:], cfg))
    pspecs, tp = L._tp_specs(cfg, mesh)
    pool_specs = (L._TP_POOL_SPEC,) * n_pools
    return jax.jit(shard_map(
        lambda p, *a: step(p, a[:n_pools], *a[n_pools:], cfg, tp),
        mesh=mesh, in_specs=(pspecs, *pool_specs, *(P(),) * n_inputs),
        out_specs=(*ISLAND_LOGITS[kind], *pool_specs), check_vma=False))


def _check_llama(cfg, params, kind, quant, mp, bad_id=None):
    """The program's heads against numpy on the step's logits; the cache to
    the bit. Returns (the step's logits, the program's heads)."""
    mesh = None
    pools = [_llama_pools(cfg, quant) for _ in range(2)]
    if mp > 1:
        if mp > len(jax.devices()):
            pytest.skip(f"needs {mp} devices")
        placed = [L.LlamaServing.place(mp, params, cfg, p) for p in pools]
        mesh, params = placed[0][0], placed[0][1]
        pools = [p[2] for p in placed]
    args = _inputs(cfg.vocab_size, kind, bad_id)
    n_heads = len(kind.split("+"))
    want = _llama_step(kind, cfg, quant, mesh)(params, *pools[0], *args)
    got = L._jitted_paged_step(kind, L._freeze_config(cfg), quant, mesh)(
        params, *pools[1], *args)
    assert len(got) == len(want) + n_heads
    for i, logits in enumerate(want[:n_heads]):
        logits = np.asarray(logits)
        token, finite = got[2 * i], got[2 * i + 1]
        assert token.shape == finite.shape == logits.shape[:-1]
        assert token.dtype == jnp.int32 and finite.dtype == jnp.bool_
        np.testing.assert_array_equal(token, np.argmax(logits, axis=-1))
        np.testing.assert_array_equal(finite,
                                      np.isfinite(logits).all(axis=-1))
    for a, b in zip(got[2 * n_heads:], want[n_heads:], strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return want[:n_heads], got[:2 * n_heads]


@pytest.mark.parametrize("mp", [1, 2], ids=["one_chip", "mp2"])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("kind", KINDS)
def test_llama_program_returns_the_steps_greedy_head(llama, kind, quant, mp):
    cfg, params = llama
    logits, heads = _check_llama(cfg, params, kind, quant, mp)
    assert all(bool(np.all(f)) for f in heads[1::2])
    assert all(x.shape[-1] == cfg.vocab_size for x in logits)


@pytest.mark.parametrize("mp", [1, 2], ids=["one_chip", "mp2"])
@pytest.mark.parametrize("kind", KINDS)
def test_llama_program_breaks_a_tie_at_the_first_index(llama, kind, mp):
    """Two columns of the head made the same, one in each half of the
    vocabulary (under ``mp`` = 2, one on each rank): wherever that column is
    a row's best, the token is the lower index, as numpy's on the exact
    concat."""
    cfg, params = llama
    logits, _ = _check_llama(cfg, params, kind, False, mp)
    best = int(np.argmax(np.asarray(logits[0]).reshape(-1, cfg.vocab_size)[0]))
    twin = (best + cfg.vocab_size // 2) % cfg.vocab_size
    head = params["lm_head"]
    tied = dict(params, lm_head=head.at[:, twin].set(head[:, best]))
    logits, heads = _check_llama(cfg, tied, kind, False, mp)
    first = np.asarray(logits[0]).reshape(-1, cfg.vocab_size)[0]
    assert first[best] == first[twin] == first.max()
    assert int(np.asarray(heads[0]).reshape(-1)[0]) == min(best, twin)


@pytest.mark.parametrize("mp", [1, 2], ids=["one_chip", "mp2"])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("kind", KINDS)
def test_llama_program_flags_the_row_with_a_nan_alone(llama, kind, quant, mp):
    """Token 95's embedding row is NaN: the chunk that holds it and the one
    row that feeds it read non-finite logits, flag False; the other rows'
    flags stay True and their tokens are what they were."""
    cfg, params = llama
    clean = _check_llama(cfg, params, kind, quant, mp)[1]
    bad = dict(params, embed=params["embed"].at[95].set(jnp.nan))
    heads = _check_llama(cfg, bad, kind, quant, mp, bad_id=95)[1]
    if "prefill" in kind:
        assert not bool(heads[1])
    if "decode" in kind:
        fin, tok = np.asarray(heads[-1]), np.asarray(heads[-2])
        assert fin.tolist() == [True, False, True, True]
        keep = [0, 2, 3]
        np.testing.assert_array_equal(tok[keep],
                                      np.asarray(clean[-2])[keep])


@pytest.mark.parametrize("kind", KINDS)
def test_a_bf16_models_head_writes_its_float32_accumulator(llama, kind):
    """The paged steps ask the head's matmul for float32. Rounded to bf16
    the logits of 96 columns (32768 in a cell) tie at the top, and the
    greedy head would serve the first of the tied columns where the host's
    ``argmax`` over the logits the parent fetched served the best one (on
    the chip 20 of 28 streams parted before this was pinned). So: the
    step's logits are not bf16 values, and the program's tokens are their
    ``argmax``."""
    cfg, params = llama
    cfg = L.dataclasses.replace(cfg, dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    logits, _ = _check_llama(cfg, params, kind, False, 1)
    for x in map(np.asarray, logits):
        assert x.dtype == np.float32
        rounded = np.asarray(jnp.asarray(x).astype(jnp.bfloat16), np.float32)
        assert (rounded != x).mean() > 0.5


# -- DeepSeek-V3's and GLM-5.2's programs ---------------------------------------

def _tiny(fam, name, make):
    m = fam.rehearsal(json.load(open(os.path.join(
        spec.HERE, "configs", name))))
    m["vocab_size"] = 256
    c = make(m)
    return c, D.init_deepseek_params(c, seed=3, std=0.3)


MODELS = {
    "deepseek": lambda: _tiny(ds_fam, "deepseek-v3.json",
                              ds_fam.deepseek_config),
    "glm_dsa": lambda: _tiny(glm_fam, "glm-5.2.json", glm_fam.glm_config),
}


def _latent_pools(c):
    pools = D.DeepSeekServing.init_cache(c, NB, 16, "auto")
    keys = jax.random.split(jax.random.PRNGKey(1), len(pools))
    return tuple(jax.random.normal(k, p.shape, p.dtype)
                 for k, p in zip(keys, pools))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("model", list(MODELS))
def test_latent_program_returns_the_steps_greedy_head(model, kind):
    """``deepseek`` and ``glm_dsa`` at the families' rehearsal sizes: tokens
    and flags of the step's logits, the pools (GLM-5.2: the index keys' too)
    and the counts to the bit."""
    c, params = MODELS[model]()
    n = 2 if c.indexer_types else 1
    step = D._PAGED_STEPS[kind][0]
    args = _inputs(c.vocab_size, kind)
    want = jax.jit(lambda p, *a: step(p, a[:n], *a[n:], c))(
        params, *_latent_pools(c), *args)
    got = D.DeepSeekServing.step_fn(kind, c, False, None)(
        params, *_latent_pools(c), *args)
    n_heads = len(kind.split("+"))
    assert len(got) == len(want) + n_heads
    for i, logits in enumerate(want[:n_heads]):
        logits = np.asarray(logits)
        assert logits.shape[-1] == c.vocab_size
        np.testing.assert_array_equal(got[2 * i], np.argmax(logits, -1))
        np.testing.assert_array_equal(got[2 * i + 1],
                                      np.isfinite(logits).all(-1))
        assert got[2 * i].dtype == jnp.int32
        assert got[2 * i].shape == logits.shape[:-1]
    for a, b in zip(got[2 * n_heads:], want[n_heads:], strict=True):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# -- no program of any build hands back logits ----------------------------------

def _llama_builds():
    for kind in L._PAGED_STEPS:
        for quant in (False, True):
            for mp in (1, 2):
                yield pytest.param(kind, quant, mp,
                                   id=f"{kind}-{'int8' if quant else 'fp'}"
                                      f"-mp{mp}")


@pytest.mark.parametrize("kind, quant, mp", list(_llama_builds()))
def test_no_llama_program_returns_an_array_of_the_vocabulary(llama, kind,
                                                            quant, mp):
    """All thirteen programs and the three chunks that carry the batch and
    are offered to no engine yet: what precedes the cache is int32 or bool,
    a value a row (verify: a row and position)."""
    cfg, params = llama
    if mp > len(jax.devices()):
        pytest.skip(f"needs {mp} devices")
    mesh = None if mp == 1 else L.make_mesh(L.ParallelConfig(mp=mp))
    pools = _llama_pools(cfg, quant)
    args = _inputs(cfg.vocab_size, kind) if kind != "verify" else (
        jnp.zeros((R, MAX_NB), jnp.int32), jnp.zeros((R,), jnp.int32),
        jnp.ones((R,), jnp.int32), jnp.zeros((R, 3), jnp.int32))
    out = jax.eval_shape(
        L._jitted_paged_step(kind, L._freeze_config(cfg), quant, mesh),
        params, *pools, *args)
    heads = out[:-len(pools)]
    assert len(heads) == {"decode": 2, "prefill": 2, "prefill+decode": 4,
                          "verify": 3}[kind]
    for h in heads:
        assert h.dtype in (jnp.int32, jnp.bool_), h
        assert cfg.vocab_size not in h.shape and h.size <= R * 3


# -- the engine reads tokens ------------------------------------------------------

def _window():
    rng = np.random.RandomState(7)
    return [Request(rng.randint(1, 95, size=n).tolist(), max_new_tokens=new,
                    arrival=float(at))
            for n, new, at in ((7, 9, 0), (90, 6, 0), (64, 5, 1), (20, 12, 2),
                               (33, 1, 3), (5, 7, 9))]


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="plain"),
    pytest.param({"kv_dtype": "int8"}, id="int8"),
    pytest.param({"speculative": True, "draft_k": 2}, id="speculative"),
    pytest.param({"mp": 2}, id="mp2"),
])
def test_engine_fetches_a_few_bytes_a_token(llama, kw):
    """A window with chunks alone, chunks that carry the batch and decode
    steps: every wait's bytes are counted, under 64 a decoded token where a
    row of float32 logits is 384 here; the plain engine's streams are those
    of ``greedy_generate``; the counter is on the spans, in the registry and
    in the exposition."""
    cfg, params = llama
    if kw.get("mp", 1) > len(jax.devices()):
        pytest.skip("needs two devices")
    eng = InferenceEngine(params, cfg, ServeConfig(
        block_size=128, num_blocks=12, max_batch=4, prefill_chunk=32,
        max_seq_len=384, **kw), record_events=True)
    stats = eng.run(_window(), deterministic=True)
    assert stats["requests"] == 6 and stats["failed"] == 0
    w = eng.work_totals
    assert w["prefill_chunks_total"] == 1 + 3 + 2 + 1 + 2 + 1
    assert 0 < w["step_fetch_bytes_total"] < 64 * w["decode_rows_total"]
    assert eng.metrics_snapshot()["step_fetch_bytes_total"] \
        == w["step_fetch_bytes_total"]
    assert "paddle_tpu_serve_step_fetch_bytes_total " \
        in eng.render_prometheus()
    if not kw:
        # a chunk alone 5 B; one that carries max_batch slots 5 + 4 x 5
        assert w["prefill_chunks_with_decode_total"] > 0
        fetched = (5 * w["prefill_chunks_total"]
                   + 20 * w["prefill_chunks_with_decode_total"])
        assert w["step_fetch_bytes_total"] >= fetched
        assert (w["step_fetch_bytes_total"] - fetched) % 5 == 0
        for seq, req in zip(sorted(eng.finished,
                                   key=lambda s: s.req.request_id),
                            _window()):
            want = L.greedy_generate(
                params, jnp.asarray([req.prompt], jnp.int32), cfg,
                req.max_new_tokens)
            assert seq.generated == np.asarray(want)[0].tolist()


def test_fault_hooks_carry_tokens_and_flags(llama):
    """``serve.prefill.logits`` and ``serve.decode.logits`` keep their names
    and hand a corrupt callable what the engine fetched, tokens and flags;
    a row it poisons there is quarantined, the others decode on."""
    cfg, params = llama
    seen = {}

    def look(ctx):
        seen[len(seen)] = dict(ctx)

    def poison_second(ctx):
        look(ctx)
        raise PoisonError(ctx["rids"][1], "injected at the hook")

    eng = InferenceEngine(params, cfg, ServeConfig(
        block_size=128, num_blocks=12, max_batch=4, prefill_chunk=32,
        max_seq_len=384))
    rng = np.random.RandomState(3)
    for n in (9, 12):
        eng.submit(Request(rng.randint(1, 95, size=n).tolist(),
                           max_new_tokens=5))
    with faults.scope("serve.prefill.logits", "corrupt", nth=1,
                      corrupt=look):
        while sum(len(s.generated) > 0 for s in eng.active) < 2:
            eng.step()
    assert set(seen[0]) == {"rid", "tokens", "finite"}
    tok, fin = seen[0]["tokens"], seen[0]["finite"]
    assert tok.shape == fin.shape == () and tok.dtype == np.int32
    assert bool(fin) and int(tok) == eng.active[0].generated[0]
    with faults.scope("serve.decode.logits", "corrupt", nth=1,
                      corrupt=poison_second):
        eng.step()
    assert set(seen[1]) == {"rids", "tokens", "finite"}
    tok, fin = seen[1]["tokens"], seen[1]["finite"]
    assert isinstance(tok, np.ndarray) and tok.dtype == np.int32
    assert fin.dtype == np.bool_ and tok.shape == fin.shape == (2,)
    assert fin.all()
    assert [s.req.request_id for s in eng.failed] == [1]
    assert eng.failed[0].fail_cause == "injected at the hook"
    stats = eng.run([], deterministic=True)
    assert stats["failed"] == 1 and stats["requests"] == 1
    assert eng.pool.used_blocks == 0
