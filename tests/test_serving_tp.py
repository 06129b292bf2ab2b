"""Tensor-parallel serving (PR 19).

The contract under test is PARITY.md's: an engine running at mp > 1
inside the ('mp',)-sharded mesh — weights sliced per param_pspecs,
KV/scale/draft pools sharded by kv-head — emits token streams that are
bitwise-identical to the same trace at mp=1. Greedy argmax absorbs the
ULP-level reassociation drift of the row-parallel o_proj/down_proj
reductions, and the verify step all-gathers full-vocab logits in-island
so accept/commit decisions are rank-identical by construction.

Covered here: stream parity (plain / int8+prefix / speculative / under
eviction), the sharded mid-serve weight swap (drain, zero drops, swap
lands on sharded leaves), per-rank pool accounting, divisibility
rejection at init, and the full PR-14 crash matrix re-run on a sharded
engine with speculation + int8 + prefix caching all on.
"""
import numpy as np
import pytest

from paddle_tpu.inference import (InferenceEngine, Request, ServeConfig,
                                  read_journal)
from paddle_tpu.models.llama import init_llama_params, llama_tiny
from paddle_tpu.ops import _common
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULTS", "1")
    with _common.interpret_mode(True):
        yield
    faults.disarm()


@pytest.fixture(scope="module")
def model():
    # two layers so the default draft (first layer only) genuinely
    # disagrees with the base model, and so the later-layer KV pools
    # see the hidden-state drift the parity contract has to absorb
    cfg = llama_tiny(vocab=96, hidden=64, layers=2, heads=4, kv_heads=2,
                     seq=512)
    return cfg, init_llama_params(cfg, seed=3)


def _requests(n=3, max_new=8, seed=11):
    rng = np.random.RandomState(seed)
    # one multi-block prompt (130 > block_size) so the sharded pools
    # cross block boundaries mid-trace
    return [Request(rng.randint(1, 90, size=sz).tolist(),
                    max_new_tokens=max_new, arrival=float(i),
                    request_id=i)
            for i, sz in enumerate([9, 40, 130][:n])]


def _run(model, reqs=None, journal=None, engine_kw=None, **kw):
    cfg, params = model
    serve = ServeConfig(block_size=128, num_blocks=kw.pop("num_blocks", 10),
                        max_batch=2, prefill_chunk=32, max_seq_len=256,
                        **kw)
    eng = InferenceEngine(params, cfg, serve, record_events=True,
                          journal=journal, **(engine_kw or {}))
    eng.run(reqs if reqs is not None else _requests(), deterministic=True)
    return {s.req.request_id: s.generated for s in eng.finished}, eng


# -- stream parity ------------------------------------------------------------

COMBOS = [
    pytest.param({}, id="plain"),
    pytest.param({"prefix_cache": True, "kv_dtype": "int8"},
                 id="int8-prefix"),
    pytest.param({"prefix_cache": True, "kv_dtype": "int8",
                  "speculative": True, "draft_k": 3}, id="speculative"),
]


@pytest.mark.parametrize("kw", COMBOS)
def test_tp_streams_bit_identical(model, kw):
    ref, e1 = _run(model, **kw)
    got, e2 = _run(model, mp=2, **kw)
    assert got == ref, "mp=2 streams diverged from mp=1"
    assert len(got) == 3
    assert e1.pool.used_blocks == 0 and e2.pool.used_blocks == 0
    assert e2.stats()["mp"] == 2
    # the compiled-shape family is bounded: sharding changes the mesh a
    # program runs on, never which programs exist, but for the chunk that
    # carries the decode batch, which one chip alone offers
    assert (sorted(e2.stats()["compiles"])
            == sorted(c for c in e1.stats()["compiles"]
                      if not c.startswith("prefill+decode")))


def test_tp_parity_under_eviction(model):
    # pool sized to starve at mp=2 exactly as at mp=1: eviction order is
    # host-side and rank-replicated, so the re-derived streams match
    kw = dict(speculative=True, draft_k=4, num_blocks=5)
    ref, _ = _run(model, **kw)
    got, eng = _run(model, mp=2, **kw)
    assert got == ref
    assert eng.pool.used_blocks == 0
    assert eng.preemptions >= 0  # eviction path exercised without leaks


def test_tp_mp4_streams_bit_identical(model):
    # NKV % mp must hold, so mp=4 needs a wider-kv config than the
    # module model (kv_heads=2): one kv head per rank here
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=4,
                     seq=512)
    m = (cfg, init_llama_params(cfg, seed=5))
    ref, _ = _run(m)
    got, eng = _run(m, mp=4)
    assert got == ref
    assert eng.pool.used_blocks == 0 and eng.stats()["mp"] == 4


# -- per-rank pool accounting -------------------------------------------------

def test_tp_pool_bytes_per_rank_halve(model):
    kw = dict(prefix_cache=True, kv_dtype="int8", speculative=True,
              draft_k=3)
    _, e1 = _run(model, **kw)
    _, e2 = _run(model, mp=2, **kw)
    s1, s2 = e1.stats(), e2.stats()
    assert s1["mp"] == 1 and s2["mp"] == 2
    # every pool (int8 kv, fp32 scales, fp16 draft) shards on the
    # kv-head axis, so one rank holds exactly half the device bytes
    assert s1["pool_bytes_per_rank"] == 2 * s2["pool_bytes_per_rank"]
    assert s2["pool_bytes_per_rank"] > 0


def test_tp_rejects_indivisible_heads(model):
    cfg, params = model  # kv_heads=2: mp=4 cannot shard the KV pools
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=256, mp=4)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        InferenceEngine(params, cfg, serve)


def test_tp_env_knob_sets_degree(model, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVE_MP", "2")
    ref, _ = _run(model)  # ServeConfig(mp=) absent -> env knob wins
    cfg, params = model
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=256)
    eng = InferenceEngine(params, cfg, serve)
    assert eng.mp == 2
    monkeypatch.setenv("PADDLE_TPU_SERVE_MP", "1")


# -- sharded weight swap ------------------------------------------------------

def _copy(tree):
    import jax
    # fresh containers, same leaves: swap_fill mutates dicts in place
    return jax.tree_util.tree_map(lambda a: a, tree)


def test_tp_sharded_swap_drains_and_stays_sharded(model):
    cfg, params = model
    ref, _ = _run(model)  # mp=1, no swap: the bitwise reference

    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=256, mp=2)
    eng = InferenceEngine(params, cfg, serve, record_events=True)
    # the swap source is an UNSHARDED host-side copy: _apply_swap must
    # re-place every leaf onto the engine's sharded layout
    eng.swap_weights(_copy(params), at_iteration=3)
    stats = eng.run(_requests(), deterministic=True)

    got = {s.req.request_id: s.generated for s in eng.finished}
    assert got == ref  # identical swap is bit-identical, zero drops
    assert stats["weight_swaps"] == 1 and stats["unfinished"] == 0
    assert (eng.last_swap["in_flight_running"]
            + eng.last_swap["in_flight_prefill"]) >= 1
    assert eng.pool.used_blocks == 0
    # the swapped-in weights landed on the mp mesh, not replicated
    assert not eng.params["lm_head"].sharding.is_fully_replicated
    assert not eng.params["embed"].sharding.is_fully_replicated


# -- crash matrix, sharded ----------------------------------------------------

MATRIX = [
    ("serve.admit.before", 2),
    ("serve.admit.after", 2),
    ("serve.prefill.before", 2),
    ("serve.prefill.after", 2),
    ("serve.decode.before", 3),
    ("serve.decode.after", 3),
    ("serve.swap.before", 1),
    ("serve.swap.after", 1),
]

_TP_KW = dict(mp=2, prefix_cache=True, kv_dtype="int8", speculative=True,
              draft_k=3)


def _shared_requests(n=3, max_new=6, seed=7):
    """Identical 150-token prompts: one full shared block, so the
    prefix cache registers + hits on the sharded pools."""
    rng = np.random.RandomState(seed)
    prompt = rng.randint(1, 96, size=150).tolist()
    return [Request(list(prompt), max_new_tokens=max_new,
                    arrival=float(i), request_id=i) for i in range(n)]


@pytest.fixture(scope="module")
def tp_crash_ref(model, tmp_path_factory):
    """Unkilled sharded reference streams (computed once for the
    matrix), with the same mid-run weight swap the matrix runs
    schedule."""
    tmp = tmp_path_factory.mktemp("tpref")
    cfg, params = model
    with _common.interpret_mode(True):
        eng = InferenceEngine(
            params, cfg,
            ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=256, **_TP_KW),
            journal=str(tmp / "ref19.jsonl"))
        eng.swap_weights(_copy(params), at_iteration=4)
        eng.run(_shared_requests(), deterministic=True)
        ref = {s.req.request_id: s.generated for s in eng.finished}
    assert len(ref) == 3
    assert eng.pool.used_blocks == 0
    # identical prompts -> identical greedy streams, via cache hits
    assert len({tuple(t) for t in ref.values()}) == 1
    return ref


@pytest.mark.parametrize("point,nth", MATRIX,
                         ids=[f"{p}-tp" for p, _ in MATRIX])
def test_crash_matrix_recovers_bit_identical_sharded(model, tmp_path,
                                                     tp_crash_ref, point,
                                                     nth):
    """The full PR-14 fault matrix on a SHARDED engine with speculation,
    prefix caching and int8 KV on. The journal stays host-side and
    rank-replicated, recovery replays into a fresh sharded engine, and
    every re-derived stream is bitwise the unkilled sharded stream —
    which is itself bitwise the mp=1 stream."""
    cfg, params = model
    path = str(tmp_path / "kill19.jsonl")
    reqs = _shared_requests()
    serve_kw = dict(block_size=128, num_blocks=10, max_batch=2,
                    prefill_chunk=32, max_seq_len=256, **_TP_KW)

    eng = InferenceEngine(params, cfg, ServeConfig(**serve_kw),
                          journal=path)
    eng.swap_weights(_copy(params), at_iteration=4)
    with faults.scope(point, "raise", nth=nth) as plan:
        with pytest.raises(faults.FaultError):
            eng.run(reqs, deterministic=True)
        assert plan.fired == 1
        # the crash path released every live block on the sharded pool
        assert eng.pool.used_blocks == 0

        # recover into a FRESH sharded engine over the same journal
        eng2 = InferenceEngine(params, cfg, ServeConfig(**serve_kw),
                               journal=path)
        rec = eng2.recover()
        assert rec["torn_lines"] == 0
        journaled = ({s.req.request_id for s in eng2.waiting}
                     | {s.req.request_id for s in eng2.finished})
        resubmit = [Request(r.prompt, max_new_tokens=r.max_new_tokens,
                            request_id=r.request_id)
                    for r in reqs if r.request_id not in journaled]
        eng2.run(resubmit, deterministic=True)

    got = {s.req.request_id: s.generated for s in eng2.finished}
    assert got == tp_crash_ref, f"sharded streams diverged at {point}"
    assert eng2.pool.used_blocks == 0
    st = read_journal(path)
    assert st.finished == set(tp_crash_ref)
    assert st.torn_lines == 0


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
