"""Live weight push + engine preemption (InferenceEngine.swap_weights).

The serving-side contract pinned here (ISSUE 13):

  * a mid-serve swap applies at an iteration boundary — the drain point
    where the previous decode has synced its tokens — and never drops or
    corrupts a request;
  * swapping in IDENTICAL weights is bit-identical: the token stream
    matches an unswapped run exactly;
  * requests served entirely after a swap follow the NEW weights
    (greedy parity against the new params), earlier requests keep their
    already-generated prefix — the standard live-update contract;
  * `source` may be an in-memory tree, a checkpoint dir, or a
    CheckpointManager root (newest complete checkpoint wins);
  * engine preemption (flag, SIGTERM, injected) stops at an iteration
    boundary with queue/active state intact; a re-driven engine finishes
    every request with the same tokens as an uninterrupted run.

Tiny llama, pallas interpret mode on CPU, deterministic traces.
"""
import os
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.checkpoint import save_load as sl
from paddle_tpu.distributed.checkpoint.manager import CheckpointManager
from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.models.llama import (greedy_generate, init_llama_params,
                                     llama_tiny)
from paddle_tpu.ops import _common
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                     seq=512)
    return cfg, init_llama_params(cfg, seed=3), init_llama_params(cfg,
                                                                  seed=11)


def _serve():
    return ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                       prefill_chunk=32, max_seq_len=512)


def _prompts():
    rng = np.random.RandomState(0)
    return [rng.randint(1, 96, size=n).tolist() for n in (7, 130)]


def _copy(tree):
    # fresh containers, same leaves: swap_fill mutates dicts in place and
    # module-scoped fixture params must never be touched by a swap
    return jax.tree_util.tree_map(lambda a: a, tree)


def _greedy(cfg, params, prompt, n_new):
    with _common.interpret_mode(True):
        out = greedy_generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                              n_new)
    return np.asarray(out)[0].tolist()


def _toks(eng):
    return {s.req.request_id: s.tokens for s in eng.finished}


def _run(params, cfg, reqs, **kw):
    eng = InferenceEngine(_copy(params), cfg, _serve(), record_events=True,
                          **kw)
    stats = eng.run(reqs, deterministic=True)
    return eng, stats


# -- the swap contract -------------------------------------------------------

def test_mid_serve_identical_swap_is_bit_identical(model):
    cfg, params, _ = model
    prompts = _prompts()
    mk = lambda: [Request(p, max_new_tokens=5, arrival=float(i))
                  for i, p in enumerate(prompts)]
    base, _ = _run(params, cfg, mk())

    eng = InferenceEngine(_copy(params), cfg, _serve(), record_events=True)
    sched = eng.swap_weights(_copy(params), at_iteration=3)
    assert sched == {"scheduled_at": 3}
    stats = eng.run(mk(), deterministic=True)

    assert _toks(eng) == _toks(base)  # bit-identical token streams
    assert stats["requests"] == 2 and stats["unfinished"] == 0
    assert stats["weight_swaps"] == 1 and eng.swaps == 1
    # the swap really happened mid-serve, at the scheduled drain point,
    # with work in flight — not on an idle engine
    assert eng.last_swap["iteration"] == 2  # top of the step becoming 3
    assert (eng.last_swap["in_flight_running"]
            + eng.last_swap["in_flight_prefill"]) >= 1
    assert eng.pool.used_blocks == 0  # no leaks through the swap


def test_requests_after_swap_follow_new_weights(model):
    cfg, params, params2 = model
    prompt = _prompts()[0]  # 7 tokens: one prefill chunk
    old_ref = _greedy(cfg, params, prompt, 4)
    new_ref = _greedy(cfg, params2, prompt, 4)
    assert old_ref != new_ref  # otherwise this test proves nothing

    eng = InferenceEngine(_copy(params), cfg, _serve(), record_events=True)
    eng.swap_weights(_copy(params2), at_iteration=6)
    reqs = [Request(prompt, max_new_tokens=4, arrival=0.0),   # pre-swap
            Request(prompt, max_new_tokens=4, arrival=8.0)]   # post-swap
    stats = eng.run(reqs, deterministic=True)

    assert stats["requests"] == 2 and stats["unfinished"] == 0
    got = {s.req.request_id: s.generated for s in eng.finished}
    assert got[0] == old_ref  # finished before the swap landed
    assert got[1] == new_ref  # served end-to-end by the new weights


def test_swap_from_checkpoint_dir_and_manager_root(model, tmp_path):
    cfg, params, params2 = model
    prompt = _prompts()[0]
    new_ref = _greedy(cfg, params2, prompt, 4)

    # a bare save_state_dict dir
    ck = str(tmp_path / "ck")
    sl.save_state_dict(_copy(params2), ck)
    eng = InferenceEngine(_copy(params), cfg, _serve())
    stats = eng.swap_weights(ck)
    # the engine's leaves: the model's prepared tree (fused q/k/v)
    assert stats["n_leaves"] == len(jax.tree_util.tree_leaves(eng.params))
    assert stats["source"] == os.path.abspath(ck)
    eng.run([Request(prompt, max_new_tokens=4, arrival=0.0)],
            deterministic=True)
    assert eng.finished[0].generated == new_ref

    # a CheckpointManager root: newest complete checkpoint, nested under
    # the TrainStep state dict's "params" key
    mgr = CheckpointManager(str(tmp_path / "root"), keep=2)
    mgr.save({"params": _copy(params), "step": 1}, 1, block=True)
    mgr.save({"params": _copy(params2), "step": 2}, 2, block=True)
    eng2 = InferenceEngine(_copy(params), cfg, _serve())
    stats2 = eng2.swap_weights(str(tmp_path / "root"))
    assert stats2["source"] == mgr.step_dir(2)
    eng2.run([Request(prompt, max_new_tokens=4, arrival=0.0)],
             deterministic=True)
    assert eng2.finished[0].generated == new_ref


def test_swap_rejects_mismatched_trees(model):
    cfg, params, _ = model
    eng = InferenceEngine(_copy(params), cfg, _serve())
    bad = _copy(params)
    bad.pop(sorted(bad)[0])
    with pytest.raises(ValueError, match="param tree mismatch"):
        eng.swap_weights(bad)

    leaves, treedef = jax.tree_util.tree_flatten(_copy(params))
    i = next(j for j, l in enumerate(leaves) if l.ndim >= 1)
    leaves[i] = leaves[i][..., :1]
    with pytest.raises(ValueError, match="shape mismatch"):
        eng.swap_weights(jax.tree_util.tree_unflatten(treedef, leaves))
    # a rejected swap leaves the engine serving the OLD weights intact
    prompt = _prompts()[0]
    eng.run([Request(prompt, max_new_tokens=4, arrival=0.0)],
            deterministic=True)
    assert eng.finished[0].generated == _greedy(cfg, params, prompt, 4)
    assert eng.swaps == 0


def test_swap_under_preemption_storm_drops_nothing(model, monkeypatch):
    """Forced evictions raining on the scheduler while a (identical)
    swap lands mid-serve: every request still finishes with the greedy
    reference tokens (recompute semantics), nothing leaks."""
    monkeypatch.setenv(faults.ENV_FAULTS, "1")
    cfg, params, _ = model
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 96, size=120).tolist() for _ in range(3)]
    serve = ServeConfig(block_size=128, num_blocks=5, max_batch=3,
                        prefill_chunk=64, max_seq_len=256)
    eng = InferenceEngine(_copy(params), cfg, serve, record_events=True)
    eng.swap_weights(_copy(params), at_iteration=6)
    reqs = [Request(p, max_new_tokens=16, arrival=float(i))
            for i, p in enumerate(prompts)]
    try:
        with faults.scope("serve.preempt_storm", "fire", p=0.25, seed=5,
                          max_fires=None) as plan:
            stats = eng.run(reqs, deterministic=True)
    finally:
        faults.disarm()
    assert plan.fired >= 1, "the storm never struck — weaken nothing"
    assert stats["requests"] == 3 and stats["unfinished"] == 0
    assert eng.swaps == 1
    assert all(len(s.generated) == 16 for s in eng.finished)
    for i, p in enumerate(prompts):
        got = [s for s in eng.finished
               if s.req.request_id == i][0].generated
        assert got == _greedy(cfg, params, p, 16), f"request {i}"
    assert eng.pool.used_blocks == 0


# -- engine preemption -------------------------------------------------------

def test_injected_preemption_stops_cleanly_and_resumes(model, monkeypatch,
                                                       tmp_path):
    """A preemption three iterations in: run() exits at the boundary with
    the post-mortem dumped and all state intact; re-driving the same
    engine finishes every request bit-identically to an uninterrupted
    run."""
    from paddle_tpu.observability import load_dump
    monkeypatch.setenv(faults.ENV_FAULTS, "1")
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    cfg, params, _ = model
    prompts = _prompts()
    mk = lambda: [Request(p, max_new_tokens=5, arrival=float(i))
                  for i, p in enumerate(prompts)]
    base, _ = _run(params, cfg, mk())

    eng = InferenceEngine(_copy(params), cfg, _serve(), record_events=True,
                          flight_recorder=True)
    try:
        with faults.scope("serve.preempt", "fire", nth=3):
            st1 = eng.run(mk(), deterministic=True)
    finally:
        faults.disarm()
    assert st1["preempted"] is True
    assert any(e[1] == "preempt_stop" for e in eng.events)
    assert st1["unfinished"] >= 1  # stopped with work still queued/active
    assert len(eng.recorder.dumped) == 1
    payload = load_dump(eng.recorder.dumped[0])
    assert payload["reason"] == "preemption" and payload["source"] == "engine"

    # the successor re-drives the SAME engine state: nothing was dropped
    st2 = eng.run([], deterministic=True)
    assert st2["requests"] == 2 and st2["unfinished"] == 0
    assert _toks(eng) == _toks(base)
    assert eng.pool.used_blocks == 0


def test_sigterm_preempts_then_cleared_engine_serves(model):
    cfg, params, _ = model
    prompts = _prompts()
    eng = InferenceEngine(_copy(params), cfg, _serve())
    reqs = [Request(p, max_new_tokens=5, arrival=0.0) for p in prompts]
    eng.install_preemption_handler()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        stats = eng.run(reqs, deterministic=True)
    finally:
        eng.uninstall_preemption_handler()
    # the flag was already set: not a single request was admitted or lost
    assert stats["preempted"] is True and len(eng.finished) == 0
    eng.clear_preemption()
    stats2 = eng.run(reqs, deterministic=True)
    assert stats2["requests"] == 2 and stats2["unfinished"] == 0
    for i, p in enumerate(prompts):
        got = [s for s in eng.finished
               if s.req.request_id == i][0].generated
        assert got == _greedy(cfg, params, p, 5), f"request {i}"


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
