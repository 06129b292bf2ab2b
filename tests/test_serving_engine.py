"""Continuous-batching serving engine (paddle_tpu/inference/engine.py).

Scheduler invariants pinned here:
  * token parity: continuous batching + chunked prefill + the paged KV
    cache produce the SAME greedy tokens as the contiguous-cache
    ``greedy_generate`` path, per request;
  * no block leaks: the pool returns to fully-free after every run,
    including runs with preemption;
  * deterministic replay: the same arrival trace replays to an
    identical event log and identical tokens;
  * preempt-by-eviction: when the pool runs dry mid-decode the
    youngest sequence is evicted, re-prefilled on readmission, and
    still produces the greedy reference tokens (recompute semantics).

Tiny model, pallas interpret mode on CPU. The two engine scenarios run
once in module fixtures; tests assert on their results.
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.inference import (BlockPool, InferenceEngine, Request,
                                  ServeConfig, pad_table)
from paddle_tpu.models.llama import (greedy_generate, init_llama_params,
                                     llama_tiny)
from paddle_tpu.ops import _common


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                     seq=512)
    return cfg, init_llama_params(cfg, seed=3)


def _greedy_ref(model, prompt, n_new):
    cfg, params = model
    with _common.interpret_mode(True):
        out = greedy_generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                              n_new)
    return np.asarray(out)[0].tolist()


@pytest.fixture(scope="module")
def basic_run(model):
    """Two mixed-length prompts (one multi-chunk, multi-block) through
    the engine twice on the same deterministic trace."""
    cfg, params = model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (7, 130)]
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=512)

    def one():
        eng = InferenceEngine(params, cfg, serve, record_events=True)
        reqs = [Request(p, max_new_tokens=5, arrival=float(i))
                for i, p in enumerate(prompts)]
        stats = eng.run(reqs, deterministic=True)
        return eng, stats

    with _common.interpret_mode(True):
        eng, stats = one()
        eng2, _ = one()
    return {"prompts": prompts, "eng": eng, "stats": stats, "eng2": eng2}


def test_engine_matches_greedy_generate(model, basic_run):
    for i, p in enumerate(basic_run["prompts"]):
        got = [s for s in basic_run["eng"].finished
               if s.req.request_id == i][0].generated
        assert got == _greedy_ref(model, p, 5), f"request {i}"


def test_no_block_leaks(basic_run):
    eng = basic_run["eng"]
    assert eng.pool.used_blocks == 0
    assert eng.pool.free_blocks == eng.serve.num_blocks - 1


def test_deterministic_replay(basic_run):
    eng, eng2 = basic_run["eng"], basic_run["eng2"]
    assert eng.events == eng2.events
    toks = lambda e: {s.req.request_id: s.tokens for s in e.finished}
    assert toks(eng) == toks(eng2)


def test_bounded_compiles(basic_run):
    """One compile per bucketed shape: prefill chunk, decode buckets, and
    the chunk that carries the decode batch (``max_batch`` rows wide)."""
    stats = basic_run["stats"]
    assert set(stats["compiles"]) <= {"prefill_32", "decode_1", "decode_2",
                                      "prefill+decode_32_2"}


def test_bounded_compiles_speculative(model):
    """With speculation on (PR 18) the family stays counted/bounded:
    the draft's prefill + per-bucket decode programs and the base's
    per-bucket K+1-wide verify program replace plain decode — no
    program keyed on data (accept length, proposal count) ever
    compiles."""
    cfg, params = model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (7, 40)]
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=512,
                        speculative=True, draft_k=2)
    eng = InferenceEngine(params, cfg, serve)
    reqs = [Request(p, max_new_tokens=5, arrival=float(i))
            for i, p in enumerate(prompts)]
    stats = eng.run(reqs, deterministic=True)
    assert set(stats["compiles"]) <= {"prefill_32", "draft_prefill_32",
                                      "draft_1", "draft_2",
                                      "verify_1", "verify_2"}
    assert any(k.startswith("verify_") for k in stats["compiles"])
    for i, p in enumerate(prompts):
        got = [s for s in eng.finished
               if s.req.request_id == i][0].generated
        assert got == _greedy_ref(model, p, 5), f"request {i}"


@pytest.fixture(scope="module")
def evict_run(model):
    """Pool sized so three one-block sequences admit, then starve when
    each crosses its block boundary mid-decode: 4 usable blocks, three
    120-token prompts growing past 128 cached tokens."""
    cfg, params = model
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 96, size=120).tolist() for _ in range(3)]
    serve = ServeConfig(block_size=128, num_blocks=5, max_batch=3,
                        prefill_chunk=64, max_seq_len=256)
    eng = InferenceEngine(params, cfg, serve, record_events=True)
    reqs = [Request(p, max_new_tokens=16, arrival=float(i))
            for i, p in enumerate(prompts)]
    with _common.interpret_mode(True):
        stats = eng.run(reqs, deterministic=True)
    return {"prompts": prompts, "eng": eng, "stats": stats}


def test_eviction_fires_and_recovers(evict_run):
    st = evict_run["stats"]
    assert st["preemptions"] >= 1
    assert st["requests"] == 3
    evicted = [ev for ev in evict_run["eng"].events if ev[1] == "evict"]
    assert evicted, "no evict event recorded"
    # evicted sequences are readmitted and finish
    assert all(len(s.generated) == 16 for s in evict_run["eng"].finished)


def test_eviction_recompute_matches_greedy(model, evict_run):
    for i, p in enumerate(evict_run["prompts"]):
        got = [s for s in evict_run["eng"].finished
               if s.req.request_id == i][0].generated
        assert got == _greedy_ref(model, p, 16), f"request {i}"


def test_no_block_leaks_after_eviction(evict_run):
    assert evict_run["eng"].pool.used_blocks == 0


# -- host-side unit checks (no device work) ---------------------------------

def test_block_pool_invariants():
    pool = BlockPool(num_blocks=6, block_size=128)
    assert pool.free_blocks == 5          # block 0 reserved (null block)
    got = pool.alloc(5)
    assert got is not None and 0 not in got
    assert pool.alloc(1) is None          # all-or-nothing when dry
    pool.free(got[:2])
    assert pool.free_blocks == 2
    with pytest.raises(ValueError):
        pool.free([got[0]])               # double free
    with pytest.raises(ValueError):
        pool.free([0])                    # the null block is never owned
    assert pool.blocks_for(129) == 2
    assert 0.0 < pool.utilization < 1.0


def test_pad_table_pads_with_null_block():
    row = pad_table([3, 7], 4)
    assert row.dtype == np.int32
    assert row.tolist() == [3, 7, 0, 0]


def test_serve_config_and_submit_validation(model):
    cfg, params = model
    serve = ServeConfig(block_size=128, num_blocks=4, max_batch=4,
                        max_seq_len=256)
    assert serve.decode_buckets == (1, 2, 4)
    with pytest.raises(ValueError):
        ServeConfig(max_batch=4, decode_buckets=(1, 2))  # largest != max
    eng = InferenceEngine(params, cfg, serve)
    with pytest.raises(ValueError):
        eng.submit(Request([1] * 250, max_new_tokens=16))  # > max_seq_len
    with pytest.raises(ValueError):
        eng.submit(Request([]))


if __name__ == "__main__":
    pytest.main([__file__, "-q"])


# -- PR-12: request tracing, streaming SLO, flight recorder -------------------

_BUCKET = 10.0 ** (1.0 / 16.0) * (1.0 + 1e-9)  # one histogram bucket


def _nearest_rank(xs, q):
    import math
    s = sorted(xs)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


@pytest.fixture(scope="module")
def traced_evict_run(model):
    """The evict_run trace replayed with every observability layer on —
    tracing must not perturb scheduling, so tokens and the event log must
    match the untraced fixture bit for bit."""
    cfg, params = model
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 96, size=120).tolist() for _ in range(3)]
    serve = ServeConfig(block_size=128, num_blocks=5, max_batch=3,
                        prefill_chunk=64, max_seq_len=256)
    eng = InferenceEngine(params, cfg, serve, record_events=True,
                          trace_requests=True, flight_recorder=True)
    reqs = [Request(p, max_new_tokens=16, arrival=float(i))
            for i, p in enumerate(prompts)]
    with _common.interpret_mode(True):
        stats = eng.run(reqs, deterministic=True)
    return {"eng": eng, "stats": stats}


def test_tracing_is_measurement_only(evict_run, traced_evict_run):
    """Bit-identical tokens and event log, traced vs untraced."""
    toks = lambda e: {s.req.request_id: s.tokens for s in e.finished}
    assert toks(traced_evict_run["eng"]) == toks(evict_run["eng"])
    assert traced_evict_run["eng"].events == evict_run["eng"].events


def test_span_tree_spans_eviction_and_reprefill(traced_evict_run):
    from paddle_tpu.observability.request_trace import spans_overlap
    eng, stats = traced_evict_run["eng"], traced_evict_run["stats"]
    assert stats["preemptions"] >= 1
    assert eng.tracer.request_ids() == [0, 1, 2]
    evicted = [rid for rid in (0, 1, 2)
               if any(s["cat"] == "evict"
                      for s in eng.tracer.tree(rid)["children"])]
    assert evicted, "eviction run recorded no evict spans"
    tree = eng.tracer.tree(evicted[0])
    cats = [c["cat"] for c in tree["children"]]
    names = [c["name"] for c in tree["children"]]
    # full lifecycle: queue wait -> prefill -> decode -> evicted ->
    # requeued -> recompute prefill -> decode again -> finish
    for cat in ("queue", "prefill", "decode", "evict", "reprefill",
                "finish"):
        assert cat in cats, (cat, cats)
    assert "requeue" in names
    assert cats.index("evict") < cats.index("reprefill")
    # recompute covers already-generated context, after the evict marker
    re_i = cats.index("reprefill")
    assert tree["children"][re_i]["args"]["n_tokens"] > 0
    # children are time-ordered under a root covering the lifetime
    t0s = [c["t0"] for c in tree["children"]]
    assert t0s == sorted(t0s)
    assert tree["t0"] <= t0s[0] and tree["t1"] >= tree["children"][-1]["t1"]
    # a request is in one engine phase at a time: row spans never overlap
    assert not spans_overlap(tree["children"])


def test_streaming_slo_within_one_bucket_of_exact(traced_evict_run):
    eng, stats = traced_evict_run["eng"], traced_evict_run["stats"]
    ttfts = [s.first_token_t - s.arrival for s in eng.finished]
    gaps = []
    for s in eng.finished:
        gaps.extend(np.diff(s.token_times).tolist())
    for key, xs, q in (("ttft_stream_p50_s", ttfts, 50),
                       ("ttft_stream_p99_s", ttfts, 99),
                       ("tpot_stream_p50_s", gaps, 50),
                       ("tpot_stream_p99_s", gaps, 99)):
        exact = _nearest_rank(xs, q)
        assert exact / _BUCKET <= stats[key] <= exact * _BUCKET, (key, exact,
                                                                 stats[key])
    # queue-wait histogram saw exactly one first admission per request
    assert eng.slo["queue_wait"].count == 3


def test_trace_exports_jsonl_and_chrome(traced_evict_run, tmp_path):
    eng = traced_evict_run["eng"]
    jp = eng.tracer.export_jsonl(str(tmp_path / "spans.jsonl"))
    from paddle_tpu.observability import load_jsonl
    recs = load_jsonl(jp)
    assert len(recs) == eng.tracer.span_count()
    assert all(r["t0_s"] >= 0 for r in recs)
    cp = eng.tracer.export_chrome(str(tmp_path / "trace.json"))
    data = json.load(open(cp))
    names = {e["args"]["name"] for e in data["traceEvents"]
             if e["name"] == "thread_name"}
    assert {"engine/serve.step", "engine/serve.admit",
            "engine/serve.prefill", "engine/serve.decode",
            "engine/serve.report", "request 0", "request 1",
            "request 2"} <= names
    evs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert evs and all(e["dur"] >= 0 for e in evs)


def test_metrics_snapshot_and_prometheus(traced_evict_run):
    eng = traced_evict_run["eng"]
    snap = eng.metrics_snapshot()
    assert snap["finished_requests"] == 3
    assert snap["queue_depth"] == 0 and snap["pool_utilization"] == 0.0
    prom = eng.render_prometheus()
    assert "# TYPE paddle_tpu_serve_ttft_seconds histogram" in prom
    assert "paddle_tpu_serve_tpot_seconds_bucket" in prom
    assert 'le="+Inf"' in prom
    assert "paddle_tpu_serve_preemptions" in prom
    assert f"paddle_tpu_serve_queue_wait_seconds_count 3" in prom


def test_recorder_ring_populated_and_clean(traced_evict_run):
    eng = traced_evict_run["eng"]
    assert len(eng.recorder.ring) > 0
    assert eng.recorder.dumped == []
    rec = next(r for r in reversed(eng.recorder.ring) if "tokens" in r)
    assert {"iteration", "queue_depth", "pool_utilization"} <= set(rec)


def test_unfinished_requests_counted_not_dropped(model):
    """End-of-run TTFT accounting: a request that never produced a first
    token lands in ``unfinished`` instead of silently vanishing from (or
    poisoning) the percentiles."""
    cfg, params = model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (7, 130)]
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=512)
    eng = InferenceEngine(params, cfg, serve)
    reqs = [Request(p, max_new_tokens=5, arrival=0.0) for p in prompts]
    with pytest.raises(RuntimeError):
        eng.run(reqs, deterministic=True, max_iterations=8)
    st = eng.stats()
    assert st["requests"] + st["unfinished"] == 2
    assert st["unfinished"] >= 1
    # percentiles are conditioned on requests that got a first token
    n_with_token = sum(1 for s in eng.finished
                       if s.first_token_t is not None)
    assert (st["ttft_p50_s"] is None) == (n_with_token == 0)
    # a finished run reports zero unfinished (see traced_evict_run)


def test_exception_dumps_flight_recorder(model, tmp_path, monkeypatch):
    """A mid-serve crash writes the last-N-iterations post-mortem before
    the exception propagates."""
    from paddle_tpu.observability import load_dump
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    cfg, params = model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (7, 130)]
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=512)
    eng = InferenceEngine(params, cfg, serve, flight_recorder=True)
    reqs = [Request(p, max_new_tokens=5, arrival=0.0) for p in prompts]
    with pytest.raises(RuntimeError):
        eng.run(reqs, deterministic=True, max_iterations=6)
    assert len(eng.recorder.dumped) == 1
    payload = load_dump(eng.recorder.dumped[0])
    assert payload["reason"] == "exception"
    assert payload["source"] == "engine"
    assert payload["n_records"] > 0
    assert payload["records"][-1]["iteration"] == 6


def test_engine_module_imports_no_model():
    """What a model hands the engine lives beside the model and is fetched
    inside ``_serving_for``: importing ``inference/engine.py`` runs no import
    from ``paddle_tpu.models``."""
    import ast

    from paddle_tpu.inference import engine

    def at_import(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield node
            for field in ("body", "orelse", "finalbody", "handlers"):
                yield from at_import(getattr(node, field, []))

    with open(engine.__file__) as f:
        tree = ast.parse(f.read())
    found = []
    for node in at_import(tree.body):
        if isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            names = [base + "." * bool(node.module) + a.name
                     for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        found += [(node.lineno, n) for n in names
                  if n.startswith(("..models", "paddle_tpu.models"))]
    assert not found, f"module-level imports of a model: {found}"
