"""The phase spans of ``InferenceEngine.step()`` (``serve.*``).

One call site per phase boundary feeds the profiler's trace (a
``TraceAnnotation`` beside the device's lines, on one clock), the request
tracer (the same name, iteration and parent) and the flight recorder's
per-phase milliseconds. Pinned here, on a tiny model with Pallas in
interpret mode: the xplane holds every span the run crossed, children lie
inside their parents, the counts on the spans add up to the registry's
counters, and none of it changes a token.

The profiler session is opened and closed in ONE place (``profiled``, used
by the module fixture only), with the stop in a ``finally``: a failing
test leaves no session open for ``tests/test_profiler.py`` on this worker.
"""
import glob
import os

import numpy as np
import pytest

import jax

from paddle_tpu import observability as obs
from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.models.llama import init_llama_params, llama_tiny
from paddle_tpu.ops import _common

CONFIGS = {
    "plain": {},
    "int8": {"kv_dtype": "int8"},
    "spec": {"speculative": True, "draft_k": 3},
}
LEAVES = ("plan", "launch", "wait", "commit")


def build(**serve_kw):
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                     seq=512)
    params = init_llama_params(cfg, seed=3)
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=64, max_seq_len=512, **serve_kw)
    rng = np.random.RandomState(0)
    # 7 tokens: one chunk; 130: three chunks over two blocks; 20: arrives
    # while the batch is full and waits a few iterations
    reqs = [Request(rng.randint(1, 96, size=n).tolist(), max_new_tokens=6,
                    arrival=float(i)) for i, n in enumerate((7, 130, 20))]
    return cfg, params, serve, reqs


def serve_once(trace_requests=False, **serve_kw):
    cfg, params, serve, reqs = build(**serve_kw)
    with _common.interpret_mode(True):
        eng = InferenceEngine(params, cfg, serve,
                              trace_requests=trace_requests)
        eng.run(reqs, deterministic=True)
    return eng


def tokens(eng):
    return {s.req.request_id: list(s.tokens) for s in eng.finished}


def profiled(fn, trace_dir):
    """``fn()`` under a profiler session; returns (its result, every
    ``serve.*`` event of the xplane as (name, start_ns, end_ns, stats))."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the annotations stay; no per-call
    opts.host_tracer_level = 2        # Python events
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    return result, sorted(events, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Each configuration served once under a profiler session with the
    request tracer on, and once with neither."""
    out = {}
    for name, kw in CONFIGS.items():
        eng, events = profiled(
            lambda: serve_once(trace_requests=True, **kw),
            tmp_path_factory.mktemp(f"xplane_{name}"))
        out[name] = {"eng": eng, "events": events,
                     "plain_eng": serve_once(**kw)}
    return out


def parent_of(events, i):
    """The innermost span that encloses event ``i`` in time."""
    name, s, e, _ = events[i]
    best = None
    for j, (n2, s2, e2, _) in enumerate(events):
        if j != i and s2 <= s and e <= e2 and (e2 - s2) > (e - s):
            if best is None or (e2 - s2) < (best[2] - best[1]):
                best = events[j]
    return best[0] if best else None


def expected_parent(name):
    if name in ("serve.step", "serve.submit"):
        return None
    if name in ("serve.admit", "serve.prefill", "serve.decode",
                "serve.settle", "serve.report"):
        return "serve.step"
    if name in ("serve.draft", "serve.verify"):
        return "serve.decode"
    if name == "serve.draft.prefill":
        return "serve.prefill.commit"
    return name.rsplit(".", 1)[0]


@pytest.mark.parametrize("config", list(CONFIGS))
def test_xplane_holds_every_span_the_run_crossed(runs, config):
    names = {e[0] for e in runs[config]["events"]}
    want = {"serve.submit", "serve.step", "serve.admit", "serve.report",
            "serve.prefill", "serve.decode"}
    want |= {f"serve.prefill.{x}" for x in LEAVES}
    want |= {"serve.decode.plan", "serve.decode.commit"}
    if config == "spec":     # (e) the speculative path's own spans
        want |= {"serve.draft", "serve.draft.launch", "serve.draft.wait",
                 "serve.draft.prefill", "serve.verify",
                 "serve.verify.launch", "serve.verify.wait"}
        assert "serve.decode.launch" not in names
    else:                    # (e) int8 KV crosses the same boundaries
        want |= {"serve.decode.launch", "serve.decode.wait"}
    assert want <= names, sorted(want - names)


@pytest.mark.parametrize("config", list(CONFIGS))
def test_children_lie_inside_their_parents(runs, config):
    events = runs[config]["events"]
    for i, (name, *_rest) in enumerate(events):
        assert parent_of(events, i) == expected_parent(name), name


@pytest.mark.parametrize("config", list(CONFIGS))
def test_one_step_span_per_iteration(runs, config):
    eng, events = runs[config]["eng"], runs[config]["events"]
    steps = [e for e in events if e[0] == "serve.step"]
    assert [e[3]["iteration"] for e in steps] \
        == list(range(1, eng.iteration + 1))
    submits = [e for e in events if e[0] == "serve.submit"]
    assert sorted(e[3]["rid"] for e in submits) == [0, 1, 2]
    assert all(e[3]["accepted"] == 1 for e in submits)
    admits = [e[3] for e in events if e[0] == "serve.admit"]
    assert sum(a["admitted"] for a in admits) == 3
    assert max(a["waiting"] for a in admits) >= 1


@pytest.mark.parametrize("config", list(CONFIGS))
def test_counts_on_the_spans_add_up_to_the_registry(runs, config):
    """(d): useful never exceeds attempted, and the counters are the sums
    over the recorded spans. A ``serve.prefill`` whose program carried the
    decode batch (the plain cache alone offers it) says so with ``rows`` of
    a ``bucket`` of ``max_batch``, and its rows count as decoded rows do."""
    eng, events = runs[config]["eng"], runs[config]["events"]
    chunks = [e[3] for e in events if e[0] == "serve.prefill"]
    carrying = [c for c in chunks if "rows" in c]
    assert bool(carrying) == (config == "plain")
    assert all(1 <= c["rows"] <= c["bucket"] == 2 for c in carrying)
    decodes = [e[3] for e in events if e[0] == "serve.decode"] + carrying
    assert decodes and chunks
    assert all(1 <= d["rows"] <= d["bucket"] <= 2 for d in decodes)
    assert all(1 <= c["n_live"] <= c["chunk"] == 64 for c in chunks)
    assert {c["rid"] for c in chunks} == {0, 1, 2}
    # the 130-token prompt: chunks start at 0, 64, 128 and the last holds 2
    assert [(c["start"], c["n_live"]) for c in chunks if c["rid"] == 1] \
        == [(0, 64), (64, 64), (128, 2)]
    snap = eng.metrics_snapshot()
    assert snap["decode_rows_total"] == sum(d["rows"] for d in decodes)
    assert snap["decode_slots_total"] == sum(d["bucket"] for d in decodes)
    assert snap["prefill_tokens_total"] == sum(c["n_live"] for c in chunks) \
        == 7 + 130 + 20
    assert snap["prefill_slots_total"] == 64 * len(chunks)
    # blocks of 128 in a table of 512 / 128: the 130-token prompt's chunks
    # walk 1, 1 and 2 live blocks of the 4 a whole-table walk would visit
    assert [(c["ctx_blocks"], c["table_blocks"]) for c in chunks
            if c["rid"] == 1] == [(1, 4), (1, 4), (2, 4)]
    assert snap["prefill_ctx_blocks_total"] \
        == sum(c["ctx_blocks"] for c in chunks) == 1 + 4 + 1
    assert snap["prefill_table_blocks_total"] == 4 * len(chunks)
    assert snap["prefill_chunks_total"] == len(chunks)
    assert snap["prefill_chunks_with_decode_total"] == len(carrying)
    prom = eng.render_prometheus()
    for name in ("decode_rows_total", "decode_slots_total",
                 "prefill_tokens_total", "prefill_slots_total",
                 "prefill_ctx_blocks_total", "prefill_table_blocks_total",
                 "prefill_chunks_total", "prefill_chunks_with_decode_total"):
        assert f"paddle_tpu_serve_{name} {snap[name]}" in prom


@pytest.mark.parametrize("config", list(CONFIGS))
def test_first_call_marks_the_launch_that_compiled(runs, config):
    events = runs[config]["events"]
    launches = [e for e in events if e[0].endswith(".launch")
                or e[0] == "serve.draft.prefill"]
    firsts = [e[0] for e in launches if e[3].get("first_call")]
    # one per compiled program of the engine, and only its first call
    assert len(firsts) == len(runs[config]["eng"]._compiled)
    assert firsts[0] == "serve.prefill.launch"
    assert len(launches) > len(firsts)


@pytest.mark.parametrize("config", list(CONFIGS))
def test_tracing_changes_no_token(runs, config):
    """(b): a profiler session and the request tracer are measurement
    only."""
    r = runs[config]
    assert tokens(r["eng"]) == tokens(r["plain_eng"])
    assert r["eng"].iteration == r["plain_eng"].iteration
    assert r["plain_eng"].tracer is None


def test_request_tracer_alone_changes_no_token(runs):
    eng = serve_once(trace_requests=True)
    assert tokens(eng) == tokens(runs["plain"]["plain_eng"])


@pytest.mark.parametrize("config", list(CONFIGS))
def test_tracer_phases_are_the_annotations(runs, config):
    """(c): same names, same count of each, same parents, same
    iteration numbers."""
    eng, events = runs[config]["eng"], runs[config]["events"]
    phases = [s for s in eng.tracer.spans if s["cat"] == "phase"]
    count = lambda names: {n: names.count(n) for n in set(names)}
    assert count([p["name"] for p in phases]) \
        == count([e[0] for e in events])
    for p in phases:
        assert p["args"]["parent"] == expected_parent(p["name"]), p["name"]
    steps = [p["args"]["iteration"] for p in phases
             if p["name"] == "serve.step"]
    assert steps == list(range(1, eng.iteration + 1))
    by_iter = {}
    for p in phases:
        by_iter.setdefault(p["args"]["iteration"], []).append(p)
    for it, ps in by_iter.items():
        step = [p for p in ps if p["name"] == "serve.step"]
        if not step:        # submits before the first step carry 0
            assert it == 0 and {p["name"] for p in ps} == {"serve.submit"}
            continue
        for p in ps:
            if p["name"] != "serve.submit":
                assert step[0]["t0"] <= p["t0"] and p["t1"] <= step[0]["t1"]


def test_no_global_counter_per_iteration(runs):
    """The serving path bumps nothing in the process-global table that only
    the FleetMonitor reads: its events are the registry's (finished, shed,
    failed, preemptions) and the event log's."""
    obs.reset_counters()
    eng = serve_once()
    keys = obs.counters()
    assert not [k for k in keys if k.startswith(("serve.", "site.serve."))]
    assert eng.metrics_snapshot()["finished_requests"] == 3


def test_recorder_record_holds_the_phases(tmp_path):
    cfg, params, serve, reqs = build()
    with _common.interpret_mode(True):
        eng = InferenceEngine(params, cfg, serve, flight_recorder=True)
        eng.run(reqs, deterministic=True)
    recs = [r for r in eng.recorder.ring if "step_time_s" in r]
    assert len(recs) == eng.iteration
    both = next(r for r in recs if r.get("n_live") and r.get("rows"))
    for key in ("admit_ms", "report_ms", "prefill_ms", "decode_ms"):
        assert both[key] >= 0.0, key
    # a phase reads the program launched before its own (nothing is in
    # flight before the first): every leaf is in some iteration's record
    names = [f"{k}_{x}_ms" for k in ("prefill", "decode") for x in LEAVES]
    for key in names:
        assert any(r.get(key, -1.0) >= 0.0 for r in recs), key
    assert both["rows"] <= both["bucket"] and both["n_live"] <= 64
    leaves = sum(both.get(k, 0.0) for k in names) + both["admit_ms"]
    assert leaves <= both["step_time_s"] * 1e3 + 1e-6
    assert both["prefill_ms"] >= sum(both.get(f"prefill_{x}_ms", 0.0)
                                     for x in LEAVES) - 1e-6
    decode_only = next(r for r in recs if not r.get("n_live"))
    assert "prefill_plan_ms" not in decode_only
    assert decode_only["prefill_ms"] == 0.0
    # compiles are ring events, and an iteration that compiled is fed to
    # neither window of the spike detector
    compiled = sum(1 for r in eng.recorder.ring
                   if r.get("event") == "compile")
    assert compiled == len(eng._compiled)
    fed = sum(len(w.times) for w in eng.recorder._windows.values())
    assert eng.iteration - compiled <= fed < eng.iteration
    assert set(eng.recorder._windows) <= {"chunk", "decode"}
