"""Where each request's time before its first token goes, inside the engine.

``InferenceEngine.first_wait`` keeps three request-seconds integrals, the
registry's ``request_queued_seconds_total``,
``request_prompt_wait_seconds_total`` and
``request_prompt_launched_seconds_total``: queued; admitted with no chunk of
its prompt launched and unread; a chunk of its prompt launched and unread.
Pinned here on a tiny Llama with Pallas in interpret mode: the three
partition the engine's time from ``submit()`` to each first token's commit
(also when speculating, and for requests that are quarantined or shed), the
queued part is the request tracer's queue spans, a second prompt's wait
behind the first one's chunks lands in the middle state and on the chunks'
``serve.prefill`` spans as ``prompts_waiting``, a scrape advances nothing,
and none of it changes a token.
"""
import glob
import os
import time

import numpy as np
import pytest

import jax

from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.inference.engine import (PROMPT_LAUNCHED, PROMPT_WAIT,
                                         QUEUED)
from paddle_tpu.models.llama import init_llama_params, llama_tiny
from paddle_tpu.ops import _common
from paddle_tpu.testing import faults

NAMES = ("request_queued_seconds_total", "request_prompt_wait_seconds_total",
         "request_prompt_launched_seconds_total")


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULTS", "1")
    with _common.interpret_mode(True):
        yield
    faults.disarm()


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                     seq=512)
    return cfg, init_llama_params(cfg, seed=3)


def engine(model, trace_requests=False, **serve_kw):
    cfg, params = model
    kw = dict(block_size=128, num_blocks=10, max_batch=2, prefill_chunk=64,
              max_seq_len=512)
    kw.update(serve_kw)
    return InferenceEngine(params, cfg, ServeConfig(**kw),
                           trace_requests=trace_requests)


def requests(sizes=(7, 130, 20), max_new_tokens=6, **kw):
    """7 tokens: one chunk; 130: three chunks; 20: arrives while the batch
    is full and waits in the queue."""
    rng = np.random.RandomState(0)
    return [Request(rng.randint(1, 96, size=n).tolist(),
                    max_new_tokens=max_new_tokens, arrival=float(i), **kw)
            for i, n in enumerate(sizes)]


def served(model, trace_requests=True, **serve_kw):
    eng = engine(model, trace_requests, **serve_kw)
    eng.run(requests(), deterministic=True)
    return eng


def tokens(eng):
    return {s.req.request_id: list(s.tokens) for s in eng.finished}


def split(eng):
    snap = eng.metrics_snapshot()
    return [snap[n] for n in NAMES]


def left_at(eng):
    """rid -> when the engine's tracer saw the request leave the three
    states: the read of its last prompt chunk (its first token), or its
    shed or quarantine."""
    out = {}
    for s in eng.tracer.spans:
        if s["cat"] == "prefill" or s["cat"] in ("shed", "quarantine"):
            out[s["rid"]] = s["t1"]
    return out


def submitted_at(eng):
    """rid -> the stamp of ``submit()``: where its first queue span (or,
    shed while queued, its shed span) begins."""
    out = {}
    for s in eng.tracer.spans:
        if s["name"] in ("queue", "shed") and s["rid"] not in out:
            out[s["rid"]] = s["t0"]
    return out


@pytest.fixture(scope="module")
def runs(model):
    with _common.interpret_mode(True):
        return {"plain": served(model),
                "spec": served(model, speculative=True, draft_k=3),
                "quiet": served(model, trace_requests=False)}


@pytest.mark.parametrize("config", ["plain", "spec"])
def test_the_states_partition_the_time_to_first_token(runs, config):
    """(a), and (g) for the synchronous speculative path: the three sum,
    to a nanosecond, to every request's submit stamp to the commit of its
    first token; nothing is left in any state once the engine is idle."""
    eng = runs[config]
    sub, left = submitted_at(eng), left_at(eng)
    assert sorted(sub) == sorted(left) == [0, 1, 2]
    got = split(eng)
    assert sum(got) == pytest.approx(sum(left[r] - sub[r] for r in sub),
                                     abs=1e-9)
    assert all(v > 0 for v in got)
    assert eng.first_wait.count == [0, 0, 0]
    assert all(s.ttft_state is None for s in eng.finished)


def test_queued_is_the_request_tracers_queue_spans(runs):
    """(b): submit and admission hand the tracer the stamps the split
    takes."""
    eng = runs["plain"]
    queues = {}
    for s in eng.tracer.spans:
        if s["name"] == "queue":
            queues.setdefault(s["rid"], s["t1"] - s["t0"])
    assert split(eng)[QUEUED] == pytest.approx(sum(queues.values()),
                                               abs=1e-9)


def profiled(fn, trace_dir):
    """``fn()`` under a profiler session, stopped in a ``finally``; returns
    (its result, the arguments of every ``serve.prefill`` event, in
    order)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
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
                if ev.name == "serve.prefill":
                    events.append((ev.start_ns, dict(ev.stats)))
    return result, [stats for _, stats in sorted(events, key=lambda e: e[0])]


def head_of_line(model, n_prompts, trace_dir):
    """``n_prompts`` three-chunk prompts submitted together; returns (the
    engine, the ``serve.prefill.launch`` stamps in order, the admission
    stamp, the ``serve.prefill`` spans' arguments)."""
    reqs = requests((130,) * n_prompts, max_new_tokens=8)
    for r in reqs:
        r.arrival = 0.0

    def run():
        eng = engine(model, trace_requests=True)
        eng.run(reqs, deterministic=True)
        return eng

    eng, chunks = profiled(run, trace_dir)
    spans = eng.tracer.spans
    launches = sorted(s["t0"] for s in spans
                      if s["name"] == "serve.prefill.launch")
    admitted = {s["t1"] for s in spans if s["name"] == "queue"}
    return eng, launches, admitted, chunks


def test_a_prompt_behind_another_prompts_chunks_waits(model, tmp_path):
    """(c): a lone three-chunk prompt launches its chunks back to back, so
    its prompt waits only from its admission to its first launch, less than
    any iteration's host time. Beside a second one, the second waits from
    the same admission to its own first launch, after the first prompt's
    three chunks, each of which says that one prompt waits behind it."""
    lone, launches, admitted, chunks = head_of_line(model, 1,
                                                    tmp_path / "lone")
    t_admit, = admitted
    wait = split(lone)[PROMPT_WAIT]
    assert wait == pytest.approx(launches[0] - t_admit, abs=1e-9)
    steps = [s["t1"] - s["t0"] for s in lone.tracer.spans
             if s["name"] == "serve.step"]
    assert wait < min(steps)
    assert [c["prompts_waiting"] for c in chunks] == [0, 0, 0]

    pair, launches, admitted, chunks = head_of_line(model, 2,
                                                    tmp_path / "pair")
    t_admit, = admitted                 # both in one admission
    first, second = launches[0], launches[3]
    wait = split(pair)[PROMPT_WAIT]
    assert wait == pytest.approx((first - t_admit) + (second - t_admit),
                                 abs=1e-9)
    assert wait - (first - t_admit) >= second - first > 0
    assert [(c["rid"], c["prompts_waiting"]) for c in chunks] \
        == [(0, 1)] * 3 + [(1, 0)] * 3
    assert pair.first_wait.count == [0, 0, 0]


def test_a_scrape_between_steps_advances_nothing(model):
    """(d): the integrals move at the engine's transitions alone; a scrape
    reads them as of the last one."""
    eng = engine(model)
    for r in requests((130, 20)):
        eng.submit(r)
    eng.step()
    assert eng.first_wait.count == [0, 1, 1]     # one chunk out, one waits
    before, prom = split(eng), eng.render_prometheus()
    time.sleep(0.02)
    assert split(eng) == before and eng.render_prometheus() == prom
    for name, value in zip(NAMES, before):
        assert f"paddle_tpu_serve_{name} {value!r}" in prom
    eng.step()
    after = split(eng)
    assert after[PROMPT_WAIT] >= before[PROMPT_WAIT] + 0.02
    assert after[PROMPT_LAUNCHED] >= before[PROMPT_LAUNCHED] + 0.02


def test_quarantined_and_shed_requests_leave_the_states(model):
    """(e): a prefill that raises and a request shed past its deadline
    leave every count at 0 once the engine is idle, and the three still
    sum to each request's submit stamp to the moment it left."""
    eng = engine(model, trace_requests=True)
    with faults.scope("serve.prefill.poison", "raise", nth=1):
        eng.run(requests((24, 24)), deterministic=True)
    assert len(eng.failed) == 1 and len(eng.finished) == 1
    sub, left = submitted_at(eng), left_at(eng)
    assert sum(split(eng)) == pytest.approx(
        sum(left[r] - sub[r] for r in sub), abs=1e-9)
    assert eng.first_wait.count == [0, 0, 0]

    eng = engine(model, trace_requests=True, num_blocks=3, max_batch=1,
                 prefill_chunk=32, max_seq_len=256, max_queue=8,
                 overcommit=8.0)
    eng.run(requests((30,) * 4, ttft_deadline=6.0), deterministic=True)
    assert eng.shed
    sub, left = submitted_at(eng), left_at(eng)
    assert sorted(sub) == sorted(left) == [0, 1, 2, 3]
    assert sum(split(eng)) == pytest.approx(
        sum(left[r] - sub[r] for r in sub), abs=1e-9)
    assert eng.first_wait.count == [0, 0, 0]


def test_a_demoted_prompt_is_queued_again(model):
    """(e): after a crash every prompt without a first token is back in the
    queue, and a later run takes it from there to its first token."""
    eng = engine(model)
    for r in requests((130, 20)):
        eng.submit(r)
    eng.step()
    assert eng.first_wait.count == [0, 1, 1]
    eng._demote_all()
    assert eng.first_wait.count == [2, 0, 0]
    assert [s.ttft_state for s in eng.waiting] == [QUEUED, QUEUED]
    eng.run([], deterministic=True)
    assert len(eng.finished) == 2 and eng.first_wait.count == [0, 0, 0]


def test_scrapes_change_no_token(model, runs):
    """(f): an engine scraped after every iteration serves the tokens of
    one never scraped, in as many iterations."""
    eng = engine(model)
    step = eng.step

    def scraped_step():
        done = step()
        eng.metrics_snapshot()
        eng.render_prometheus()
        return done

    eng.step = scraped_step
    eng.run(requests(), deterministic=True)
    quiet = runs["quiet"]
    assert tokens(eng) == tokens(quiet)
    assert eng.iteration == quiet.iteration
