"""Profiler tests: scheduler edge cases, single-fire on_trace_ready,
chrome-trace export paths/naming, summary time units, nested RecordEvent."""
import json
import os

import pytest

from paddle_tpu import profiler
from paddle_tpu.profiler import (Profiler, ProfilerState, RecordEvent,
                                 export_chrome_tracing, make_scheduler)


# -- make_scheduler edge cases ----------------------------------------------

def test_scheduler_basic_cycle():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=0)
    states = [sched(i) for i in range(8)]
    assert states[:4] == [ProfilerState.CLOSED, ProfilerState.READY,
                          ProfilerState.RECORD,
                          ProfilerState.RECORD_AND_RETURN]
    assert states[4:] == states[:4]  # repeat=0 cycles forever


def test_scheduler_skip_first():
    sched = make_scheduler(closed=0, ready=1, record=1, skip_first=3)
    assert [sched(i) for i in range(3)] == [ProfilerState.CLOSED] * 3
    assert sched(3) == ProfilerState.READY
    assert sched(4) == ProfilerState.RECORD_AND_RETURN


def test_scheduler_repeat_exhausts():
    sched = make_scheduler(closed=1, ready=0, record=1, repeat=2)
    assert sched(1) == ProfilerState.RECORD_AND_RETURN
    assert sched(3) == ProfilerState.RECORD_AND_RETURN
    # after `repeat` cycles the scheduler pins CLOSED
    assert all(sched(i) == ProfilerState.CLOSED for i in range(4, 10))


def test_scheduler_record_one_is_record_and_return():
    # a 1-step record window must close itself (RECORD_AND_RETURN), or the
    # window would never export
    sched = make_scheduler(closed=2, ready=1, record=1)
    assert sched(3) == ProfilerState.RECORD_AND_RETURN
    assert sched(2) == ProfilerState.READY


# -- single-fire on_trace_ready ---------------------------------------------

def _run(prof, n):
    prof.start()
    for _ in range(n):
        with RecordEvent("tick"):
            pass
        prof.step()
    prof.stop()


def test_on_trace_ready_fires_once_per_window():
    fired = []
    prof = Profiler(scheduler=make_scheduler(closed=1, ready=1, record=2,
                                             repeat=1),
                    on_trace_ready=lambda p: fired.append(p._step),
                    timer_only=True)
    _run(prof, 6)
    # window closes once at the RECORD_AND_RETURN->CLOSED edge (step 4);
    # stop() must NOT re-fire for the already-exported window
    assert fired == [4]


def test_stop_fires_pending_window_once():
    fired = []
    prof = Profiler(on_trace_ready=lambda p: fired.append(1),
                    timer_only=True)
    prof.start()
    with RecordEvent("w"):
        pass
    prof.stop()
    prof.stop()  # double stop: still exactly one export
    assert fired == [1]


def test_back_to_back_windows_fire_separately():
    fired = []
    prof = Profiler(scheduler=make_scheduler(closed=0, ready=1, record=1,
                                             repeat=2),
                    on_trace_ready=lambda p: fired.append(p._step),
                    timer_only=True)
    _run(prof, 4)
    assert len(fired) == 2


# -- export_chrome_tracing (satellite a) -------------------------------------

def test_export_chrome_tracing_writes_into_dir(tmp_path):
    out = str(tmp_path / "prof_out")
    prof = Profiler(scheduler=make_scheduler(closed=0, ready=1, record=1,
                                             repeat=1),
                    on_trace_ready=export_chrome_tracing(out, "workerA"),
                    timer_only=True)
    _run(prof, 2)
    files = os.listdir(out)
    assert len(files) == 1
    assert files[0].startswith("workerA_time_")
    assert files[0].endswith(".paddle_trace.json")
    data = json.load(open(os.path.join(out, files[0])))
    assert "traceEvents" in data


def test_export_chrome_tracing_default_worker_name(tmp_path):
    out = str(tmp_path / "prof_out2")
    prof = Profiler(on_trace_ready=export_chrome_tracing(out),
                    timer_only=True)
    prof.start()
    with RecordEvent("span"):
        pass
    prof.stop()
    (name,) = os.listdir(out)
    assert name.startswith("host_") and f"pid_{os.getpid()}" in name


# -- nested RecordEvent -> chrome trace (satellite d) -------------------------

def test_nested_record_events_chrome_json(tmp_path):
    prof = Profiler(timer_only=True)
    prof.start()
    with RecordEvent("outer"):
        with RecordEvent("inner"):
            pass
        with RecordEvent("inner"):
            pass
    # overlapping begin/end via explicit API
    a = RecordEvent("manual")
    a.begin()
    a.end()
    prof.stop()
    path = str(tmp_path / "trace.json")
    prof.export(path)
    events = json.load(open(path))["traceEvents"]
    names = [e["name"] for e in events]
    assert names.count("inner") >= 2
    assert "outer" in names and "manual" in names
    outer = next(e for e in events if e["name"] == "outer")
    inners = [e for e in events if e["name"] == "inner"]
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
    # nesting: both inner spans lie inside the outer span
    for i in inners:
        assert outer["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= outer["ts"] + outer["dur"] + 1e-3


# -- summary time units (satellite c) ----------------------------------------

def test_summary_time_units():
    prof = Profiler(timer_only=True)
    prof.start()
    with RecordEvent("unit_span"):
        sum(range(10000))
    prof.stop()
    s_ms = prof.summary(time_unit="ms")
    assert "Total(ms)" in s_ms and "unit_span" in s_ms

    def total(report):
        line = next(l for l in report.splitlines() if "unit_span" in l)
        return float(line.split()[-1])

    t_s = total(prof.summary(time_unit="s"))
    t_ms = total(prof.summary(time_unit="ms"))
    t_us = total(prof.summary(time_unit="us"))
    # report renders 3 decimals: a sub-ms span prints 0.000 in seconds, so
    # only ms<->us are exactly comparable; s must still parse and be smaller
    assert t_ms > 0 and t_s <= t_ms
    assert t_us == pytest.approx(t_ms * 1e3, abs=0.5)  # 3-decimal rounding
    with pytest.raises(ValueError):
        prof.summary(time_unit="fortnights")


def test_summary_includes_telemetry_section():
    from paddle_tpu import observability as obs
    m = obs.StepMetrics(name="sumtest", peak_flops=1e12)
    m.record_compile(compile_s=0.1, flops=1e6)
    m.step()
    m.step()
    obs.set_active(m)
    try:
        prof = Profiler(timer_only=True)
        prof.start()
        with RecordEvent("x"):
            pass
        prof.stop()
        assert "StepMetrics[sumtest]" in prof.summary()
    finally:
        obs.set_active(None)


# -- chrome trace-event schema (shared writer, PR-12) -------------------------

def _assert_chrome_schema(path):
    """Minimal Chrome trace-event-format contract: a JSON object with a
    ``traceEvents`` list whose events all carry name/ph/pid, duration
    events numeric ts/dur, and instants a valid scope."""
    data = json.load(open(path))
    assert isinstance(data, dict) and isinstance(data["traceEvents"], list)
    assert data["traceEvents"], "empty trace"
    for e in data["traceEvents"]:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ph"] in ("X", "i", "M", "B", "E")
        assert isinstance(e["pid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        elif e["ph"] == "i":
            assert e.get("s", "t") in ("t", "p", "g")
        elif e["ph"] == "M":
            assert e["name"] in ("process_name", "thread_name")
            assert isinstance(e["args"]["name"], str)
    return data


def test_profiler_export_matches_chrome_schema(tmp_path):
    prof = Profiler(timer_only=True)
    prof.start()
    with RecordEvent("alpha"):
        with RecordEvent("beta"):
            pass
    prof.stop()
    path = str(tmp_path / "prof_schema.json")
    prof.export(path)
    _assert_chrome_schema(path)


def test_request_tracer_export_matches_chrome_schema(tmp_path):
    # the tracer goes through the same write_chrome_trace writer as the
    # profiler, so both exports must satisfy the same schema
    from paddle_tpu.observability.request_trace import RequestTracer
    tr = RequestTracer()
    tr.submit(0, 0.0)
    tr.admit(0, 0.5)
    tr.prefill_chunk(0, 0.5, 0.8, n_tokens=32, recompute=False)
    tr.phase("serve.prefill", 0.5, 0.8, iteration=0)
    tr.phase("serve.prefill.wait", 0.6, 0.8, iteration=0,
             parent="serve.prefill")
    tr.decode([0], 1.0, 1.1, iteration=1)
    tr.evict(0, 1.2, n_preempted=1)
    tr.admit(0, 1.5, n_preempted=1)
    tr.prefill_chunk(0, 1.5, 1.9, n_tokens=33, recompute=True)
    tr.decode([0], 2.0, 2.1, iteration=4)
    tr.finish(0, 2.1, n_generated=2)
    path = tr.export_chrome(str(tmp_path / "req_schema.json"))
    data = _assert_chrome_schema(path)
    phs = {e["ph"] for e in data["traceEvents"]}
    assert {"M", "X", "i"} <= phs
    rows = {e["args"]["name"] for e in data["traceEvents"]
            if e["name"] == "thread_name"}
    assert "request 0" in rows and "engine/serve.prefill" in rows
    # a phase with no row of its own lies on its parent's
    from paddle_tpu.observability.request_trace import PHASE_TIDS
    tids = {e["name"]: e["tid"] for e in data["traceEvents"]
            if e["ph"] == "X" and e.get("cat") == "phase"}
    assert tids["serve.prefill.wait"] == tids["serve.prefill"] \
        == PHASE_TIDS["serve.prefill"]
