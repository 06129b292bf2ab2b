"""The readers of the program's ``serve.*`` spans, on hand-built events."""
import json
import os

import pytest

from chipbench import readers, readers_spans, spec, trace
from chipbench.readers import Facts
from chipbench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
ENGINE, OTHER = "python3", "other-thread"


def op(start, end):
    return Event(DEV, trace.OPS_LINE, "%fusion.1 = x", start, end - start)


def sp(name, start, end, line=ENGINE):
    return Event(HOST, line, name, start, end - start)


def facts(events, traced_s=10.0):
    return Facts({}, {}, 1, {}, events, traced_s, {})


def metric(name):
    with open(os.path.join(spec.HERE, "metrics", name + ".json")) as f:
        return json.load(f)


IDLE = ("idle_in_wait.serve", "idle_in_launch.serve", "idle_in_host.serve",
        "idle_outside_step.serve")


def one_iteration(t, chunk=False):
    """An engine iteration from ``t``: its spans tile the step, the device
    runs from the middle of the launch to the middle of the wait."""
    out = [sp("serve.step", t, t + 1.0), sp("serve.admit", t, t + 0.1)]
    kind = "prefill" if chunk else "decode"
    out += [sp(f"serve.{kind}", t + 0.1, t + 0.9),
            sp(f"serve.{kind}.plan", t + 0.1, t + 0.2),
            sp(f"serve.{kind}.launch", t + 0.2, t + 0.4),
            sp(f"serve.{kind}.wait", t + 0.4, t + 0.7),
            sp(f"serve.{kind}.commit", t + 0.7, t + 0.9),
            sp("serve.report", t + 0.9, t + 1.0),
            op(t + 0.3, t + 0.6)]
    return out


def test_idle_shares_by_phase_and_their_sum():
    # three iterations with half a second outside the engine between them;
    # the device idles from 0.6 of one to 0.3 of the next
    evs = [op(0.0, 0.1)]
    for t, chunk in ((0.5, False), (2.0, True), (3.5, False)):
        evs += one_iteration(t, chunk)
    f = facts(evs)
    got = {n: readers.read(metric(n), f) for n in IDLE}
    # per iteration: 0.1 s of the wait, 0.1 s of the launch, 0.1 + 0.1 +
    # 0.2 + 0.1 s of admit, plan, commit and report; less the last
    # iteration's wait, commit and report, since no gap follows the
    # device's last operation
    assert got["idle_in_wait.serve"] == pytest.approx(100 * 0.2 / 10)
    assert got["idle_in_launch.serve"] == pytest.approx(100 * 0.3 / 10)
    assert got["idle_in_host.serve"] == pytest.approx(100 * 1.2 / 10)
    assert got["idle_outside_step.serve"] == pytest.approx(100 * 1.4 / 10)
    gaps = sum(e - s for s, e in trace.idle_gaps(evs))
    assert sum(got.values()) == pytest.approx(100 * gaps / 10)


def test_a_gap_that_straddles_two_spans_is_split_between_them():
    evs = [op(0.0, 1.0), op(2.0, 3.0),
           sp("serve.step", 0.5, 2.5),
           sp("serve.decode.wait", 0.5, 1.4),
           sp("serve.decode.commit", 1.4, 2.5)]
    f = facts(evs, traced_s=4.0)
    assert readers_spans.idle_under_pct(f, match=r"\.wait$") \
        == pytest.approx(100 * 0.4 / 4)
    assert readers_spans.idle_under_pct(f, match=r"\.commit$") \
        == pytest.approx(100 * 0.6 / 4)
    assert readers_spans.idle_under_pct(f, outside=r"^serve\.step$") == 0.0


def test_a_span_on_a_second_thread_counts_once_and_is_no_child():
    evs = [op(0.0, 1.0), op(2.0, 3.0),
           sp("serve.step", 0.9, 2.1), sp("serve.decode.wait", 1.0, 1.5),
           sp("serve.decode.wait", 1.2, 1.8, line=OTHER)]
    f = facts(evs, traced_s=4.0)
    # the union of the two waits covers 1.0-1.8 of the gap 1.0-2.0
    assert readers_spans.idle_under_pct(f, match=r"\.wait$") \
        == pytest.approx(100 * 0.8 / 4)
    # self time takes off only the wait on the step's own thread
    assert readers_spans.span_ms(f, r"^serve\.step$", 50, minus=r"\.wait$") \
        == pytest.approx(1e3 * (1.2 - 0.5))


def test_span_ms_percentile_and_self_time():
    evs = []
    for i, wait in enumerate((0.2, 0.3, 0.6)):
        t = 2.0 * i
        evs += [sp("serve.step", t, t + 1.0),
                sp("serve.prefill.wait", t + 0.1, t + 0.1 + wait),
                sp("serve.decode.wait", t + 0.8, t + 0.9)]
    f = facts(evs)
    assert readers_spans.span_ms(f, r"^serve\.step$", 50) \
        == pytest.approx(1000.0)
    assert readers.read(metric("step_host_ms.serve"), f) \
        == pytest.approx(1e3 * (1.0 - 0.3 - 0.1))
    assert readers_spans.span_ms(f, r"^serve\.prefill\.wait$", 100) \
        == pytest.approx(600.0)


@pytest.mark.parametrize("name", sorted(
    n[:-5] for n in os.listdir(os.path.join(spec.HERE, "metrics"))
    if json.load(open(os.path.join(spec.HERE, "metrics", n)))["reader"]
    in ("span_ms", "idle_under_pct")))
def test_a_program_without_the_spans_gives_nothing(name):
    """The parent commit emits no ``serve.*`` span: every reader returns
    ``None``, raises nothing, and the harness leaves the metric out."""
    evs = [op(0.0, 1.0), op(2.0, 3.0),
           sp("chipbench.engine_step", 0.5, 2.5),
           # what its comm_span emitted, around launch and wait together
           sp("serve.prefill", 0.6, 1.2), sp("serve.decode", 1.3, 2.4)]
    assert readers.read(metric(name), facts(evs)) is None
    assert readers.read(metric(name), facts([])) is None
    assert readers.read(metric(name), facts(evs, traced_s=0.0)) is None


def test_the_cell_lists_the_nine_and_the_harness_reads_them():
    from chipbench import harness
    cell = spec.load_cell("mistral7b.serve.chat")
    names = [m["name"] for m in cell.per_layer
             if m["source"] == "program_span" and m["file"]["reader"]
             in ("span_ms", "idle_under_pct")]
    assert len(names) == 9
    evs = [op(0.0, 0.1)] + one_iteration(0.5) + one_iteration(2.0, True)
    out = harness.per_layer_metrics(cell, facts(evs))
    assert set(names) <= set(out)
    assert all(m["layer"] == "server host side" for m in cell.per_layer
               if m["name"] in names)
    train = spec.load_cell("yi9b.train.seq4k")
    assert not set(names) & {m["name"] for m in train.per_layer}


def test_a_new_reader_reads_a_spans_arguments_and_a_registry_counter(
        tmp_path):
    """What a later ``readers_<x>.py`` needs and no edit: a span keeps the
    arguments it was opened with (read here from a real trace), and the
    counters hold the change of the program's own registry over the traced
    part, under the registry's names."""
    import types

    import jax
    from paddle_tpu.observability.registry import MetricsRegistry
    from chipbench import serve
    jax.profiler.start_trace(str(tmp_path))
    for rows in (3, 5):
        with jax.profiler.TraceAnnotation("serve.decode", rows=rows,
                                          bucket=8):
            pass
    jax.profiler.stop_trace()
    events = trace.load(trace.find_xplane(str(tmp_path)))

    registry = MetricsRegistry(prefix="some_program")
    routed = registry.counter("routed_tokens_total")
    wait = registry.summary("queue_wait_seconds")
    engine = types.SimpleNamespace(registry=registry)
    routed.inc(10)
    wait.hist.record(0.5)
    before = serve.registry_numbers(engine)
    routed.inc(32)
    wait.hist.record(0.25)
    change = serve.registry_change(before, serve.registry_numbers(engine))
    assert change == {"routed_tokens_total": 32.0,
                      "queue_wait_seconds_count": 1,
                      "queue_wait_seconds_sum": 0.25}

    def fill_pct(f, match, per):
        spans = readers_spans.host_spans(f.events, match)
        rows = sum(e.stats["rows"] for e in spans)
        slots = sum(e.stats["bucket"] for e in spans)
        return 100.0 * rows / slots, f.counters[per] / rows

    f = Facts({}, {}, 1, {}, events, 1.0, change)
    assert fill_pct(f, r"^serve\.decode$", "routed_tokens_total") \
        == (50.0, 4.0)
