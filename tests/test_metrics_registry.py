"""Unified MetricsRegistry (PR 15): Prometheus text-format compliance
and the engine-migration byte-compat golden.

The compliance checker parses a full exposition and enforces the spec
rules that matter to a scraper: ``# HELP`` (when present) immediately
precedes its family's ``# TYPE``, every sample line belongs to the
family announced by the most recent ``# TYPE``, no family is announced
twice, histogram ``le`` bounds are strictly increasing with cumulative
(nondecreasing) counts ending in ``le="+Inf"`` equal to ``_count``, and
label values are escaped. It runs against BOTH live expositions — the
serving engine's and the FleetMonitor's — not just synthetic registries.

The golden test pins the engine migration: the non-comment lines of
``InferenceEngine.render_prometheus()`` must stay byte-identical to the
legacy dict renderer fed the same values in the pre-PR-15 key set.
"""
import collections
import math
import re

import numpy as np
import pytest

from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.models.llama import init_llama_params, llama_tiny
from paddle_tpu.observability import histogram as _hist
from paddle_tpu.observability.fleet import FleetMonitor
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.ops import _common

_SAMPLE_RE = re.compile(
    r'^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})? (\S+)$')
_LABEL_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _labels_dict(raw):
    return dict(_LABEL_RE.findall(raw or ""))


def _num(s):
    if s == "+Inf":
        return math.inf
    if s == "NaN":
        return math.nan
    return float(s)


def check_exposition(text):
    """Assert ``text`` is a spec-compliant Prometheus exposition; return
    ``{family: kind}``."""
    assert text.endswith("\n"), "exposition must end with a newline"
    kinds = {}
    samples = collections.defaultdict(list)
    family = kind = pending_help = None
    for line in text.splitlines():
        if line.startswith("# HELP "):
            assert pending_help is None, \
                f"HELP with no following TYPE before {line!r}"
            pending_help = line.split(" ", 3)[2]
        elif line.startswith("# TYPE "):
            _, _, name, k = line.split(" ", 3)
            assert name not in kinds, f"family {name} announced twice"
            assert k in ("counter", "gauge", "histogram"), k
            if pending_help is not None:
                assert pending_help == name, \
                    f"HELP for {pending_help} not followed by its TYPE"
                pending_help = None
            kinds[name] = k
            family, kind = name, k
        elif line.startswith("#"):
            raise AssertionError(f"unexpected comment line {line!r}")
        else:
            assert pending_help is None, \
                f"sample {line!r} between HELP and TYPE"
            m = _SAMPLE_RE.match(line)
            assert m, f"unparseable sample line {line!r}"
            name, raw_labels, value = m.groups()
            assert family is not None, f"sample {line!r} before any TYPE"
            if kind == "histogram":
                assert name in (family + "_bucket", family + "_sum",
                                family + "_count"), \
                    f"{name} outside histogram family {family}"
            else:
                assert name == family, \
                    f"{name} under TYPE block for {family}"
            samples[family].append((name, raw_labels or "", value))
    assert pending_help is None, "trailing HELP with no TYPE"
    for fam, k in kinds.items():
        if k != "histogram":
            continue
        # group bucket/sum/count lines by their non-``le`` label set so
        # a labeled family (one histogram child per label value) checks
        # out too
        series = collections.defaultdict(
            lambda: {"buckets": [], "sum": None, "count": None})
        for name, raw_labels, value in samples[fam]:
            labels = _labels_dict(raw_labels)
            key = tuple(sorted((k2, v) for k2, v in labels.items()
                               if k2 != "le"))
            if name.endswith("_bucket"):
                assert "le" in labels, f"bucket line without le in {fam}"
                series[key]["buckets"].append(
                    (_num(labels["le"]), float(value)))
            elif name.endswith("_sum"):
                series[key]["sum"] = float(value)
            else:
                series[key]["count"] = float(value)
        assert series, f"histogram family {fam} has no samples"
        for key, s in series.items():
            bounds = [b for b, _ in s["buckets"]]
            counts = [c for _, c in s["buckets"]]
            assert bounds, f"{fam}{key}: no buckets"
            assert all(a < b for a, b in zip(bounds, bounds[1:])), \
                f"{fam}{key}: le bounds not strictly increasing: {bounds}"
            assert all(a <= b for a, b in zip(counts, counts[1:])), \
                f"{fam}{key}: cumulative counts decrease: {counts}"
            assert bounds[-1] == math.inf, f"{fam}{key}: missing +Inf"
            assert s["count"] is not None and s["sum"] is not None, \
                f"{fam}{key}: missing _sum/_count"
            assert counts[-1] == s["count"], \
                f"{fam}{key}: +Inf bucket {counts[-1]} != _count {s['count']}"
    return kinds


# -- registry semantics ------------------------------------------------------

def test_counter_is_monotone():
    reg = MetricsRegistry(prefix="t")
    c = reg.counter("reqs")
    c.inc()
    c.inc(2.5)
    assert c.get() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_callback_gauge_rejects_set():
    reg = MetricsRegistry(prefix="t")
    g = reg.gauge("live", fn=lambda: 7)
    assert g.get() == 7
    with pytest.raises(ValueError):
        g.set(3)


def test_duplicate_registration_raises():
    reg = MetricsRegistry(prefix="t")
    reg.counter("x")
    with pytest.raises(ValueError, match="duplicate metric"):
        reg.counter("x")
    with pytest.raises(ValueError, match="already a counter"):
        reg.gauge("x")  # cross-kind shadowing is the dangerous one


def test_family_validates_labels():
    reg = MetricsRegistry(prefix="t")
    fam = reg.family("hop_ms", "gauge", labelnames=("site",))
    fam.labels(site="a").set(1)
    with pytest.raises(ValueError):
        fam.labels(wrong="a")
    with pytest.raises(ValueError):
        reg.family("bad_kind", "sparkline", labelnames=("x",))
    with pytest.raises(ValueError):
        reg.family("bad_label", "gauge", labelnames=("not-a-label",))


def test_snapshot_keeps_registration_order():
    reg = MetricsRegistry(prefix="t")
    reg.gauge("zeta").set(1)
    reg.counter("alpha").inc(4)
    reg.family("mid", "gauge", labelnames=("k",)).labels(k="a").set(9)
    snap = reg.snapshot()
    assert list(snap) == ["zeta", "alpha", "mid"]
    assert snap["alpha"] == 4
    assert snap["mid"] == {("a",): 9}


def test_none_gauge_emits_type_but_no_sample():
    reg = MetricsRegistry(prefix="t")
    reg.gauge("maybe", fn=lambda: None)
    text = reg.render_prometheus()
    assert "# TYPE t_maybe gauge" in text
    assert "\nt_maybe " not in text and not text.startswith("t_maybe ")
    check_exposition(text)


# -- text-format compliance --------------------------------------------------

def test_help_precedes_type_and_is_escaped():
    reg = MetricsRegistry(prefix="t")
    reg.counter("reqs", help="total\nrequests with a \\ backslash")
    reg.gauge("depth")  # no help: TYPE only
    text = reg.render_prometheus()
    lines = text.splitlines()
    i = lines.index("# TYPE t_reqs counter")
    assert lines[i - 1] == \
        "# HELP t_reqs total\\nrequests with a \\\\ backslash"
    assert "# HELP t_depth" not in text
    check_exposition(text)


def test_label_values_are_escaped():
    reg = MetricsRegistry(prefix="t")
    fam = reg.family("hop_ms", "gauge", labelnames=("site",))
    fam.labels(site='a\\b"c\nd').set(2)
    text = reg.render_prometheus()
    assert 't_hop_ms{site="a\\\\b\\"c\\nd"} 2.0' in text
    check_exposition(text)


def test_histogram_buckets_are_cumulative_and_monotone():
    reg = MetricsRegistry(prefix="t")
    s = reg.summary("lat_seconds", lo=1e-3, hi=1e2)
    # underflow (below lo), two mid-range decades, and overflow (>= hi)
    for v in (1e-5, 0.004, 0.004, 0.3, 7.0, 500.0):
        s.observe(v)
    fam = reg.family("hop_seconds", "histogram", labelnames=("site",))
    fam.labels(site="a").observe(0.01)
    fam.labels(site="b").observe(2.0)
    text = reg.render_prometheus()
    kinds = check_exposition(text)
    assert kinds["t_lat_seconds"] == "histogram"
    assert kinds["t_hop_seconds"] == "histogram"
    assert 't_lat_seconds_bucket{le="+Inf"} 6' in text
    assert 't_hop_seconds_bucket{site="a",le="+Inf"} 1' in text


def test_registry_histogram_lines_match_legacy_renderer():
    """The shared bucket assembler keeps the two surfaces byte-identical:
    same LogHistogram, same non-comment lines."""
    reg = MetricsRegistry(prefix="p")
    s = reg.summary("d_seconds")
    for v in (0.002, 0.1, 0.1, 3.0):
        s.observe(v)
    legacy = _hist.render_prometheus({"d_seconds": s.hist}, prefix="p")
    new = reg.render_prometheus()
    strip = lambda t: [l for l in t.splitlines() if not l.startswith("#")]
    assert strip(new) == strip(legacy)


# -- live expositions: engine (golden byte-compat) and fleet -----------------

@pytest.fixture(scope="module")
def served_engine():
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                     seq=512)
    params = init_llama_params(cfg, seed=3)
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=512)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (7, 40)]
    with _common.interpret_mode(True):
        eng = InferenceEngine(params, cfg, serve)
        eng.run([Request(p, max_new_tokens=4, arrival=float(i))
                 for i, p in enumerate(prompts)], deterministic=True)
    return eng


def _legacy_engine_dict(eng):
    """The pre-PR-15 ``metrics_snapshot()`` dict, rebuilt attribute by
    attribute in the old literal order."""
    from paddle_tpu.inference.engine import PREFILL, RUNNING
    return {
        "ttft_seconds": eng.slo["ttft"],
        "tpot_seconds": eng.slo["tpot"],
        "queue_wait_seconds": eng.slo["queue_wait"],
        "queue_depth": len(eng.waiting),
        "running": sum(1 for s in eng.active if s.state == RUNNING),
        "prefilling": sum(1 for s in eng.active if s.state == PREFILL),
        "batch_capacity": eng.serve.max_batch,
        "pool_utilization": eng.pool.utilization,
        "iterations": eng.iteration,
        "preemptions": eng.preemptions,
        "finished_requests": len(eng.finished),
        "rejected_requests": len(eng.rejected),
        "shed_requests": len(eng.shed),
        "failed_requests": len(eng.failed),
        "decode_redrives": eng._redrives,
        "generated_tokens": sum(len(s.generated) for s in eng.finished),
        # the useful-over-attempted counters of the phase spans
        "decode_rows_total": eng.work_totals["decode_rows_total"],
        "decode_slots_total": eng.work_totals["decode_slots_total"],
        "step_fetch_bytes_total": eng.work_totals["step_fetch_bytes_total"],
        "prefill_tokens_total": eng.work_totals["prefill_tokens_total"],
        "prefill_slots_total": eng.work_totals["prefill_slots_total"],
        "prefill_ctx_blocks_total":
            eng.work_totals["prefill_ctx_blocks_total"],
        "prefill_table_blocks_total":
            eng.work_totals["prefill_table_blocks_total"],
        "prefill_chunks_total": eng.work_totals["prefill_chunks_total"],
        "prefill_chunks_with_decode_total":
            eng.work_totals["prefill_chunks_with_decode_total"],
        "steps_launched_total": eng.work_totals["steps_launched_total"],
        "steps_launched_ahead_total":
            eng.work_totals["steps_launched_ahead_total"],
        "rows_discarded_total": eng.work_totals["rows_discarded_total"],
        # the time before first tokens, by state
        "request_queued_seconds_total": eng.first_wait.seconds[0],
        "request_prompt_wait_seconds_total": eng.first_wait.seconds[1],
        "request_prompt_launched_seconds_total": eng.first_wait.seconds[2],
        # the bytes the model relaid when it took the weights
        "weights_relaid_bytes": eng.relaid_bytes,
    }


def test_engine_exposition_matches_legacy_golden(served_engine):
    eng = served_engine
    legacy = _hist.render_prometheus(_legacy_engine_dict(eng),
                                     prefix="paddle_tpu_serve")
    new = eng.render_prometheus()
    strip = lambda t: [l for l in t.splitlines() if not l.startswith("#")]
    assert strip(new) == strip(legacy)
    # ... and the migrated exposition actually carries traffic
    assert "paddle_tpu_serve_ttft_seconds_count" in new
    assert eng.metrics_snapshot()["finished_requests"] == 2


def test_engine_exposition_is_compliant(served_engine):
    kinds = check_exposition(served_engine.render_prometheus())
    assert kinds["paddle_tpu_serve_ttft_seconds"] == "histogram"
    assert kinds["paddle_tpu_serve_queue_depth"] == "gauge"


def test_engine_registry_rejects_shadowing(served_engine):
    with pytest.raises(ValueError, match="duplicate metric"):
        served_engine.registry.gauge("iterations")


# -- replica merge (PR 20) ---------------------------------------------------

def test_merge_label_splits_replicas():
    regs = []
    for i in range(3):
        r = MetricsRegistry(prefix="p")
        r.gauge("depth", help="queue depth").set(i)
        s = r.summary("lat_seconds", help="latency")
        s.observe(0.01 * (i + 1))
        regs.append((str(i), r))
    text = MetricsRegistry.merge(regs, label="replica")
    kinds = check_exposition(text)
    assert kinds == {"p_depth": "gauge", "p_lat_seconds": "histogram"}
    assert 'p_depth{replica="0"} 0.0' in text
    assert 'p_depth{replica="2"} 2.0' in text
    assert 'p_lat_seconds_bucket{replica="1",le="+Inf"} 1' in text
    # one HELP/TYPE declaration per family, not one per replica
    assert text.count("# TYPE p_depth gauge") == 1
    assert text.count("# HELP p_depth queue depth") == 1


def test_merge_appends_replica_to_family_labels():
    a = MetricsRegistry(prefix="p")
    a.family("hop_ms", "gauge", labelnames=("site",)) \
        .labels(site="x").set(1)
    b = MetricsRegistry(prefix="p")
    b.family("hop_ms", "gauge", labelnames=("site",)) \
        .labels(site="x").set(2)
    text = MetricsRegistry.merge([("0", a), ("1", b)])
    check_exposition(text)
    assert 'p_hop_ms{replica="0",site="x"} 1.0' in text
    assert 'p_hop_ms{replica="1",site="x"} 2.0' in text


def test_merge_rejects_non_label_split_collisions():
    a = MetricsRegistry(prefix="p")
    a.gauge("x", help="h")
    b = MetricsRegistry(prefix="p")
    b.counter("x", help="h")
    with pytest.raises(ValueError, match="collides"):
        MetricsRegistry.merge([("0", a), ("1", b)])
    c = MetricsRegistry(prefix="p")
    c.gauge("x", help="a DIFFERENT help")
    with pytest.raises(ValueError, match="collides"):
        MetricsRegistry.merge([("0", a), ("1", c)])


def test_merge_rejects_duplicate_label_values_and_label_shadowing():
    a = MetricsRegistry(prefix="p")
    a.gauge("x").set(1)
    with pytest.raises(ValueError, match="duplicate replica"):
        MetricsRegistry.merge([("0", a), ("0", a)])
    d = MetricsRegistry(prefix="p")
    d.family("y", "gauge", labelnames=("replica",)) \
        .labels(replica="z").set(1)
    with pytest.raises(ValueError, match="already carries"):
        MetricsRegistry.merge([("0", d)])
    with pytest.raises(ValueError, match="invalid label"):
        MetricsRegistry.merge([("0", a)], label="not-a-label")


def test_engine_registries_merge_compliant(served_engine):
    """Two copies of a LIVE engine registry merge into one compliant
    scrape with every sample label-split by replica — the fleet
    exposition's building block."""
    eng = served_engine
    text = MetricsRegistry.merge([("0", eng.registry),
                                  ("1", eng.registry)])
    check_exposition(text)
    assert 'paddle_tpu_serve_finished_requests{replica="0"} 2.0' in text
    assert 'paddle_tpu_serve_finished_requests{replica="1"} 2.0' in text
    assert ('paddle_tpu_serve_ttft_seconds_bucket{replica="0",le='
            in text)


def test_fleet_exposition_is_compliant():
    mon = FleetMonitor(rank=0, world=1, interval=2, out_path=None)
    for t in (0.010, 0.012, 0.011, 0.013):
        mon.on_step(step_time_s=t)
    text = mon.registry.render_prometheus()
    kinds = check_exposition(text)
    assert kinds["paddle_tpu_fleet_local_step_time_seconds"] == "histogram"
    assert kinds["paddle_tpu_fleet_step_time_ms_worst"] == "gauge"
    assert "paddle_tpu_fleet_reports_total 2.0" in text
    with pytest.raises(ValueError, match="duplicate metric"):
        mon.registry.counter("reports_total")


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
