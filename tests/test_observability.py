"""Observability subsystem tests: StepMetrics, counters/comm_span, exporters,
MoE routing stats, and the TrainStep telemetry integration."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import observability as obs
from paddle_tpu.jit import TrainStep
from paddle_tpu.optimizer import AdamW


@pytest.fixture(autouse=True)
def _clean_counters():
    obs.reset_counters()
    yield
    obs.reset_counters()
    obs.set_active(None)


# -- counters + comm_span ----------------------------------------------------

def test_counters_roundtrip():
    obs.record_counter("x.calls")
    obs.record_counter("x.calls", 2)
    obs.set_counter("x.flag", 7)
    c = obs.counters()
    assert c["x.calls"] == 3
    assert c["x.flag"] == 7
    obs.reset_counters()
    assert obs.counters() == {}


def test_comm_span_counts_and_traces():
    def f(a):
        with obs.comm_span("t.span", nbytes=a.size * a.dtype.itemsize):
            return a * 2

    out = jax.jit(f)(jnp.ones((4, 4), jnp.float32))
    assert float(out[0, 0]) == 2.0
    c = obs.counters()
    assert c["t.span.calls"] >= 1
    assert c["t.span.bytes"] >= 64


def test_comm_span_value_passthrough():
    # the span must be transparent: same value, grads flow through
    def f(a):
        with obs.comm_span("t.g"):
            b = a * 3.0
        return b.sum()

    g = jax.grad(f)(jnp.ones((3,), jnp.float32))
    np.testing.assert_allclose(np.asarray(g), 3.0)


def test_telemetry_env_flag(monkeypatch):
    monkeypatch.delenv(obs.ENV_TELEMETRY, raising=False)
    assert not obs.telemetry_enabled()
    assert obs.telemetry_enabled(True)
    monkeypatch.setenv(obs.ENV_TELEMETRY, "1")
    assert obs.telemetry_enabled()
    assert not obs.telemetry_enabled(False)


# -- StepMetrics -------------------------------------------------------------

def test_step_metrics_records_and_summary(tmp_path):
    path = str(tmp_path / "steps.jsonl")
    m = obs.StepMetrics(name="t", n_devices=2, peak_flops=1e12)
    m.attach(obs.JsonlWriter(path, flush_every=1))
    m.record_compile(compile_s=0.5, trace_s=0.1, flops=4e9)
    for _ in range(3):
        m.step(tokens=128)
    m.close()

    assert m.compiles == 1 and m.recompiles == 0 and m.steps == 3
    recs = obs.load_jsonl(path)
    assert len(recs) == 3
    # first step after a compile has no interval -> no fake timing
    assert recs[0]["step_time_ms"] is None
    assert recs[1]["step_time_ms"] > 0
    assert recs[1]["tokens_per_sec"] > 0
    # mfu = flops / (t * peak_total); peak_total = 2 * 1e12
    t_s = recs[1]["step_time_ms"] / 1e3
    np.testing.assert_allclose(recs[1]["mfu"], 4e9 / (t_s * 2e12), rtol=1e-6)

    s = m.summary()
    assert s["steps"] == 3 and s["compile_time_s"] == 0.5
    assert s["step_time_ms_best"] <= s["step_time_ms_mean"]
    assert any("StepMetrics[t]" in ln for ln in m.summary_lines())


def test_step_metrics_recompile_resets_interval():
    m = obs.StepMetrics(name="t", peak_flops=1e12)
    m.record_compile(flops=1e6)
    m.step()
    m.record_compile(flops=2e6)      # recompile
    rec = m.step()
    assert m.recompiles == 1
    assert rec["step_time_ms"] is None  # interval clock restarted
    assert m.flops_per_step == 2e6


def test_peak_flops_table(monkeypatch):
    monkeypatch.setenv(obs.metrics.ENV_PEAK_FLOPS, "123.0")
    assert obs.peak_flops_per_device() == 123.0
    monkeypatch.delenv(obs.metrics.ENV_PEAK_FLOPS)

    class FakeDev:
        device_kind = "TPU v5p"
    assert obs.peak_flops_per_device(FakeDev()) == 459e12

    # no CPU row and no bare "v5" row: a device without a row has no
    # rate, and a measurement that needs one raises
    class Cpu:
        device_kind = "cpu"
    assert obs.peak_flops_per_device(Cpu()) is None

    class FutureV5:
        device_kind = "TPU v5x"
    obs.metrics._PEAK_WARNED.add("tpu v5x")   # the warning is not under test
    assert obs.peak_flops_per_device(FutureV5()) is None
    with pytest.raises(RuntimeError, match="unknown:cpu"):
        obs.metrics.require_peak_flops(Cpu())


# -- exporters ---------------------------------------------------------------

def test_jsonl_writer_buffers_and_flushes(tmp_path):
    path = str(tmp_path / "a.jsonl")
    w = obs.JsonlWriter(path, flush_every=100)
    w.write({"a": 1, "x": np.float32(2.5), "arr": np.arange(2)})
    w.flush()
    recs = obs.load_jsonl(path)
    assert recs == [{"a": 1, "x": 2.5, "arr": [0, 1]}]
    w.close()


def test_rank_logger_format(capsys):
    logger = obs.get_logger("paddle_tpu.test_obs")
    obs.log_event(logger, "hello", foo=1)
    err = capsys.readouterr().err
    assert "[rank 0]" in err
    payload = json.loads(err[err.index("{"):])
    assert payload["event"] == "hello" and payload["foo"] == 1


def test_tensorboard_writer_gated():
    if obs.TensorBoardWriter.available():
        pytest.skip("a tensorboard backend is installed")
    with pytest.raises(ImportError):
        obs.TensorBoardWriter("/tmp/tb")


# -- MoE routing stats -------------------------------------------------------

def test_moe_routing_stats_balanced_vs_skewed():
    from paddle_tpu.parallel import moe
    T, D, E, k = 64, 16, 4, 2
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    w1 = jnp.asarray(rng.randn(E, D, 32).astype(np.float32) * 0.02)
    w2 = jnp.asarray(rng.randn(E, 32, D).astype(np.float32) * 0.02)

    def expert_fn(params, t):
        a, b = params
        return jax.nn.gelu(t @ a) @ b

    def run(logits):
        return jax.jit(lambda xx, ll: moe.moe_dispatch_combine(
            xx, ll, expert_fn, (w1, w2), E, k=k, strict_capacity=True,
            return_stats=True))(x, logits)

    balanced = jnp.asarray(rng.randn(T, E).astype(np.float32))
    skewed = balanced + jnp.array([6.0, 0, 0, 0], jnp.float32)

    _, _, st_b = run(balanced)
    _, _, st_s = run(skewed)
    assert float(st_s["moe_dropped_tokens"]) > float(st_b["moe_dropped_tokens"])
    assert float(st_s["moe_load_imbalance"]) > float(st_b["moe_load_imbalance"])
    assert 0.0 < float(st_b["moe_capacity_util"]) <= 1.0
    # conservation: routed + dropped == T*k
    assert float(st_s["moe_routed_tokens"]) + \
        float(st_s["moe_dropped_tokens"]) == T * k

    # the one-hot gating path reports identical stats for the same routing
    _, _, st_oh = jax.jit(lambda xx, ll: moe.moe_dispatch_combine(
        xx, ll, expert_fn, (w1, w2), E, k=k, strict_capacity=True,
        use_onehot=True, return_stats=True))(x, skewed)
    for key in st_s:
        np.testing.assert_allclose(float(st_oh[key]), float(st_s[key]),
                                   rtol=1e-6, err_msg=key)


def test_moe_stats_do_not_change_loss():
    from paddle_tpu.models import ernie_moe
    cfg = ernie_moe.ernie_moe_tiny()
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int32)

    step0, p0, o0 = ernie_moe.build_train_step(cfg)
    step1, p1, o1 = ernie_moe.build_train_step(cfg, with_stats=True)
    _, _, loss0, lm0 = step0(p0, o0, ids, labels)
    _, _, loss1, aux1 = step1(p1, o1, ids, labels)
    assert float(loss0) == float(loss1)
    assert float(lm0) == float(aux1["lm_loss"])
    assert set(aux1) == {"lm_loss", "moe_dropped_tokens",
                         "moe_routed_tokens", "moe_load_imbalance",
                         "moe_capacity_util"}


# -- TrainStep integration ---------------------------------------------------

def _tiny_step(tmp_path, mesh=None, **kw):
    paddle.seed(3)
    model = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    opt = AdamW(learning_rate=1e-2, parameters=model.parameters())
    return TrainStep(model, lambda o, l: paddle.mean((o - l) ** 2), opt,
                     mesh=mesh, telemetry=True,
                     telemetry_dir=str(tmp_path), **kw)


def test_train_step_telemetry_jsonl(tmp_path, monkeypatch):
    # a CPU has no row in the peak table, so a rate is stated by hand here
    # for the MFU arithmetic; without one the records carry mfu None
    monkeypatch.setenv(obs.metrics.ENV_PEAK_FLOPS, "1e11")
    step = _tiny_step(tmp_path)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(rng.randn(4, 4).astype(np.float32))
    n_calls = 6
    for _ in range(n_calls):
        step(x, labels=y)
    m = step.telemetry
    assert m is not None
    # call 2 may legally recompile (donated outputs commit to a device and
    # change the jit cache key); telemetry must classify every compile as a
    # compile — never as a fake step sample — and settle into steady state
    assert 1 <= m.compiles <= 2
    assert m.recompiles == m.compiles - 1
    assert m.steps == n_calls - m.compiles >= 3
    assert m.flops_per_step and m.flops_per_step > 0
    m.close()
    recs = obs.load_jsonl(
        str(tmp_path / f"steps_rank{obs.process_rank():03d}.jsonl"))
    assert len(recs) == m.steps
    timed = [r for r in recs if r["step_time_ms"]]
    assert timed and all(r["mfu"] > 0 for r in timed)
    assert all(r["mfu_peak_source"] == "env" for r in timed)
    assert all(r["tokens"] == 4 for r in recs)


def test_train_step_bucket_counters(tmp_path):
    cpus = jax.devices("cpu")
    mesh = Mesh(np.array(cpus[:8]).reshape(8, 1), ("dp", "mp"))
    step = _tiny_step(tmp_path, mesh=mesh, batch_spec=P("dp"),
                      grad_sync="bucketed", grad_bucket_mb=0.0001)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 8).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
    step(x, labels=y)
    c = obs.counters()
    n = c["grad_sync.n_buckets"]
    assert n == len(step.grad_buckets) and n > 1
    plan_total = sum(c[f"grad_sync.bucket{i:02d}.plan_bytes"]
                     for i in range(int(n)))
    assert plan_total == c["grad_sync.total_bytes"] > 0
    # the traced spans tallied every bucket at least once
    assert c["grad_sync.bucket00.calls"] >= 1
    step.telemetry.close()


def test_telemetry_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.ENV_TELEMETRY, raising=False)
    paddle.seed(3)
    model = nn.Sequential(nn.Linear(8, 4))
    opt = AdamW(learning_rate=1e-2, parameters=model.parameters())
    step = TrainStep(model, lambda o, l: paddle.mean((o - l) ** 2), opt)
    assert step.telemetry is None


# -- LogHistogram streaming percentiles (PR-12) -------------------------------

_BUCKET = 10.0 ** (1.0 / 16.0)  # default bucket width factor


def _nearest_rank(xs, q):
    import math
    s = sorted(xs)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def _adversarial(dist):
    rng = np.random.RandomState(7)
    if dist == "lognormal":
        return np.exp(rng.randn(5000)).tolist()
    if dist == "bimodal":
        # two modes five decades apart: percentile walks must not smear
        # mass across the empty decades between them
        return (list(rng.uniform(8e-4, 1.2e-3, size=600))
                + list(rng.uniform(4e2, 6e2, size=400)))
    if dist == "heavy":
        return np.clip((rng.pareto(1.2, size=3000) + 1.0) * 0.01,
                       None, 9e3).tolist()
    assert dist == "constant"
    return [0.25] * 100


@pytest.mark.parametrize("dist", ["lognormal", "bimodal", "heavy",
                                  "constant"])
def test_histogram_percentiles_within_one_bucket(dist):
    xs = _adversarial(dist)
    h = obs.LogHistogram()
    for v in xs:
        h.record(float(v))
    for q in (50, 90, 99):
        exact = _nearest_rank(xs, q)
        est = h.percentile(q)
        assert exact / _BUCKET <= est <= exact * _BUCKET, (dist, q, exact,
                                                          est)


def test_histogram_out_of_range_reports_exact_extremes():
    h = obs.LogHistogram(lo=1e-2, hi=1e2)
    for v in (0.0, -3.0, 1e-5):          # all below lo (incl. non-positive)
        h.record(v)
    assert h.percentile(50) == -3.0      # underflow bucket -> exact min
    h2 = obs.LogHistogram(lo=1e-2, hi=1e2)
    h2.record(0.5)
    h2.record(5e6)                       # overflow
    assert h2.percentile(99) == 5e6      # overflow bucket -> exact max
    # p0 stays within one bucket of the exact floor (clamped to >= min)
    assert 0.5 <= h2.percentile(0) <= 0.5 * _BUCKET


def test_histogram_merge_matches_concat():
    rng = np.random.RandomState(3)
    a, b = rng.lognormal(size=200), rng.lognormal(size=300)
    ha, hb, hc = obs.LogHistogram(), obs.LogHistogram(), obs.LogHistogram()
    for v in a:
        ha.record(v)
    for v in b:
        hb.record(v)
    for v in list(a) + list(b):
        hc.record(v)
    ha.merge(hb)
    assert ha.counts == hc.counts
    assert ha.count == hc.count == 500
    assert ha.min == hc.min and ha.max == hc.max
    np.testing.assert_allclose(ha.sum, hc.sum)
    with pytest.raises(ValueError):
        ha.merge(obs.LogHistogram(bins_per_decade=8))


def test_histogram_empty_and_validation():
    h = obs.LogHistogram()
    assert h.percentile(50) is None
    assert h.snapshot()["mean"] is None
    h.record(1.0)
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        obs.LogHistogram(lo=0.0)
    with pytest.raises(ValueError):
        obs.LogHistogram(lo=2.0, hi=1.0)
    with pytest.raises(ValueError):
        obs.LogHistogram(bins_per_decade=0)


def test_render_prometheus_exposition():
    h = obs.LogHistogram()
    for v in (0.01, 0.02, 0.02, 1.5, 900.0):
        h.record(v)
    text = obs.render_prometheus(
        {"lat_seconds": h, "depth": 3, "skipped": None}, prefix="t")
    lines = text.splitlines()
    assert "# TYPE t_lat_seconds histogram" in lines
    assert "# TYPE t_depth gauge" in lines
    assert "t_depth 3.0" in lines
    assert not any("skipped" in ln for ln in lines)
    # cumulative bucket counts are nondecreasing and end at the total
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines
            if ln.startswith('t_lat_seconds_bucket')]
    assert cums == sorted(cums)
    assert cums[-1] == 5                      # the +Inf bucket
    assert 't_lat_seconds_bucket{le="+Inf"} 5' in lines
    assert "t_lat_seconds_count 5" in lines
    [s] = [ln for ln in lines if ln.startswith("t_lat_seconds_sum ")]
    np.testing.assert_allclose(float(s.split()[1]), h.sum)
    with pytest.raises(TypeError):
        obs.render_prometheus({"bad": "a string"})


def test_step_metrics_step_time_histogram():
    m = obs.StepMetrics(name="t", n_devices=1)
    for ms in (10.0, 11.0, 12.0, 100.0):
        m.step(step_time_s=ms / 1e3)
    s = m.summary()
    assert s["step_time_ms_p50"] == pytest.approx(
        _nearest_rank([10.0, 11.0, 12.0, 100.0], 50), rel=_BUCKET - 1.0)
    assert s["step_time_ms_p99"] == pytest.approx(100.0, rel=_BUCKET - 1.0)


# -- flight recorder (PR-12) --------------------------------------------------

def test_flight_recorder_ring_bound_and_dump_roundtrip(tmp_path):
    rec = obs.FlightRecorder(source="t", size=8, out_dir=str(tmp_path))
    for i in range(1, 21):
        rec.record({"iteration": i, "tokens": i * 2})
    assert len(rec.ring) == 8
    path = rec.dump("exception")
    assert path is not None and os.path.exists(path)
    payload = obs.load_dump(path)
    assert payload["source"] == "t" and payload["reason"] == "exception"
    assert payload["n_records"] == 8
    assert [r["iteration"] for r in payload["records"]] == list(range(13, 21))
    # one dump per reason unless forced
    assert rec.dump("exception") is None
    assert rec.dump("exception", force=True) is not None
    assert len(rec.dumped) == 2


def test_flight_recorder_spike_fires_and_dumps(tmp_path):
    from paddle_tpu.observability.flight_recorder import MIN_SPIKE_SAMPLES
    rec = obs.FlightRecorder(source="t", out_dir=str(tmp_path))
    for _ in range(MIN_SPIKE_SAMPLES + 4):
        assert rec.check_step_time(0.01) is None
    path = rec.check_step_time(0.5)
    assert path is not None
    assert obs.load_dump(path)["anomalies"][0]["kind"] == "step_time_spike"


def _chat_like(n, seed=0):
    """(kind, seconds) of a serving run: decode-only iterations of about
    17 ms, every fifth one with a prefill chunk at about 76 ms."""
    rng = np.random.RandomState(seed)
    return [("chunk", 0.076 + 0.002 * rng.rand()) if i % 5 == 4
            else ("decode", 0.017 + 0.001 * rng.rand()) for i in range(n)]


def test_flight_recorder_one_window_per_kind_of_step(tmp_path):
    """A bimodal series is two quiet series: a chunk iteration among
    decodes is no spike, a decode or a chunk 110 ms over its kind is."""
    series = _chat_like(200)
    one = obs.FlightRecorder(source="one")
    assert any(one.check_step_time(s) is not None or one.anomalies
               for _, s in series), "one window: the first chunk fires"
    rec = obs.FlightRecorder(source="t", out_dir=str(tmp_path))
    for i, (kind, s) in enumerate(series, 1):
        rec.record({"iteration": i, "step_time_s": s})
        assert rec.check_step_time(s, kind=kind) is None
    assert rec.anomalies == []
    rec.record({"iteration": 201, "step_time_s": 0.076})
    assert rec.check_step_time(0.076, kind="decode") is not None
    rec.record({"iteration": 202, "step_time_s": 0.131, "decode_wait_ms": 125})
    assert rec.check_step_time(0.131, kind="decode") is not None
    rec.record({"iteration": 203, "step_time_s": 0.190})
    assert rec.check_step_time(0.190, kind="chunk") is not None
    assert rec.check_step_time(0.078, kind="chunk") is None
    # quicker than its kind (a chunk with no row to decode) is no stall
    assert rec.check_step_time(0.063, kind="chunk") is None
    kinds = [(a["step_kind"], a["step_time_s"]) for a in rec.anomalies]
    assert kinds == [("decode", 0.076), ("decode", 0.131), ("chunk", 0.190)]
    # an anomaly carries the record of the step at fault
    assert [a["record"]["iteration"] for a in rec.anomalies] \
        == [201, 202, 203]
    assert rec.anomalies[1]["record"]["decode_wait_ms"] == 125
    assert rec.anomalies[1]["median_s"] == pytest.approx(0.0175, abs=1e-3)
    assert rec.anomalies[2]["median_s"] == pytest.approx(0.077, abs=2e-3)


def test_flight_recorder_spike_file_holds_the_first_few(tmp_path):
    """The spike's file is written anew for each of the first
    ``SPIKE_DUMPS`` spikes and then left alone."""
    from paddle_tpu.observability.flight_recorder import SPIKE_DUMPS
    rec = obs.FlightRecorder(source="t", out_dir=str(tmp_path))
    for kind, s in _chat_like(100):
        rec.check_step_time(s, kind=kind)
    paths = [rec.check_step_time(0.131, kind="decode")
             for _ in range(SPIKE_DUMPS + 3)]
    assert all(paths[:SPIKE_DUMPS]) and not any(paths[SPIKE_DUMPS:])
    assert len(set(paths[:SPIKE_DUMPS])) == 1
    assert len(obs.load_dump(paths[0])["anomalies"]) == SPIKE_DUMPS
    assert len(rec.anomalies) == SPIKE_DUMPS + 3


def test_flight_recorder_eviction_storm(tmp_path):
    rec = obs.FlightRecorder(source="t", out_dir=str(tmp_path))
    paths = [rec.note_eviction(i) for i in range(1, 41)]
    fired = [p for p in paths if p]
    assert len(fired) == 1                    # once, not once per iteration
    assert obs.load_dump(fired[0])["anomalies"][0]["kind"] == "eviction_storm"


def test_flight_recorder_dump_without_dir_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY_DIR", raising=False)
    rec = obs.FlightRecorder(source="t")
    rec.record({"iteration": 1})
    assert rec.dump("exception") is None
    assert rec.dumped == []


def test_flight_recorder_env_gate(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_FLIGHT_RECORDER", raising=False)
    assert not obs.flight_recorder_enabled()
    assert obs.flight_recorder_enabled(True)
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_RECORDER", "1")
    assert obs.flight_recorder_enabled()
    assert not obs.flight_recorder_enabled(False)
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_RECORDER_SIZE", "4")
    assert obs.FlightRecorder(source="t").size == 4
