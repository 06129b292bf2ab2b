"""``correct`` comes out false when it should: the control (the reference
in the precision below the configuration's, in the program's place) and a
run with the timed path broken underneath, once for each fault a cell can
have. Tiny sizes on the CPU; the limits here are read at that size (the
cells' own are read on the chip, PERF.md section 2)."""
import argparse
import json

import pytest

from chipbench import peaks, spec

# sound runs at this size read: losses under 3e-5, gradient gap 0.0022,
# change gap 0.0033; the fp8 control reads a gradient gap of 0.0099-0.012
TRAIN_LIMITS = {"loss1_rel": 1e-4, "loss2_rel": 1e-4, "loss3_rel": 1e-4,
                "grad_norm_gap": 0.005, "change_norm_gap": 0.02}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny(cell_name, **traffic):
    cell = spec.load_cell(cell_name)
    cell.model = spec.rehearsal_model(cell.model)
    cell.traffic = dict(cell.traffic, **cell.traffic["rehearse"])
    cell.traffic.update(traffic)
    return cell


def args(seed, seconds):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              rehearse=False,
                              peak=peaks.PEAKS["TPU v5 lite"])


def run_line(cell, seed=11, seconds=1.0):
    import importlib
    from chipbench.harness import KINDS
    runner = importlib.import_module(KINDS[cell.traffic["kind"]])
    return json.loads(runner.run(cell, args(seed, seconds), 0.0,
                                 dict(DEVICE)))


def cells_of(*kinds):
    """The benchmark's cells whose traffic is of one of ``kinds``."""
    return [w["name"] for w in spec.benchmark()["workloads"]
            if spec.load_cell(w["name"]).traffic["kind"] in kinds]


# -- training --------------------------------------------------------------------

TRAIN_CELLS = cells_of("train")


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_train_sound_run_is_correct(name):
    line = run_line(tiny(name, limits=TRAIN_LIMITS))
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_fp8_is_not_correct(seed):
    from chipbench import train
    cell = tiny("yi9b.train.seq4k", limits=TRAIN_LIMITS)
    ref = train.reference_readings(cell.family, cell.model, cell.traffic,
                                   seed)
    ctl = train.reference_readings(cell.family, cell.model, cell.traffic,
                                   seed, mode="fp8")
    assert not train.compare(ctl, ref, TRAIN_LIMITS).correct
    assert train.compare(ref, ref, TRAIN_LIMITS).correct


def test_train_reference_with_a_planted_fault_is_not_correct():
    """The fault the chip readings plant in the reference: half of the
    batch left out."""
    from chipbench import train
    cell = tiny("yi9b.train.seq4k", limits=TRAIN_LIMITS)
    ref = train.reference_readings(cell.family, cell.model, cell.traffic, 4)
    bad = train.reference_readings(cell.family, cell.model, cell.traffic, 4,
                                   fault="half_batch")
    assert not train.compare(bad, ref, TRAIN_LIMITS).correct


def broken_build(real_build, fault):
    """A family's ``train_step`` whose step carries the fault."""
    def build(*a, **kw):
        step, params, opt = real_build(*a, **kw)
        if fault == "state_unchanged":
            def bad(p, o, ids, labels):
                import jax
                import jax.numpy as jnp
                copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
                _, _, loss = step(copy(p), copy(o), ids, labels)
                return p, o, loss
        elif fault == "half_batch":
            def bad(p, o, ids, labels):
                half = len(ids) // 2
                return step(p, o, ids[:half], labels[:half])
        bad.jitted = step.jitted
        return bad, params, opt
    return build


@pytest.mark.parametrize("name", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(name, fault, monkeypatch):
    cell = tiny(name, limits=TRAIN_LIMITS)
    monkeypatch.setattr(cell.family, "train_step",
                        broken_build(cell.family.train_step, fault))
    line = run_line(cell)
    assert line["correct"] is False
    failing = [k for k, v in line["compared"].items()
               if not v["value"] <= v["limit"]]
    assert failing, line["compared"]


# -- serving ---------------------------------------------------------------------

SERVE_CELLS = cells_of("serve_open")


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_serve_sound_run_is_correct(name):
    line = run_line(tiny(name, limits={"token_gap_std": 0.1}), seconds=2.0)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fp8_is_not_correct(seed):
    """The control: at every position of the served requests the token the
    fp8 reference puts first goes through ``token_gaps`` and the run's own
    comparison, and fails it; the program's own tokens pass. Over some 180
    served tokens at this size the program reads 0.008-0.016 and the
    control 0.09-0.24 (seeds 1-5), so the limit here is 0.05."""
    from chipbench import serve
    cell = tiny(SERVE_CELLS[0], limits={"token_gap_std": 0.05},
                check_requests=8, output_tokens={
                    "median": 24, "sigma": 0.3, "min": 16, "max": 32})
    rows = {r["reading"]: r for r in serve.calibrate(
        cell, args(seed, 2.0), seed, {"program", "control"})}
    assert rows["program"]["correct"] is True, rows["program"]
    ctl = rows["control:fp8"]
    assert ctl["correct"] is False
    assert ctl["token_gap_std"]["value"] > ctl["token_gap_std"]["limit"]
    assert ctl["tokens"] > 100


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_serve_altered_token_is_not_correct(name, monkeypatch):
    """The decode program's logits rolled by one: every decoded token is
    its neighbour."""
    import jax.numpy as jnp
    from paddle_tpu.inference import engine as E
    real = E.InferenceEngine._step_fn

    def step_fn(self, kind, frozen, quant=False):
        fn = real(self, kind, frozen, quant)
        if kind != "decode":
            return fn

        def rolled(*a):
            out = fn(*a)
            return (jnp.roll(out[0], 1, axis=-1),) + tuple(out[1:])
        return rolled
    monkeypatch.setattr(E.InferenceEngine, "_step_fn", step_fn)
    line = run_line(tiny(name, limits={"token_gap_std": 0.1}), seconds=2.0)
    assert line["correct"] is False
    assert line["compared"]["token_gap_std"]["value"] > 0.1


def test_serve_unanswered_request_is_not_correct(monkeypatch):
    """A request the engine drops without a word never answers."""
    from paddle_tpu.inference import engine as E
    real = E.InferenceEngine._finish_seq

    def finish(self, seq, t):
        if seq.req.request_id == 2:
            del seq.tokens[seq.n_prompt + 1:]     # its answer cut short
        return real(self, seq, t)
    monkeypatch.setattr(E.InferenceEngine, "_finish_seq", finish)
    line = run_line(tiny(SERVE_CELLS[0], limits={"token_gap_std": 0.1}),
                    seconds=2.0)
    assert line["correct"] is False
    assert line["compared"]["never_answered"]["value"] >= 1
