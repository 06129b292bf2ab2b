"""The Falcon-H1 family's counts against numbers worked by hand, the
configuration file against the catalog's rules, the weights' starts, and
the cell's rehearsal through the harness's own entry."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, peaks, spec, weights
from chipbench.weights import is_leaf

PEAK = peaks.PEAKS["TPU v5 lite"]
CELL = "falconh1.serve.longreply"


def model():
    with open(os.path.join(spec.HERE, "configs", "falcon-h1-34b.json")) as f:
        m = json.load(f)
    return m, spec.family(m)


def test_layer_counts_by_hand():
    m, fam = model()
    assert fam.conv_dim(m) == 4096 + 2 * 2 * 256 == 5120
    assert fam.state_elements(m) == 32 * 128 * 256 == 1048576
    # attention 5120x2560 twice and 5120x512 twice; the mixer 5120x9248 in
    # and 4096x5120 out; SwiGLU 3 x 5120x21504
    assert fam.layer_matmul_params(m) == (
        2 * 13107200 + 2 * 2621440 + 47349760 + 20971520 + 330301440) \
        == 430080000
    assert fam.head_params(m) == 5120 * 261120 == 1336934400
    # a token's recurrence: 5 a state element, and 4 taps x 2 on 5120 columns
    assert fam.recurrence_flops_per_token(m) == 5 * 1048576 + 40960 == 5283840


def test_forward_flops_by_hand():
    m, fam = model()
    # 64 decoded tokens attending 57,600 keys in all, 64 logit rows
    assert fam.forward_flops(m, 64, 57600, 64) == 6 * (
        (2.0 * 430080000 + 5283840) * 64 + 4.0 * 2560 * 57600) \
        + 2.0 * 1336934400 * 64


def test_kernel_calls_by_hand():
    m, fam = model()
    # the update for 64 live rows: each row's state in and out (2 x 4 MiB),
    # x and y in float32, 32 decays, B and C of 512 bf16 each
    fl, by = fam.ssm_update_call(m, 64)
    assert fl == 5.0 * 1048576 * 64
    assert by == 64 * (8388608 + 2 * 4096 * 4 + 128 + 2 * 512 * 2) == 539107328
    # byte-bound: 0.66 ms a layer at 819 GB/s, 3.9 ms over six
    assert flops.min_seconds(fl, by, PEAK) == by / 819e9
    assert 6 * by / 819e9 == pytest.approx(3.95e-3, rel=0.01)
    # the scan over 512 live tokens: C B^T 2 x 128 x 256 a group of 2; per
    # head of 32 128 x 128 + 2 x 128 x 256
    fl, by = fam.ssd_scan_call(m, 512)
    assert fl == 2.0 * 512 * (2 * 128 * 256 + 32 * (16384 + 65536)) \
        == 2751463424.0
    assert by == 512 * (4096 + 1024) * 2 + 512 * 32 * 8 + 512 * 4096 * 4 \
        + 2 * 1048576 * 4 == 22151168
    # the bytes bound it: 27 us against 14 us of bf16 FLOPs
    assert flops.min_seconds(fl, by, PEAK) == by / 819e9
    its = [{"decode_ctx": [900] * 64, "prefill": None},
           {"decode_ctx": [], "prefill": (512, 200, 0)}]
    k = fam.serve_kernels(m, {"block_size": 128}, its, PEAK)
    assert k["ssm_update"]["least_s"] == pytest.approx(6 * 539107328 / 819e9)
    assert k["ssd_scan"]["least_s"] == pytest.approx(
        6 * flops.min_seconds(*fam.ssd_scan_call(m, 200), PEAK))
    assert fam.serve_kernels(m, {}, [], PEAK) == {"ssm_update": None,
                                                   "ssd_scan": None}


def test_the_cut_weighs_what_the_file_says():
    m, fam = model()
    tree = fam.leaves(m)
    n = sum(int(np.prod(leaf.shape)) for leaf in
            jax.tree_util.tree_leaves(tree, is_leaf=is_leaf))
    assert 5.25e9 < n < 5.27e9                      # 5.26B = 10.5 GB
    lay = tree["layers"]
    assert lay["ssm_in_proj"].shape == (6, 5120, 9248)
    assert lay["ssm_conv_w"].shape == (6, 5120, 4)
    assert lay["q_proj"].shape == (6, 5120, 2560)
    assert lay["ssm_D"].start == lay["ssm_norm"].start == "one"
    assert lay["ssm_conv_b"].start == "zero"
    assert lay["ssm_A_log"].start == lay["ssm_dt_bias"].start == "normal"
    e = spec.load_cell(CELL).traffic["engine"]
    state = (e["max_batch"] + 1) * 6 * (1048576 * 4 + 3 * 5120 * 2)
    kv = e["num_blocks"] * e["block_size"] * 6 * 2 * 512 * 2
    assert state == 1647759360 and kv == 1207959552     # 1.65 + 1.21 GB
    assert 13.2e9 < 2 * n + state + kv < 13.5e9          # of 16 GB


def test_configuration_follows_the_catalog():
    """Every number of the catalog's config under the same key, but for the
    keys in ``reduced``; no width among them; the floors of a cut."""
    m, _ = model()
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("the catalog is not on this machine")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r.get("name") == "Falcon-H1-34B-Instruct")
    assert m["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in m["reduced"]:
            assert k in m["published"] and m[k] != v, k
        else:
            assert m[k] == v, k
    assert m["reduced"] == ["num_hidden_layers"]
    assert m["published"]["num_hidden_layers"] == 72
    assert m["num_hidden_layers"] >= 4          # a period of 1, four layers
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b")
    assert entry["reduced"] == m["reduced"]


def test_the_starts_are_the_published_ranges():
    """``starts`` maps the made leaves: ``A`` into [1, 16], ``delta``'s bias
    onto the inverse softplus of [0.001, 0.1], the convolution's weights to
    0.5 a tap over the input's deviation; every other leaf is shared as it
    was made."""
    m, fam = model()
    small = dict(fam.rehearsal(m), hidden_size=256, mamba_d_ssm=256,
                 mamba_n_heads=16, num_hidden_layers=8)
    w = weights.make_weights(fam.leaves(small), 11, dtype=jnp.float32)
    s = fam.starts(w, small)
    lay = s["layers"]
    a = np.exp(np.asarray(lay["ssm_A_log"]))
    assert 1.0 <= a.min() < 2.5 and 14.0 < a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(lay["ssm_dt_bias"], np.float64)))
    assert 1e-3 * 0.999 <= dt.min() < 2e-3 and 0.05 < dt.max() <= 0.1 * 1.001
    # log-uniform: the median of 128 draws near the geometric middle, 0.01
    assert 0.005 < np.median(dt) < 0.02
    std = fam.conv_input_std(small)
    assert std.shape == (fam.conv_dim(small),)
    # x, B, C: 0.02 x 16 x 0.25 x (0.25, 0.177, 0.5)
    assert std[0] == pytest.approx(0.02 * 16 * 0.25 * 0.25)
    assert std[-1] == pytest.approx(0.02 * 16 * 0.25 * 0.5)
    taps = np.asarray(lay["ssm_conv_w"]) * std[None, :, None]
    assert taps.std() == pytest.approx(0.5, rel=0.05)
    for name in ("ssm_in_proj", "q_proj", "ssm_D", "ssm_conv_b"):
        assert lay[name] is w["layers"][name]
    assert s["embed"] is w["embed"]
    assert float(np.asarray(lay["ssm_D"]).min()) == 1.0


def test_the_schedule_was_drawn_by_its_count_alone():
    """``shape_seed`` is the first seed, counting up from the mix's first
    draw (20261101), whose Poisson stream puts within a tenth of rate x 45
    requests into a 45 s window at every rate a sweep may try, 2 to 7 req/s:
    a criterion on the arrivals alone."""
    from chipbench import traffic
    t = spec.load_cell(CELL).traffic

    def fits(seed):
        return all(
            abs(len(traffic.arrivals(dict(t, shape_seed=seed, rate_per_s=r),
                                     45.0)) - 45 * r) <= 0.1 * 45 * r
            for r in (2, 3, 4, 5, 6, 7))
    assert t["shape_seed"] == next(s for s in range(20261101, 20262101)
                                   if fits(s))
    assert len(traffic.arrivals(t, 45.0)) == pytest.approx(
        45 * t["rate_per_s"], rel=0.1)
    # the lengths the cell's why gives: a cycle of 32, prompts that cross the
    # scan's pieces and the engine's chunks, replies longer than prompts
    sizes = traffic.sizes(t, 32)
    prompts = [p for p, _ in sizes]
    assert sum(p > 128 for p in prompts) >= 24
    assert sum(p > 512 for p in prompts) >= 6
    assert sum(o for _, o in sizes) > sum(prompts)
    e = t["engine"]
    assert e["max_seq_len"] == t["prompt_tokens"]["max"] \
        + t["output_tokens"]["max"]


def test_train_cells_are_refused():
    m, fam = model()
    with pytest.raises(SystemExit, match="no training path"):
        fam.train_step(m, {})


def test_the_reference_knows_its_controls():
    _, fam = model()
    assert fam._precision("f32") == ("f32", "")
    assert fam._precision("fp8") == ("fp8", "")
    assert fam._precision("f32:reset") == ("f32", "reset")
    with pytest.raises(ValueError):
        fam._precision("f32:nonsense")


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    run = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL,
         "--rehearse", "--seed", "2147483999", "--seconds", "3", "--trace",
         "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["token_gap_std"]["value"] \
        <= line["compared"]["token_gap_std"]["limit"]
    values = line["rehearsal_values"]
    assert {"state_bytes_per_token.longreply", "mfu.longreply",
            "engine_iter_p50_ms.serve", "chunks_with_decode.serve"} \
        <= set(values)
    # every decoded token moves a state row a layer (the rehearsal has two
    # layers; the 8.45 MB a row are the cell's widths), the chunks' on top
    assert values["state_bytes_per_token.longreply"]["value"] >= 2 * 8.45


def test_the_state_controls_rehearse():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    run = subprocess.run(
        [sys.executable, "chipbench/state_controls.py", "--workload", CELL,
         "--rehearse", "--seeds", "5", "--prompts", "90,150", "--outputs",
         "5"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    rows = [json.loads(l) for l in run.stdout.strip().splitlines()
            if l.startswith("{")]
    assert [r["reading"] for r in rows] == ["program", "control:reset",
                                            "control:norecur"]
    assert all(r["tokens"] == 10 for r in rows)
    assert rows[0]["correct"]
