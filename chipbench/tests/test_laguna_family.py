"""The Laguna family's counts against numbers worked by hand, the
configuration file against the catalog's rules, the schedule, and the
cell's rehearsal and its controls through the harness's own entries."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import flops, peaks, spec
from chipbench.weights import is_leaf

PEAK = peaks.PEAKS["TPU v5 lite"]
CELL = "laguna.serve.repopatch"


def model():
    with open(os.path.join(spec.HERE, "configs", "laguna-s-2.1.json")) as f:
        m = json.load(f)
    return m, spec.family(m)


def test_layer_counts_by_hand():
    m, fam = model()
    # q and o 3072 x 6144 (9216 in a window layer), k and v 3072 x 1024,
    # the gate 3072 x 48 (72)
    assert fam.attn_params(m, fam.FULL) == 3072 * (12288 + 2048 + 48) \
        == 44187648
    assert fam.attn_params(m, fam.WINDOW) == 3072 * (18432 + 2048 + 72) \
        == 63135744
    assert fam.expert_params(m) == 3 * 3072 * 1024 == 9437184
    # 10 of 256 a token, 64 held: 2.5 held experts a token
    assert fam.held_experts_per_token(m) == 2.5
    assert fam.ffn_params(m, False) == 786432 + 9437184 + 2.5 * 9437184
    assert fam.layer_params(m) == (2 * 44187648 + 3 * 63135744 + 113246208
                                   + 4 * 33816576) == 526295040
    assert fam.head_params(m) == 3072 * 25088


def test_forward_flops_by_hand():
    m, fam = model()
    # two decode rows at 20,000 and 600: full layers attend all, window
    # layers 512 each
    want = (2.0 * 526295040 * 2 + 2 * 4 * 48 * 128 * 20600.0
            + 3 * 4 * 72 * 128 * 1024.0 + 2.0 * 3072 * 25088 * 2)
    assert fam.step_flops(m, [20000, 600], 2) == want
    # a chunk of 512 after 20,000: the start follows from the sum
    n, start = 512, 20000
    assert fam.forward_flops(m, n, n * start + n * (n + 1) // 2, 1) \
        == fam.step_flops(m, fam.reach(start, n), 1)


def test_kernel_calls_by_hand():
    m, fam = model()
    # a decode batch of two rows, each past the window: 512 keys a row, k
    # and v of 1024 bf16 each, the new column written, the queries in
    # (bf16) and out (f32)
    fl, by = fam.window_attend_call(m, [20000, 600], False)
    assert fl == 4 * 72 * 128 * 1024
    assert by == 4096 * 1024 + 2 * (72 * 128 * 6 + 4096) == 4313088
    # a chunk of 512 after 20,000: 512 keys a query; the 1023 columns from
    # 19,489 read once; queries in and out, bf16
    fl, by = fam.window_attend_call(m, fam.reach(20000, 512), True)
    assert fl == 4 * 72 * 128 * 512 * 512
    assert by == 4096 * 1023 + 512 * 72 * 128 * 4 == 23064576
    fl, by = fam.full_attend_call(m, fam.reach(20000, 512), True)
    assert fl == 4 * 48 * 128 * (512 * 20000 + 512 * 513 // 2)
    assert by == 4096 * 20512 + 512 * 48 * 128 * 4 == 96600064
    # the chunk's full attention is bound by its FLOPs: 1.29 ms a layer
    assert flops.min_seconds(fl, by, PEAK) == pytest.approx(fl / 197e12)
    its = [{"decode_ctx": [20000, 600], "prefill": None},
           {"decode_ctx": [], "prefill": (20000, 512, 1)}]
    k = fam.serve_kernels(m, {"block_size": 128}, its, PEAK)
    assert k["window_attend"]["least_s"] == pytest.approx(3 * (
        flops.min_seconds(*fam.window_attend_call(m, [20000, 600], False),
                          PEAK)
        + flops.min_seconds(*fam.window_attend_call(
            m, fam.reach(20000, 512), True), PEAK)))
    assert k["laguna_required_flops"] == pytest.approx(
        fam.step_flops(m, [20000, 600], 2)
        + fam.step_flops(m, fam.reach(20000, 512), 1))
    assert fam.serve_kernels(m, {}, [], PEAK) == {
        "window_attend": None, "full_attend": None,
        "laguna_required_flops": 0.0}
    # the experts: gate, up, down a pair; each expert's weights once a hit
    assert fam.moe_experts_call(m, 1000, 64) == (2.0 * 9437184 * 1000,
                                                 2.0 * 9437184 * 64)


def test_the_cut_weighs_what_the_file_says():
    m, fam = model()
    tree = fam.leaves(m)
    n = sum(int(np.prod(leaf.shape)) for leaf in
            jax.tree_util.tree_leaves(tree, is_leaf=is_leaf))
    assert 2.99e9 < n < 3.01e9                      # 3.00B = 6.00 GB
    assert tree["full"]["q_proj"].shape == (2, 3072, 6144)
    assert tree["window"]["q_proj"].shape == (3, 3072, 9216)
    assert tree["window"]["g_proj"].shape == (3, 3072, 72)
    assert tree["moe"]["experts"]["gate"].shape == (4, 64, 3072, 1024)
    assert tree["dense"]["gate_proj"].shape == (1, 3072, 12288)
    e = spec.load_cell(CELL).traffic["engine"]
    kv = e["num_blocks"] * e["block_size"] * 2 * 2 * 1024 * 2
    rings = (e["max_batch"] + 1) * 3 * 9 * e["block_size"] * 2 * 1024 * 2
    assert kv == 8053063680 and rings == 693633024      # 7.5 + 0.65 GiB
    assert 14.7e9 < 2 * n + kv + rings < 14.8e9         # 13.74 GiB of 15.75


def test_configuration_states_its_cut():
    """Each key in ``reduced`` has its published value beside it and
    differs from it; no width among them; the floors of a cut: a whole
    period of the pattern and four layers after the dense one, 8 experts or
    more, an eighth of the vocabulary or more; ``BENCHMARK.json`` names the
    same keys."""
    m, _ = model()
    assert m["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                            "layer_types", "mlp_layer_types", "gating_types",
                            "num_attention_heads_per_layer"]
    pub = m["published"]
    for k in m["reduced"]:
        assert k in pub and pub[k] != m[k], k
        assert not k.endswith(("_dim", "_rank", "_size")) or k == \
            "vocab_size", k
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 256, 100352)
    assert m["num_hidden_layers"] == 5
    for k in ("layer_types", "mlp_layer_types", "gating_types",
              "num_attention_heads_per_layer"):
        assert len(m[k]) == 5, k
    # published layers 0-4: the dense full layer, then one period
    assert m["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert m["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert m["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert m["num_hidden_layers"] - len(m["mlp_only_layers"]) >= 4
    assert m["num_experts"] >= 8 and 8 * m["vocab_size"] >= pub["vocab_size"]
    # every width as published
    assert (m["hidden_size"], m["head_dim"], m["num_key_value_heads"],
            m["intermediate_size"], m["moe_intermediate_size"],
            m["shared_expert_intermediate_size"], m["num_experts_per_tok"],
            m["sliding_window"]) == (3072, 128, 8, 12288, 1024, 1024, 10, 512)
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-s-2.1")
    assert entry["reduced"] == m["reduced"]


def test_the_schedule_was_drawn_by_its_count_alone():
    """``shape_seed`` is the first seed, counting up from 20261201, whose
    Poisson stream puts within a tenth of rate x 45 requests into a 45 s
    window at every rate a sweep may try, 0.4 to 2 req/s: a criterion on the
    arrivals alone."""
    from chipbench import traffic
    t = spec.load_cell(CELL).traffic
    rates = (0.4, 0.5, 0.6, 0.8, 1.0, 1.2, 1.5, 2.0)

    def fits(seed):
        return all(
            abs(len(traffic.arrivals(dict(t, shape_seed=seed, rate_per_s=r),
                                     45.0)) - 45 * r) <= 0.1 * 45 * r
            for r in rates)
    assert t["shape_seed"] == next(s for s in range(20261201, 20262201)
                                   if fits(s))
    sizes = traffic.sizes(t, 32)
    prompts = [p for p, _ in sizes]
    # every prompt at least 8 windows long, a repository's worth of files
    assert min(prompts) >= 8 * 512 and 14000 < np.median(prompts) < 17000
    e = t["engine"]
    assert e["max_seq_len"] == t["prompt_tokens"]["max"] \
        + t["output_tokens"]["max"] == 52224


@pytest.mark.parametrize("max_batch", [1, 2, 4, 48, 64])
def test_the_warm_up_reaches_every_decode_bucket(max_batch):
    """``Driver.warm`` fills the batch one row a step to one more than half
    of ``max_batch``: each bucket of the family's ladder is then the first
    to hold some count of rows it reaches."""
    _, fam = model()
    ladder = fam.decode_buckets(max_batch)
    assert ladder[-1] == max_batch and list(ladder) == sorted(set(ladder))
    reached = {next(b for b in ladder if b >= n)
               for n in range(1, max_batch // 2 + 2) if n <= max_batch}
    assert reached == set(ladder)
    if max_batch == 48:
        assert ladder == (1, 2, 4, 8, 16, 24, 48)
    if max_batch == 64:             # the engine's own ladder
        from paddle_tpu.inference import ServeConfig
        assert ladder == ServeConfig(max_batch=64).decode_buckets


def test_train_cells_are_refused():
    m, fam = model()
    with pytest.raises(SystemExit, match="no training path"):
        fam.train_step(m, {})


def test_the_reference_knows_its_controls():
    _, fam = model()
    assert fam._precision("f32") == ("f32", "")
    assert fam._precision("f32:nowindow") == ("f32", "nowindow")
    assert fam._precision("fp8:nogate") == ("fp8", "nogate")
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
    assert "mfu.repopatch" in line["rehearsal_values"]


def test_the_variant_controls_rehearse():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    run = subprocess.run(
        [sys.executable, "chipbench/variant_controls.py", "--workload", CELL,
         "--rehearse", "--seeds", "5", "--variants", "nowindow,nogate",
         "--prompts", "90,150", "--outputs", "5"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    rows = [json.loads(l) for l in run.stdout.strip().splitlines()
            if l.startswith("{")]
    assert [r["reading"] for r in rows] == ["program", "control:nowindow",
                                            "control:nogate"]
    assert all(r["tokens"] == 10 for r in rows)
    assert rows[0]["correct"]
