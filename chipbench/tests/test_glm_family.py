"""The GLM-5.2 family's counts against numbers worked by hand, the
configuration file against the catalog's rules, and the cell's rehearsal
through the harness's own entry."""
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench import flops, peaks, spec
from chipbench.weights import is_leaf

PEAK = peaks.PEAKS["TPU v5 lite"]


def model():
    with open(os.path.join(spec.HERE, "configs", "glm-5.2.json")) as f:
        m = json.load(f)
    return m, spec.family(m)


def test_layer_counts_by_hand():
    m, fam = model()
    # W_qa 6144x2048, W_qb 2048x64x256, W_kva 6144x576, W_kvb 512x64x448,
    # W_o 16384x6144
    assert fam.attn_params(m) == (12582912 + 33554432 + 3538944 + 14680064
                                  + 100663296) == 165019648
    # W_Iq 2048x32x128, W_Ik 6144x128, W_Iw 6144x32
    assert fam.indexer_params(m) == 8388608 + 786432 + 196608 == 9371648
    assert fam.n_full(m) == 2
    assert fam.expert_params(m) == 3 * 6144 * 2048 == 37748736
    assert fam.held_experts_per_token(m) == 0.5        # 8 x 16 / 256
    dense = 165019648 + 3 * 6144 * 12288
    moe = 165019648 + 6144 * 256 + 1.5 * 37748736
    assert fam.matmul_params(m) == dense + 5 * moe + 6144 * 19360 \
        + 2 * 9371648
    assert fam.index_pair_flops(m) == 8192
    assert fam.attend_pair_flops(m) == 139264      # 64 x (576 + 512) x 2


def test_a_step_counts_what_is_selected_and_what_is_scored():
    m, fam = model()
    body = fam.matmul_params(m) - fam.head_params(m)
    # a decoded token that reaches 1000 positions attends them all; one that
    # reaches 5000 attends 2048; both indexers score all of either
    assert fam.step_flops(m, [1000, 5000], 2) == \
        2.0 * body * 2 + 2 * 8192 * 6000.0 + 6 * 139264 * 3048.0 \
        + 2.0 * fam.head_params(m) * 2
    # the harness's call for a chunk of 512 after 3072 cached gives the
    # start back
    pairs = 512 * 3072 + 512 * 513 // 2
    assert fam.forward_flops(m, 512, pairs, 0) == \
        fam.step_flops(m, fam.reach(3072, 512), 0)
    assert fam.reach(3072, 512)[0] == 3073 and fam.reach(3072, 512)[-1] == 3584


def test_kernel_calls_by_hand():
    m, fam = model()
    # a decode batch: every row its own keys (256 B a position in whole
    # blocks), 8192 FLOP a pair, a float32 score a pair out: memory-bound
    f, b = fam.dsa_index_call(m, [1000, 129], 128, False)
    assert f == 8192 * 1129.0
    assert b == (8 + 2) * 128 * 256 + 2 * 32 * (256 + 4) + 4 * 1129
    assert flops.min_seconds(f, b, PEAK) == b / 819e9
    # a chunk of 512 after 8192: one sequence's keys once: compute-bound
    r = fam.reach(8192, 512)
    f, b = fam.dsa_index_call(m, r, 128, True)
    assert f == 8192.0 * sum(r)
    assert b == 68 * 128 * 256 + 512 * 32 * 260 + 4.0 * sum(r)
    assert flops.min_seconds(f, b, PEAK) == f / 197e12
    # attention over the selection: 2048 pairs a row past 2048; a pair reads
    # 1152 B (121 FLOP a byte, under the ridge of 240: gather-bound) in a
    # decode batch; a chunk's rows share the sequence's latent blocks
    f, b = fam.dsa_attend_call(m, [1000, 5000], 128, False)
    assert f == 139264 * 3048.0
    assert b == 3048 * 1152 + 2 * 64 * (1152 + 2048)
    assert 139264 / 1152 == pytest.approx(120.9, abs=0.1)
    assert flops.min_seconds(f, b, PEAK) == b / 819e9
    f, b = fam.dsa_attend_call(m, r, 128, True)
    assert f == 139264 * 2048.0 * 512
    assert b == 68 * 128 * 1152 + 512 * 64 * (1152 + 2048)
    its = [{"decode_ctx": [1000, 5000], "prefill": (8192, 512, 0)},
           {"decode_ctx": [], "prefill": None}]
    k = fam.serve_kernels(m, {"block_size": 128}, its, PEAK)
    assert k["dsa_index"]["least_s"] == pytest.approx(2 * (
        flops.min_seconds(*fam.dsa_index_call(m, r, 128, True), PEAK)
        + flops.min_seconds(*fam.dsa_index_call(m, [1000, 5000], 128, False),
                            PEAK)))
    assert k["dsa_attend"]["least_s"] == pytest.approx(6 * (
        flops.min_seconds(*fam.dsa_attend_call(m, r, 128, True), PEAK)
        + flops.min_seconds(*fam.dsa_attend_call(m, [1000, 5000], 128, False),
                            PEAK)))
    assert k["glm_required_flops"] == fam.step_flops(m, r, 0) \
        + fam.step_flops(m, [1000, 5000], 2)
    none = fam.serve_kernels(m, {"block_size": 128}, its[1:], PEAK)
    assert none["dsa_index"] is None and none["dsa_attend"] is None


def test_the_cut_weighs_what_the_file_says():
    m, fam = model()
    tree = fam.leaves(m)
    n = sum(int(__import__("numpy").prod(leaf.shape)) for leaf in
            jax.tree_util.tree_leaves(tree, is_leaf=is_leaf))
    assert 4.68e9 < n < 4.70e9                      # 4.69B = 9.38 GB
    assert "indexer" in tree["dense"][0]
    assert tree["moe"]["indexer"]["wq_b"].shape == (1, 2048, 4096)
    assert tree["moe"]["indexer"]["k_norm"].start == "one"
    assert tree["moe"]["indexer"]["k_bias"].start == "normal"
    t = spec.load_cell("glm52.serve.longdoc").traffic["engine"]
    cache = t["num_blocks"] * t["block_size"] * (6 * 1152 + 2 * 256)
    assert cache == 3892314112                      # 3.89 GB


def test_configuration_follows_the_catalog():
    """Every number of the catalog's config under the same key, but for the
    keys in ``reduced``; no width among them; the floors of a cut."""
    m, _ = model()
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("the catalog is not on this machine")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f) if r.get("name") == "GLM-5.2")
    assert m["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in m["reduced"]:
            assert k in m["published"] and m[k] != v, k
        else:
            assert m[k] == v, k
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in m["reduced"])
    # the cut is published layers 2-7: their kinds as published
    assert m["indexer_types"] == row["config"]["indexer_types"][2:8]
    assert m["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:8]
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4
    assert m["n_routed_experts"] >= 8
    assert m["vocab_size"] * 8 >= m["published"]["vocab_size"]
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "glm-5.2")
    assert entry["reduced"] == m["reduced"]


def test_the_reference_compiles_at_a_requests_own_bucket():
    _, fam = model()
    got = [fam.own_padded(n, 33792) for n in (2100, 4096, 4097, 9000, 16384,
                                              16385, 33535)]
    assert got == [4096, 4096, 8192, 16384, 16384, 33792, 33792]
    assert fam.own_padded(300, 1024) == 1024       # the rehearsal's one shape


def test_the_schedule_was_drawn_by_its_count_alone():
    """``shape_seed`` is the first seed, counting up from the mix's first
    draw (20261003), whose Poisson stream puts within a tenth of rate x 45
    requests into a 45 s window at every rate a sweep may try, 0.3 to 1.0
    req/s: a criterion on the arrivals alone. (The first draw was thin by a
    quarter, the second, picked by a model of the engine, by a third: 15
    requests where 21.6 were due.)"""
    from chipbench import traffic
    t = spec.load_cell("glm52.serve.longdoc").traffic

    def fits(seed):
        return all(
            abs(len(traffic.arrivals(dict(t, shape_seed=seed, rate_per_s=r),
                                     45.0)) - 45 * r) <= 0.1 * 45 * r
            for r in (0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0))
    assert t["shape_seed"] == next(s for s in range(20261003, 20262003)
                                   if fits(s))
    assert len(traffic.arrivals(t, 45.0)) == pytest.approx(
        45 * t["rate_per_s"], rel=0.1)


def test_train_cells_are_refused():
    m, fam = model()
    with pytest.raises(SystemExit, match="no training path"):
        fam.train_step(m, {})


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    run = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "glm52.serve.longdoc", "--rehearse", "--seed", "2147483888",
         "--seconds", "1.5", "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    values = line["rehearsal_values"]
    assert {"moe_local_pairs_per_token.serve", "dsa_selected_share.longdoc",
            "mfu.longdoc"} <= set(values)
    assert 0 < values["dsa_selected_share.longdoc"]["value"] < 100
