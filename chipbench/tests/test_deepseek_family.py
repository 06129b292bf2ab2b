"""The DeepSeek family's counts against numbers worked by hand, the
configuration file against the catalog's rules, and the cell's rehearsal
through the harness's own entry."""
import json
import math
import os
import subprocess
import sys

import jax
import pytest

from chipbench import flops, peaks, spec
from chipbench.weights import is_leaf

PEAK = peaks.PEAKS["TPU v5 lite"]


def model():
    with open(os.path.join(spec.HERE, "configs", "deepseek-v3.json")) as f:
        m = json.load(f)
    return m, spec.family(m)


def test_layer_counts_by_hand():
    m, fam = model()
    # W_qa 7168x1536, W_qb 1536x128x192, W_kva 7168x576, W_kvb 512x128x256,
    # W_o 16384x7168
    assert fam.attn_params(m) == (11010048 + 37748736 + 4128768 + 16777216
                                  + 117440512) == 187105280
    assert fam.expert_params(m) == 3 * 7168 * 2048 == 44040192
    assert fam.held_experts_per_token(m) == 0.5        # 8 x 16 / 256
    assert fam.layer_matmul_params(m, False) == 187105280 + 3 * 7168 * 18432
    # attention, the router's 256 outputs, the shared expert and half a
    # routed one
    assert fam.layer_matmul_params(m, True) == 187105280 + 1835008 \
        + 1.5 * 44040192 == 255000576
    assert fam.head_params(m) == 7168 * 16160
    assert fam.matmul_params(m) == 583467008 + 5 * 255000576 + 115834880
    assert fam.pair_flops(m, absorbed=False) == 81920
    assert fam.pair_flops(m, absorbed=True) == 278528


def test_forward_flops_tells_decode_from_prefill():
    m, fam = model()
    body = 583467008 + 5 * 255000576
    # one decoded token at a context of 1000: the absorbed rate
    assert fam.forward_flops(m, 1, 1000, 1) == \
        2.0 * body + 6 * 278528 * 1000 + 2.0 * 115834880
    # a chunk of 512 after 3072 cached, no logit row: the expanded rate
    pairs = 512 * 3072 + 512 * 513 // 2
    assert fam.forward_flops(m, 512, pairs, 0) == \
        2.0 * body * 512 + 6 * 81920 * pairs


def test_kernel_calls_by_hand():
    m, fam = model()
    f, b = fam.mla_decode_call(m, [1000, 129], 128)
    assert f == 278528 * 1129
    # 8 + 2 latent blocks of 128 x 1152 B; per row 128 heads x (576 bf16 in
    # + 512 f32 out) and one block written back
    assert b == 10 * 147456 + 2 * (128 * (1152 + 2048) + 147456)
    # 242 FLOP a byte of latent: the kernel sits on the chip's ridge of 240.5
    assert 278528 / 1152 == pytest.approx(241.8, abs=0.1)
    assert PEAK["flops_per_s"] / PEAK["hbm_bytes_per_s"] == \
        pytest.approx(240.5, abs=0.1)
    f, b = fam.mla_prefill_call(m, 3072, 512, 128)
    assert f == 278528 * (512 * 3072 + 512 * 513 // 2)
    assert b == 28 * 147456 + 512 * 128 * (576 + 512) * 2
    assert flops.min_seconds(f, b, PEAK) == f / 197e12     # compute-bound
    f, b = fam.moe_experts_call(m, 10, 8)
    assert (f, b) == (2.0 * 44040192 * 10, 2.0 * 44040192 * 8)
    assert flops.min_seconds(f, b, PEAK) == b / 819e9      # memory-bound
    its = [{"decode_ctx": [1000, 129], "prefill": (3072, 512, 0)},
           {"decode_ctx": [], "prefill": None}]
    k = fam.serve_kernels(m, {"block_size": 128}, its, PEAK)
    assert k["mla_decode"]["least_s"] == pytest.approx(
        6 * flops.min_seconds(*fam.mla_decode_call(m, [1000, 129], 128),
                              PEAK))
    assert k["mla_prefill"]["least_s"] == pytest.approx(
        6 * 278528 * (512 * 3072 + 512 * 513 // 2) / 197e12)
    assert fam.serve_kernels(m, {"block_size": 128}, its[1:], PEAK) == {
        "mla_decode": None, "mla_prefill": None}


def test_the_cut_weighs_what_the_file_says():
    m, fam = model()
    leaves = jax.tree_util.tree_leaves(fam.leaves(m), is_leaf=is_leaf)
    params = sum(math.prod(leaf.shape) for leaf in leaves)
    assert 5.49e9 < params < 5.52e9                 # 11.0 GB in bf16
    e = spec.load_cell("dsv3.serve.docqa").traffic["engine"]
    cache = e["num_blocks"] * e["block_size"] * m["num_hidden_layers"] \
        * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * 2
    assert cache == 1811939328                      # 1.81 GB
    assert 12.5e9 < 2 * params + cache < 13.0e9     # 76-80 % of 16 GB
    tree = fam.leaves(m)
    assert isinstance(tree["dense"], list) and len(tree["dense"]) == 1
    assert tree["moe"]["experts"]["gate"].shape == (5, 16, 7168, 2048)
    assert tree["moe"]["router"].shape == (5, 7168, 256)    # all 256 scored
    assert tree["moe"]["router_bias"].start == "normal"
    assert tree["moe"]["q_a_norm"].start == "one"


def test_configuration_follows_the_catalog():
    """Every number of the catalog's config under the same key, but for the
    keys in ``reduced``; no width among them; the floors of a cut."""
    m, _ = model()
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("the catalog is not on this machine")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r.get("name") == "DeepSeek-V3")
    assert m["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in m["reduced"]:
            assert m["published"][k] == v and m[k] != v
        else:
            assert m[k] == v, k
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in m["reduced"])
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4
    assert m["n_routed_experts"] >= 8
    assert m["vocab_size"] * 8 >= m["published"]["vocab_size"]
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v3")
    assert entry["reduced"] == m["reduced"]


def test_train_cells_are_refused():
    m, fam = model()
    with pytest.raises(SystemExit, match="no training path"):
        fam.train_step(m, {})


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    run = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "dsv3.serve.docqa",
         "--rehearse", "--seed", "2147483888", "--seconds", "1.5",
         "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    values = line["rehearsal_values"]
    assert {"moe_local_pairs_per_token.serve",
            "moe_expert_imbalance.serve"} <= set(values)
    assert values["moe_expert_imbalance.serve"]["value"] >= 1.0
