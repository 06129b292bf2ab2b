"""FLOP and byte counts against numbers worked by hand for one layer of each
configuration, read through the configuration's family."""
import json
import os

import pytest

from chipbench import flops, peaks, spec


def model(name):
    """(the configuration, its family's module)"""
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        m = json.load(f)
    return m, spec.family(m)


def test_yi_layer_and_step():
    m, fam = model("yi-1.5-9b")
    # q o: 2 x 4096 x 4096; k v: 2 x 4096 x 512; ffn: 3 x 4096 x 11008
    assert fam.layer_matmul_params(m) == 33554432 + 4194304 + 135266304
    assert fam.head_params(m) == 4096 * 64000
    assert fam.matmul_params(m) == 3 * 173015040 + 262144000
    # 6 N + 6 L S q_width = 4,687,134,720 + 301,989,888
    assert fam.train_flops_per_token(m, 4096) == 4989124608.0


def test_mistral_layer():
    m, fam = model("mistral-7b-v0.3")
    # q o: 33,554,432; k v: 2 x 4096 x 1024; ffn: 3 x 4096 x 14336
    assert fam.layer_matmul_params(m) == 33554432 + 8388608 + 176160768
    assert fam.head_params(m) == 134217728
    # one decoded token at context 1000 through 16 layers and the head
    one = fam.forward_flops(m, 1, 1000, 1)
    assert one == 16 * (2 * 218103808 + 4 * 4096 * 1000) + 2 * 134217728


def test_flash_calls_yi():
    m, fam = model("yi-1.5-9b")
    f, b = fam.flash_fwd_call(m, 2, 4096)
    assert f == 2 * 2 * 32 * 4096 * 4096 * 128 == 274877906944
    assert b == 134217728 + 16777216 + 1048576
    f, b = fam.flash_bwd_call(m, 2, 4096)
    assert f == 2.5 * 274877906944
    assert b == 2 * 134217728 + 2 * 16777216 + 2 * 1048576
    pk = peaks.peak("TPU v5 lite")
    # compute-bound: 274.9 GFLOP at 197 TFLOP/s
    assert flops.min_seconds(*fam.flash_fwd_call(m, 2, 4096), pk) \
        == pytest.approx(1.3953e-3, rel=1e-4)
    # the counters the roofline readers get are those calls' least seconds
    counters = fam.train_kernels(m, {"batch": 2, "seq": 4096}, pk)
    assert counters["flash_fwd"]["per_call_least_s"] \
        == pytest.approx(1.3953e-3, rel=1e-4)
    assert counters["flash_bwd"]["per_call_least_s"] \
        == pytest.approx(2.5 * 1.3953e-3, rel=1e-4)


def test_paged_decode_call_mistral():
    m, fam = model("mistral-7b-v0.3")
    f, b = fam.paged_decode_call(m, [1000, 129], 128)
    assert f == 4 * 32 * 128 * 1129
    # 8 + 2 blocks of 128 tokens x 1024 KV width x 2 bytes, K and V
    assert b == 2 * 10 * 128 * 1024 * 2 + 2 * 2 * 32 * 128 * 2
    pk = peaks.peak("TPU v5 lite")
    assert flops.min_seconds(f, b, pk) == pytest.approx(b / 819e9)
    # the counter: that call once a layer in every traced decode step, and
    # nothing for an iteration that only prefilled
    its = [{"decode_ctx": [1000, 129]}, {"decode_ctx": []}]
    counters = fam.serve_kernels(m, {"block_size": 128}, its, pk)
    assert counters["paged_decode"]["least_s"] \
        == pytest.approx(16 * b / 819e9)
    assert fam.serve_kernels(m, {"block_size": 128}, its[1:], pk) \
        == {"paged_decode": None}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        peaks.peak("TPU v9 imaginary")
