"""The weights a Llama engine's programs run on (``LlamaServing.prepare``).

``InferenceEngine`` hands its step programs the q/k/v stacks fused and
transposed once, ``qkv_proj: {"wT": [L, (nh + 2 nkv) hd, H]}``, where the
caller gave the training layout ``[L, in, out]``: sliced out of that stack,
each layer's projection is relaid in every program. Pinned here: the
prepared engine emits the raw tree's greedy tokens (decode, speculation's
verify with the default draft, weight-int8 leaves); a training-layout
checkpoint still swaps in without a recompile; a fleet relays once; a
prepared tree, and a model that relays nothing, read 0 on the gauge
``paddle_tpu_serve_weights_relaid_bytes``.

Tiny models in float32, pallas interpret mode on the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.checkpoint import save_load as sl
from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.inference.fleet import FleetRouter
from paddle_tpu.models.llama import (LlamaServing, init_llama_params,
                                     llama_tiny, make_draft_model,
                                     prepare_decode_params,
                                     quantize_llama_int8)
from paddle_tpu.ops import _common

QKV = ("q_proj", "k_proj", "v_proj")


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


@pytest.fixture(scope="module")
def model():
    # two layers, so that the default draft (the first) disagrees with the
    # base and verify rejects proposals
    cfg = llama_tiny(vocab=96, hidden=64, layers=2, heads=4, kv_heads=2,
                     seq=512)
    return cfg, init_llama_params(cfg, seed=0), init_llama_params(cfg, seed=5)


def _serve(**kw):
    return ServeConfig(**dict(dict(block_size=128, num_blocks=12, max_batch=3,
                                   prefill_chunk=32, max_seq_len=256), **kw))


def _requests():
    # the 100- and 70-token prompts take 4 and 3 chunks, each arriving while
    # an earlier request decodes, so the rows ride the chunks
    rng = np.random.RandomState(7)
    return [Request(rng.randint(1, 90, size=n).tolist(), max_new_tokens=8,
                    arrival=float(at), request_id=i)
            for i, (n, at) in enumerate([(9, 0), (100, 2), (70, 4)])]


def _tokens(eng):
    eng.run(_requests(), deterministic=True)
    return {s.req.request_id: s.generated for s in eng.finished}


def _qkv_bytes(params):
    return sum(a.nbytes for n in QKV
               for a in jax.tree_util.tree_leaves(params["layers"][n]))


@pytest.mark.parametrize("case", ["decode", "verify", "int8-weights"])
def test_prepared_engine_serves_the_raw_trees_tokens(model, case):
    cfg, params, _ = model
    kw = {"speculative": True, "draft_k": 3} if case == "verify" else {}
    if case == "int8-weights":
        params = quantize_llama_int8(params)
    eng = InferenceEngine(params, cfg, _serve(**kw))
    fused = eng.params["layers"]["qkv_proj"]
    assert set(fused) == ({"wT", "s"} if case == "int8-weights" else {"wT"})
    assert fused["wT"].shape == (2, (4 + 2 * 2) * 16, 64)
    assert not any(n in eng.params["layers"] for n in QKV)
    assert eng.relaid_bytes == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(fused)) > 0
    # the same engine run on the caller's tree, which the programs also read
    raw = InferenceEngine(params, cfg, _serve(**kw))
    raw.params = params
    if case == "verify":
        raw.draft_params = make_draft_model(params, cfg)[0]
        assert "qkv_proj" in eng.draft_params["layers"]
    got, want = _tokens(eng), _tokens(raw)
    assert got == want and all(len(t) == 8 for t in got.values())
    if case != "verify":
        assert eng.work_totals["prefill_chunks_with_decode_total"] > 0


def test_swap_from_a_training_layout_checkpoint_recompiles_nothing(
        model, tmp_path):
    cfg, params, params2 = model
    ck = str(tmp_path / "ck")
    sl.save_state_dict(jax.tree_util.tree_map(lambda a: a, params2), ck)
    eng = InferenceEngine(params, cfg, _serve())
    _tokens(eng)
    compiled = set(eng._compiled)
    eng.finished.clear()
    stats = eng.swap_weights(ck)
    assert stats["n_leaves"] == len(jax.tree_util.tree_leaves(eng.params))
    assert eng.metrics_snapshot()["weights_relaid_bytes"] \
        == _qkv_bytes(params2)
    assert _tokens(eng) == _tokens(InferenceEngine(params2, cfg, _serve()))
    assert set(eng._compiled) == compiled
    # a swap of the engine's own prepared tree relays nothing
    eng.swap_weights(eng.params)
    assert eng.relaid_bytes == 0


def test_fleet_replicas_share_one_prepared_tree(model):
    cfg, params, _ = model
    fleet = FleetRouter(params, cfg, _serve(), n_replicas=3)
    first = fleet.engines[0].params
    assert "qkv_proj" in first["layers"]
    assert all(e.params is first for e in fleet.engines)
    assert [e.relaid_bytes for e in fleet.engines] \
        == [_qkv_bytes(params), 0, 0]


def test_a_prepared_tree_is_relaid_no_further(model):
    cfg, params, _ = model
    prepared = prepare_decode_params(
        jax.tree_util.tree_map(jnp.copy, params), cfg)
    eng = InferenceEngine(prepared, cfg, _serve())
    assert eng.params is prepared
    assert eng.metrics_snapshot()["weights_relaid_bytes"] == 0
    assert LlamaServing.prepare(prepared, cfg) is prepared
    assert _tokens(eng) == _tokens(InferenceEngine(params, cfg, _serve()))


def test_prepare_donates_nothing_and_keeps_every_other_leaf(model):
    cfg, params, _ = model
    out = LlamaServing.prepare(params, cfg)
    # the caller's stacks stay live (the harness and the tests read them)
    assert all(not params["layers"][n].is_deleted() for n in QKV)
    for name in ("embed", "final_norm", "lm_head"):
        assert out[name] is params[name]
    kept = set(params["layers"]) - set(QKV)
    assert all(out["layers"][n] is params["layers"][n] for n in kept)
    wT = out["layers"]["qkv_proj"]["wT"]
    np.testing.assert_array_equal(
        wT, jnp.concatenate([jnp.swapaxes(params["layers"][n], -1, -2)
                             for n in QKV], axis=-2))


def test_heads_that_do_not_split_keep_three_transposed_leaves(model):
    """Pruned heads: q's width no longer nh / nkv times k's, so the fused
    split would cut the heads in the wrong places."""
    cfg, params, _ = model
    layers = dict(params["layers"],
                  q_proj=params["layers"]["q_proj"][..., :48])
    out = LlamaServing.prepare(dict(params, layers=layers), cfg)
    assert "qkv_proj" not in out["layers"]
    for n in QKV:
        np.testing.assert_array_equal(out["layers"][n]["wT"],
                                      jnp.swapaxes(layers[n], -1, -2))


def test_tensor_parallel_engines_take_the_tree_as_given(model):
    cfg, params, _ = model
    eng = InferenceEngine(params, cfg, _serve(mp=2))
    assert set(QKV) <= set(eng.params["layers"])
    assert eng.relaid_bytes == 0


@pytest.mark.parametrize("family", ["deepseek", "falcon_h1"])
def test_models_that_relay_nothing_read_zero(family):
    if family == "deepseek":
        from paddle_tpu.models import deepseek as D
        c = D.deepseek_tiny()
        params = D.init_deepseek_params(c, seed=3)
    else:
        from paddle_tpu.models import falcon_h1 as H
        c = H.falcon_h1_tiny()
        params = H.init_falcon_h1_params(c, seed=1)
    eng = InferenceEngine(params, c, _serve(max_batch=2))
    assert eng.params is params
    assert eng.metrics_snapshot()["weights_relaid_bytes"] == 0


def test_the_gauge_is_exposed():
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                     seq=256)
    params = init_llama_params(cfg, seed=1)
    text = InferenceEngine(params, cfg, _serve()).render_prometheus()
    assert (f"paddle_tpu_serve_weights_relaid_bytes {_qkv_bytes(params)}"
            in text)
