"""int8 paged KV cache (ops/paged_attention.py quant kernels + engine).

The quantization contract (PARITY.md "int8 paged KV"):

  * ``kv_quant_columns`` is the ONE quantizer: per-column (per-token
    position), per-kv-head abs-max symmetric int8, qmax=127, scale
    floor 1e-8 — the same convention as quantization/quanters.py.
    Every cache byte is written exactly once from its own fp values,
    on prefill-scatter and decode-update alike, so the cache contents
    are a pure function of the token prefix (path-independence is what
    makes cached-vs-cold parity and journal recovery bit-identical
    with int8 on).
  * the quant decode kernel matches the fp32 XLA reference within the
    dequantization error bound (|err| <= scale/2 per element before
    softmax), checked here at int8-appropriate tolerance.
  * the fused attend+update kernel merges the pre-quantized new column
    into the aliased int8 pools + scale pools; written bytes equal the
    out-of-kernel quantizer's output bitwise.
  * engine end-to-end: ``kv_dtype="int8"`` runs leak-free; the fp16
    default stays bitwise identical to the pre-PR path (the quant code
    is never on the default trace).

Tiny shapes, pallas interpret mode on CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.models.llama import init_llama_params, llama_tiny
from paddle_tpu.ops import _common
from paddle_tpu.ops.paged_attention import (_LOG2E, KV_QMAX, KV_SCALE_FLOOR,
                                            kv_quant_columns,
                                            paged_attend_update_quant,
                                            paged_update_walk,
                                            paged_attention_quant,
                                            paged_attention_xla)

L, NH, HD, BS = 2, 4, 32, 128
KVD = NH * HD
NKV = NH  # MHA pools in the kernel tests


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


def _quantize_pool(pool, nkv):
    """Quantize a [L, NB, KVD, BS] fp pool column-by-column through the
    one shared quantizer, returning (int8 pool, [L, NB, nkv, BS] scales)."""
    l, nb, kvd, bs = pool.shape
    cols = jnp.asarray(pool).transpose(0, 1, 3, 2).reshape(l * nb * bs, kvd)
    q, s = kv_quant_columns(cols, nkv)
    qp = q.reshape(l, nb, bs, kvd).transpose(0, 1, 3, 2)
    sp = s.reshape(l, nb, bs, nkv).transpose(0, 1, 3, 2)
    return qp, sp


def _dequant_pool(qp, sp, nkv):
    l, nb, kvd, bs = qp.shape
    hd = kvd // nkv
    x = np.asarray(qp, np.float32).reshape(l, nb, nkv, hd, bs)
    return (x * np.asarray(sp)[:, :, :, None, :]).reshape(l, nb, kvd, bs)


def test_kv_quant_columns_convention():
    """abs-max symmetric per (column, kv-head): qmax 127, floor 1e-8,
    round-half-even like the quantization/ quanters; error <= scale/2."""
    rng = np.random.RandomState(0)
    x = rng.randn(16, KVD).astype(np.float32)
    x[3] = 0.0  # all-zero column exercises the scale floor
    q, s = kv_quant_columns(jnp.asarray(x), NKV)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == (16, KVD) and s.shape == (16, NKV)
    xg = x.reshape(16, NKV, HD)
    ref_s = np.maximum(np.abs(xg).max(-1) / KV_QMAX, KV_SCALE_FLOOR)
    np.testing.assert_allclose(np.asarray(s), ref_s, rtol=1e-6)
    deq = np.asarray(q, np.float32).reshape(16, NKV, HD) * ref_s[:, :, None]
    assert np.abs(deq - xg).max() <= ref_s.max() / 2 + 1e-7
    assert np.abs(np.asarray(q)).max() <= KV_QMAX
    # zero column: scale floored, bytes exactly zero
    assert (np.asarray(q)[3] == 0).all()
    assert (np.asarray(s)[3] == KV_SCALE_FLOOR).all()


def test_quant_decode_matches_xla_reference():
    """Ragged batch through the int8 kernel vs the fp32 XLA reference on
    the DEQUANTIZED pool: only f32-accumulation error remains, because
    the kernel's dequant reproduces the same fp values."""
    rng = np.random.RandomState(1)
    q = rng.randn(3, NH, KVD).astype(np.float32) * 0.1
    qs = jnp.asarray(q * (_LOG2E / (HD ** 0.5)))
    pool_k = rng.randn(L, 8, KVD, BS).astype(np.float32)
    pool_v = rng.randn(L, 8, KVD, BS).astype(np.float32)
    kq, ks = _quantize_pool(pool_k, NKV)
    vq, vs = _quantize_pool(pool_v, NKV)
    tables = jnp.asarray([[5, 2, 0], [1, 3, 7], [4, 0, 0]], jnp.int32)
    lens = jnp.asarray([129, 384, 17], jnp.int32)
    out = paged_attention_quant(qs, kq, vq, ks, vs, tables, lens, 1)
    ref = paged_attention_xla(
        jnp.asarray(q), jnp.asarray(_dequant_pool(kq, ks, NKV)),
        jnp.asarray(_dequant_pool(vq, vs, NKV)), tables, lens, 1,
        1.0 / (HD ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_quant_update_writes_prequantized_bytes():
    """The fused update merges EXACTLY the bytes+scale the out-of-kernel
    quantizer produced — bitwise — and leaves every other column alone."""
    rng = np.random.RandomState(2)
    pool_k = rng.randn(L, 4, KVD, BS).astype(np.float32)
    pool_v = rng.randn(L, 4, KVD, BS).astype(np.float32)
    kq, ks = _quantize_pool(pool_k, NKV)
    vq, vs = _quantize_pool(pool_v, NKV)
    q = rng.randn(1, NH, KVD).astype(np.float32) * 0.1
    qs = jnp.asarray(q * (_LOG2E / (HD ** 0.5)))
    newk = rng.randn(1, KVD).astype(np.float32)
    newv = rng.randn(1, KVD).astype(np.float32)
    nkq, nks = kv_quant_columns(jnp.asarray(newk), NKV)
    nvq, nvs = kv_quant_columns(jnp.asarray(newv), NKV)
    tables = jnp.asarray([[1, 3]], jnp.int32)
    pos = jnp.asarray([127], jnp.int32)
    out, kp_u, vp_u, ks_u, vs_u = paged_attend_update_quant(
        qs, nkq, nvq, nks, nvs, kq, vq, ks, vs,
        paged_update_walk(tables, pos, BS), 1)
    kp_u, ks_u = np.asarray(kp_u), np.asarray(ks_u)
    # the written column is the quantizer's bytes, bitwise
    assert (kp_u[1, 1, :, 127] == np.asarray(nkq)[0]).all()
    assert (ks_u[1, 1, :, 127] == np.asarray(nks)[0]).all()
    assert (np.asarray(vp_u)[1, 1, :, 127] == np.asarray(nvq)[0]).all()
    assert (np.asarray(vs_u)[1, 1, :, 127] == np.asarray(nvs)[0]).all()
    # every other column of the touched block is untouched
    mask = np.arange(BS) != 127
    assert (kp_u[1, 1][:, mask] == np.asarray(kq)[1, 1][:, mask]).all()
    assert (ks_u[1, 1][:, mask] == np.asarray(ks)[1, 1][:, mask]).all()
    # attention output matches XLA on the merged dequantized cache
    lens = jnp.asarray([128], jnp.int32)
    ref = paged_attention_xla(
        jnp.asarray(q),
        jnp.asarray(_dequant_pool(jnp.asarray(kp_u), jnp.asarray(ks_u),
                                  NKV)),
        jnp.asarray(_dequant_pool(vp_u, vs_u, NKV)),
        tables, lens, 1, 1.0 / (HD ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("positions", [[127, 128, 300, 0], [0, 0, 0, 0]],
                         ids=["ragged", "all_padding"])
def test_quant_update_grid_ended_at_the_last_live_block(positions):
    """As the plain kernel's: the grid ends at the walk's live total, and
    driven over every slot of every table instead it writes bitwise the
    same attention, bytes and scales."""
    rng = np.random.RandomState(5)
    b = len(positions)
    kq, ks = _quantize_pool(rng.randn(L, 12, KVD, BS).astype(np.float32), NKV)
    vq, vs = _quantize_pool(rng.randn(L, 12, KVD, BS).astype(np.float32), NKV)
    qs = jnp.asarray(rng.randn(b, NH, KVD).astype(np.float32) * 0.1)
    nkq, nks = kv_quant_columns(
        jnp.asarray(rng.randn(b, KVD).astype(np.float32)), NKV)
    nvq, nvs = kv_quant_columns(
        jnp.asarray(rng.randn(b, KVD).astype(np.float32)), NKV)
    tables = np.zeros((b, 3), np.int32)
    free = iter(range(1, 12))
    for i, pos in enumerate(positions):
        for j in range(pos // BS + 1 if pos else 0):
            tables[i, j] = next(free)
    sched, live = paged_update_walk(
        jnp.asarray(tables), jnp.asarray(positions, jnp.int32), BS)
    assert int(live) == sum(p // BS + 1 for p in positions) < sched.shape[1]

    def run(total):
        return jax.jit(lambda *a: paged_attend_update_quant(
            *a[:-1], (sched, a[-1]), 1))(
                qs, nkq, nvq, nks, nvs, kq, vq, ks, vs, jnp.int32(total))

    for whole, ended in zip(run(sched.shape[1]), run(live)):
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(ended))


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny(vocab=96, hidden=64, layers=1, heads=4, kv_heads=2,
                     seq=512)
    return cfg, init_llama_params(cfg, seed=3)


def _run_engine(model, prompts, **kw):
    cfg, params = model
    serve = ServeConfig(block_size=128, num_blocks=10, max_batch=2,
                        prefill_chunk=32, max_seq_len=512, **kw)
    eng = InferenceEngine(params, cfg, serve, record_events=True)
    reqs = [Request(p, max_new_tokens=5, arrival=float(i))
            for i, p in enumerate(prompts)]
    eng.run(reqs, deterministic=True)
    return eng, {s.req.request_id: s.generated for s in eng.finished}


def test_engine_int8_end_to_end(model):
    """kv_dtype='int8' serves multi-chunk + multi-block prompts leak-free;
    pools are int8 with fp32 scale sidecars."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (7, 130)]
    eng, toks = _run_engine(model, prompts, kv_dtype="int8")
    assert eng.k_pool.dtype == jnp.int8
    assert eng.k_scale is not None and eng.k_scale.dtype == jnp.float32
    assert eng.pool.used_blocks == 0
    assert all(len(t) == 5 for t in toks.values())
    assert eng.stats()["kv_dtype"] == "int8"


def test_engine_fp16_default_unchanged(model):
    """The default path never touches quant code: no scale pools, tokens
    identical whether kv_dtype is unset or 'auto'."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (7, 130)]
    eng, toks = _run_engine(model, prompts)
    eng2, toks2 = _run_engine(model, prompts, kv_dtype="auto")
    assert eng.k_scale is None and eng2.k_scale is None
    assert toks == toks2
    assert eng.stats()["kv_dtype"] == "auto"


def test_engine_rejects_unknown_kv_dtype(model):
    cfg, params = model
    with pytest.raises(ValueError, match="kv_dtype"):
        InferenceEngine(params, cfg,
                        ServeConfig(block_size=128, num_blocks=4,
                                    kv_dtype="fp8"))


def test_int8_decode_replay_deterministic(model):
    """Same trace twice with int8 KV: identical events and tokens —
    quantization is deterministic, so replay stays exact."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 96, size=n).tolist() for n in (20, 140)]
    eng, toks = _run_engine(model, prompts, kv_dtype="int8")
    eng2, toks2 = _run_engine(model, prompts, kv_dtype="int8")
    assert toks == toks2
    assert eng.events == eng2.events
