"""Chunked-prefill attention through the block table
(ops/paged_attention.py ``paged_prefill_attention``, PR 29).

The kernel replaced a dense softmax over every slot of the sequence's block
table, whatever the context held. Pinned here, in interpret mode on the CPU:

  * parity with that dense float32 formula (gather the whole table,
    mask ``t <= position``, softmax, PV) on the chunk's live rows, over where
    the chunk starts, how much of it is live, the GQA ratio and the pool's
    dtype, on a table with scattered block ids and dead slots;
  * the schedule: a tile walks the blocks up to its own causal frontier, a
    dead step re-presents the last live block, a tile of padding alone walks
    nothing;
  * the engine: a prompt prefilled in chunks gives the greedy stream the
    tree gave before the change (recorded from commit 4c35a44), with the
    prefix cache on and off, fp and int8 pools.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import InferenceEngine, Request, ServeConfig
from paddle_tpu.models.llama import init_llama_params, llama_tiny
from paddle_tpu.ops import _common
from paddle_tpu.ops import paged_attention as pa

L, NP, NKV, HD, BS, MAX_NB, C = 2, 24, 2, 16, 8, 10, 32
LAYER = 1


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


def dense_reference(q, kp, vp, table_row, start, scales=None):
    """The formula ``llama_paged_prefill_chunk`` held before PR 29, in
    float32: the whole table gathered to [KVD, max_nb * bs], every slot
    scored, the causal mask the only thing that tells live from dead."""
    c, nh, hd = q.shape
    kvd, bs = kp.shape[2:]
    nkv, rep, T = kvd // hd, nh // (kvd // hd), table_row.shape[0] * bs

    def ctx(pool, sc):
        x = jnp.transpose(pool[LAYER][table_row], (1, 0, 2)).reshape(kvd, T)
        x = x.astype(jnp.float32).reshape(nkv, hd, T)
        if sc is not None:
            s = jnp.transpose(sc[LAYER][table_row], (1, 0, 2)).reshape(nkv, T)
            # the dense path rounded the dequantised context to q's dtype
            x = (x * s[:, None, :]).astype(q.dtype).astype(jnp.float32)
        return x

    kg = ctx(kp, scales and scales[0])
    vg = ctx(vp, scales and scales[1])
    s = jnp.einsum("cgrd,gdt->cgrt",
                   q.astype(jnp.float32).reshape(c, nkv, rep, hd), kg)
    pidx = start + jnp.arange(c)
    s = jnp.where((jnp.arange(T)[None, :] <= pidx[:, None])[:, None, None, :],
                  s / hd ** 0.5, -1e30)
    return jnp.einsum("cgrt,gdt->cgrd", jax.nn.softmax(s, axis=-1),
                      vg).reshape(c, nh, hd)


def pools(kind, seed, nkv=NKV):
    """(k_pool, v_pool, scales or None, q dtype) with every block filled:
    a dead slot or the null block holds finite garbage, as on the chip."""
    rng = np.random.default_rng(seed)
    shape = (L, NP, nkv * HD, BS)
    if kind == "int8":
        draw = lambda: jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        scale = lambda: jnp.asarray(
            rng.uniform(0.002, 0.02, (L, NP, nkv, BS)), jnp.float32)
        return draw(), draw(), (scale(), scale()), jnp.bfloat16
    dtype = jnp.dtype(kind)
    draw = lambda: jnp.asarray(rng.normal(size=shape), dtype)
    return draw(), draw(), None, dtype


def scattered_table(seed, live_slots):
    """Block ids in no order, dead slots pointing at the null block 0."""
    rng = np.random.default_rng(seed)
    table = np.zeros(MAX_NB, np.int32)
    table[:live_slots] = rng.permutation(np.arange(1, NP))[:live_slots]
    return jnp.asarray(table)


STARTS = {"zero": 0, "mid_block": 3, "block_aligned": 8, "blocks_and_some": 21}
LIVES = {"one": 1, "partial": 19, "full": C}


@pytest.mark.parametrize("kind", ["bfloat16", "int8"])
# (KV heads, query heads a KV head): Mistral's and Yi's groups of 4 and 8,
# and 20 heads over 4 KV heads, a group of 5 (Falcon-H1's: no power of two)
@pytest.mark.parametrize("nkv,rep", [(NKV, 4), (NKV, 8), (4, 5)],
                         ids=["4", "8", "20over4"])
@pytest.mark.parametrize("n_live", list(LIVES.values()), ids=list(LIVES))
@pytest.mark.parametrize("start", list(STARTS.values()), ids=list(STARTS))
def test_matches_the_dense_formula_it_replaced(start, n_live, nkv, rep, kind,
                                               monkeypatch):
    # two query tiles of 16: at n_live 1 the second holds padding alone
    monkeypatch.setattr(pa, "PREFILL_BLOCK_Q", 16)
    kp, vp, scales, qdt = pools(kind, seed=start + n_live, nkv=nkv)
    nh = nkv * rep
    q = jnp.asarray(np.random.default_rng(rep).normal(size=(C, nh, HD)), qdt)
    table = scattered_table(start, -(-(start + n_live) // BS))
    out = pa.paged_prefill_attention(
        q, kp, vp, table, jnp.int32(start), jnp.int32(n_live),
        jnp.int32(LAYER), kv_scales=scales)
    ref = dense_reference(q, kp, vp, table, start, scales)
    assert out.shape == (C, nh, HD) and out.dtype == qdt
    out = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(out).all()          # the padding rows too
    # bf16 probabilities and a bf16 result: 2^-8 of a value of a few units
    np.testing.assert_allclose(out[:n_live], np.asarray(ref)[:n_live],
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("block_q", [128, 16])
def test_float32_pools_agree_to_rounding(block_q, monkeypatch):
    """With nothing rounded to bf16 the online softmax and the dense one
    differ by float32 rounding alone, in one query tile or in two."""
    monkeypatch.setattr(pa, "PREFILL_BLOCK_Q", block_q)
    kp, vp, _, _ = pools("float32", seed=7)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(C, 8, HD)),
                    jnp.float32)
    table = scattered_table(3, 7)
    out = pa.paged_prefill_attention(q, kp, vp, table, jnp.int32(21),
                                     jnp.int32(C), jnp.int32(LAYER))
    ref = dense_reference(q, kp, vp, table, 21)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dead_slots_and_their_garbage_are_never_read():
    """Whatever the blocks past the live context hold, NaN included, the
    result is the same: the old path scored them and leaned on the mask."""
    kp, vp, _, _ = pools("float32", seed=11)
    q = jnp.asarray(np.random.default_rng(2).normal(size=(C, 8, HD)),
                    jnp.float32)
    start, n_live = 8, 19
    table = np.asarray(scattered_table(5, 4)).copy()
    args = (jnp.int32(start), jnp.int32(n_live), jnp.int32(LAYER))
    clean = pa.paged_prefill_attention(q, kp, vp, jnp.asarray(table), *args)
    dead = next(b for b in range(1, NP) if b not in table[:4])
    table[4:] = dead                    # dead slots point at a poisoned block
    poison = lambda p: p.at[:, dead].set(jnp.nan).at[:, 0].set(jnp.nan)
    dirty = pa.paged_prefill_attention(q, poison(kp), poison(vp),
                                       jnp.asarray(table), *args)
    assert (np.asarray(clean)[:n_live] == np.asarray(dirty)[:n_live]).all()


def test_schedule_walks_each_tile_to_its_own_frontier():
    table = jnp.arange(100, 100 + MAX_NB, dtype=jnp.int32)
    # four tiles of 8 queries from position 21, 19 of the 32 live: last
    # live position 39, in slot 4
    blk, tiles = pa.paged_prefill_schedule(table, 21, 19, 4, 8, BS)
    q0, nblk = np.asarray(tiles)
    assert q0.tolist() == [21, 29, 37, 45]
    # tile 0 ends at position 28 (slot 3), tile 1 at 36 (slot 4), tile 2
    # holds the live rows 37..39 (slot 4), tile 3 padding alone
    assert nblk.tolist() == [4, 5, 5, 0]
    blk = np.asarray(blk)
    assert blk[0].tolist() == [100, 101, 102, 103] + [103] * 6
    assert blk[1].tolist() == [100, 101, 102, 103, 104] + [104] * 5
    assert blk[3].tolist() == [100] * MAX_NB
    # the blocks visited: never more than the live context's
    assert nblk.max() == -(-(21 + 19) // BS)


@pytest.mark.parametrize("c, want", [(512, 128), (64, 64), (96, 96),
                                     (192, 96), (8, 8), (24, 24)])
def test_query_tile_divides_the_chunk(c, want):
    assert pa._fit_paged_prefill_blocks(c, 32, 128, 8, 128, 2) == want


def test_windows_past_the_vmem_limit_are_refused_at_trace_time():
    with pytest.raises(ValueError, match="VMEM"):
        pa._fit_paged_prefill_blocks(512, 256, 128, 256, 128, 2)


# -- the engine: the same greedy streams as before the change ----------------

# generated tokens of the four requests below at commit 4c35a44 (the dense
# path), the same for every configuration
STREAMS_BEFORE = [[63, 31, 76, 61, 30, 32], [76, 61, 30, 15, 37, 28],
                  [47, 62, 91, 76, 61, 30], [56, 91, 91, 57, 21, 57]]


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["cache_off", "cache_on"])
def test_chunked_prefill_gives_the_streams_it_gave_before(prefix_cache,
                                                          kv_dtype):
    """Three prompts share 140 tokens (one whole block: with the cache on
    the third starts its first chunk at 128), one is alone; chunks of
    64 over blocks of 128, so chunks start mid-block and on a block's edge."""
    cfg = llama_tiny(vocab=96, hidden=64, layers=2, heads=8, kv_heads=2,
                     seq=512)
    params = init_llama_params(cfg, seed=5)
    rng = np.random.RandomState(1)
    shared = rng.randint(1, 96, size=140).tolist()
    prompts = [shared + rng.randint(1, 96, size=n).tolist()
               for n in (3, 37, 150)]
    prompts.append(rng.randint(1, 96, size=11).tolist())
    kw = {} if kv_dtype is None else {"kv_dtype": kv_dtype}
    serve = ServeConfig(block_size=128, num_blocks=12, max_batch=2,
                        prefill_chunk=64, max_seq_len=512,
                        prefix_cache=prefix_cache, **kw)
    eng = InferenceEngine(params, cfg, serve)
    eng.run([Request(p, max_new_tokens=6, arrival=float(i))
             for i, p in enumerate(prompts)], deterministic=True)
    got = {s.req.request_id: list(s.generated) for s in eng.finished}
    assert [got[k] for k in sorted(got)] == STREAMS_BEFORE
    hits = eng.metrics_snapshot().get("prefix_cache_hits", 0)
    assert (hits >= 1) if prefix_cache else (hits == 0)
    # the walk never passes the live context, and falls short of the table
    totals = eng.work_totals
    assert 0 < totals["prefill_ctx_blocks_total"] \
        < totals["prefill_table_blocks_total"]
