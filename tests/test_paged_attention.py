"""Paged decode attention (ops/paged_attention.py): numerics contract.

The kernel walks per-sequence block tables over a shared block pool on a
flat scalar-prefetched schedule (the grouped_matmul tile_schedule idiom:
dead steps replay the last live step so their DMAs are elided). The
contract pinned here (PARITY.md "Paged-attention numerics"):

  * B=1, one live block: BITWISE equal to decode_attention_slab on the
    contiguous layout (the acceptance pin — both kernels run the exact
    same op sequence per tile).
  * fragmented table == contiguous table, bitwise, at any block count
    (gathering through the table is pure data movement).
  * the fused attend+update kernel matches decode_attend_update_slab
    bitwise on outputs AND on the cache contents it writes, including a
    new token that straddles into a fresh block.
  * multi-sequence ragged batches match the XLA reference to f32
    accumulation tolerance.

Everything runs in pallas interpret mode on CPU with tiny shapes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import _common
from paddle_tpu.ops.decode_attention import (decode_attend_update_slab,
                                             decode_attention_slab)
from paddle_tpu.ops.paged_attention import (_LOG2E, paged_attend_update,
                                            paged_attention,
                                            paged_attention_xla,
                                            paged_schedule,
                                            paged_update_walk,
                                            paged_schedule_stats)

L, NH, HD, BS = 2, 4, 32, 128
KVD = NH * HD


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    q = rng.randn(1, NH, KVD).astype(np.float32) * 0.1
    qs = jnp.asarray(q * (_LOG2E / (HD ** 0.5)))
    pool_k = rng.randn(L, 4, KVD, BS).astype(np.float32)
    pool_v = rng.randn(L, 4, KVD, BS).astype(np.float32)
    return qs, jnp.asarray(pool_k), jnp.asarray(pool_v), pool_k, pool_v


def test_single_block_bitwise_vs_slab(data):
    """Acceptance pin: contiguous single-block layout is BITWISE equal to
    the slab decode kernel (block_size == the slab's 128-lane T tile)."""
    qs, kp, vp, pool_k, pool_v = data
    out = paged_attention(qs, kp, vp, jnp.asarray([[1]], jnp.int32),
                          jnp.asarray([70], jnp.int32), 1)
    out_slab = decode_attention_slab(qs, jnp.asarray(pool_k[:, 1:2]),
                                     jnp.asarray(pool_v[:, 1:2]), 1, 69)
    assert (np.asarray(out) == np.asarray(out_slab)).all()


def test_fragmented_table_bitwise_vs_contiguous_slab(data):
    """Three blocks in non-monotone pool order == the same tokens laid out
    contiguously, bitwise — table indirection is pure data movement."""
    qs, kp, vp, pool_k, pool_v = data
    out = paged_attention(qs, kp, vp, jnp.asarray([[2, 0, 3]], jnp.int32),
                          jnp.asarray([300], jnp.int32), 0)
    kc = np.concatenate([pool_k[:, 2:3], pool_k[:, 0:1], pool_k[:, 3:4]], -1)
    vc = np.concatenate([pool_v[:, 2:3], pool_v[:, 0:1], pool_v[:, 3:4]], -1)
    out_slab = decode_attention_slab(qs, jnp.asarray(kc), jnp.asarray(vc),
                                     0, 299)
    assert (np.asarray(out) == np.asarray(out_slab)).all()


def test_multi_seq_ragged_vs_xla_reference():
    """Ragged batch (lengths 129/384/17, unequal block counts, padded table
    slots pointing at the null block) vs the dense XLA reference."""
    rng = np.random.RandomState(1)
    q = rng.randn(3, NH, KVD).astype(np.float32) * 0.1
    qs = jnp.asarray(q * (_LOG2E / (HD ** 0.5)))
    kp = jnp.asarray(rng.randn(L, 8, KVD, BS).astype(np.float32))
    vp = jnp.asarray(rng.randn(L, 8, KVD, BS).astype(np.float32))
    tables = jnp.asarray([[5, 2, 0], [1, 3, 7], [4, 0, 0]], jnp.int32)
    lens = jnp.asarray([129, 384, 17], jnp.int32)
    out = paged_attention(qs, kp, vp, tables, lens, 1)
    ref = paged_attention_xla(jnp.asarray(q), kp, vp, tables, lens, 1,
                              1.0 / (HD ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_fused_update_bitwise_and_cache_contents(data):
    """attend+update == slab attend+update bitwise, on the attention output
    AND the merged cache tile it writes back through the aliased outs."""
    qs, kp, vp, pool_k, pool_v = data
    rng = np.random.RandomState(2)
    newk = rng.randn(1, KVD).astype(np.float32)
    newv = rng.randn(1, KVD).astype(np.float32)
    tables = jnp.asarray([[1, 3]], jnp.int32)
    out, kp_u, vp_u = paged_attend_update(
        qs, jnp.asarray(newk), jnp.asarray(newv), kp, vp,
        paged_update_walk(tables, jnp.asarray([127], jnp.int32), BS), 1)
    kc = np.concatenate([pool_k[:, 1:2], pool_k[:, 3:4]], -1)
    vc = np.concatenate([pool_v[:, 1:2], pool_v[:, 3:4]], -1)
    out_s, kcs, vcs = decode_attend_update_slab(
        qs, jnp.asarray(newk), jnp.asarray(newv),
        jnp.asarray(kc), jnp.asarray(vc), 1, 127)
    assert (np.asarray(out) == np.asarray(out_s)).all()
    assert (np.asarray(kp_u)[1, 1] == np.asarray(kcs)[1, 0, :, :BS]).all()
    assert (np.asarray(vp_u)[1, 1] == np.asarray(vcs)[1, 0, :, :BS]).all()


def test_fused_update_straddles_into_fresh_block(data):
    """New token at pos == block_size lands in column 0 of the NEXT table
    slot; output and written block still match the slab path bitwise."""
    qs, kp, vp, pool_k, pool_v = data
    rng = np.random.RandomState(3)
    newk = rng.randn(1, KVD).astype(np.float32)
    newv = rng.randn(1, KVD).astype(np.float32)
    tables = jnp.asarray([[1, 3]], jnp.int32)
    out, kp_u, vp_u = paged_attend_update(
        qs, jnp.asarray(newk), jnp.asarray(newv), kp, vp,
        paged_update_walk(tables, jnp.asarray([BS], jnp.int32), BS), 1)
    kc = np.concatenate([pool_k[:, 1:2], pool_k[:, 3:4]], -1)
    vc = np.concatenate([pool_v[:, 1:2], pool_v[:, 3:4]], -1)
    out_s, kcs, _ = decode_attend_update_slab(
        qs, jnp.asarray(newk), jnp.asarray(newv),
        jnp.asarray(kc), jnp.asarray(vc), 1, BS)
    assert (np.asarray(out) == np.asarray(out_s)).all()
    kb3 = np.asarray(kp_u)[1, 3]
    assert (kb3[:, 0] == newk[0]).all()
    assert (kb3 == np.asarray(kcs)[1, 0, :, BS:]).all()


def _block_diagonal(q, nkv):
    """q [B, NH, HD] -> [B, NH, NKV * HD]: head h's query in the columns of
    its KV head h // (NH / NKV), zeros elsewhere (what ``_paged_attend_rows``
    hands the kernel)."""
    b, nh, hd = q.shape
    out = np.zeros((b, nh, nkv * hd), q.dtype)
    for h in range(nh):
        g = h // (nh // nkv)
        out[:, h, g * hd:(g + 1) * hd] = q[:, h]
    return out


# the query rows a sequence brings: 4 dense rows over the whole width, and
# 20 heads over 4 KV heads, a group of 5 (Falcon-H1's), block-diagonal
@pytest.mark.parametrize("heads", [None, (20, 4)], ids=["4", "20over4"])
@pytest.mark.parametrize("positions", [
    [127, 128, 300, 0], [0, 0, 0, 0], [383, 5, 255, 256]],
    ids=["ragged", "all_padding", "at_block_edges"])
def test_fused_update_grid_ended_at_the_last_live_block(positions, heads):
    """The grid ends where the walk's live steps end. Driven over every
    slot of every table instead (the walk's total set to the schedule's
    length, whose dead steps replay the last live one), the attention of
    every row and both pools come out bitwise the same: the steps left out
    moved nothing. And what comes out is the dense softmax (base 2, as the
    kernel's) over each live row's context, its new column included."""
    rng = np.random.RandomState(4)
    b = len(positions)
    if heads is None:
        q = rng.randn(b, NH, KVD).astype(np.float32) * 0.1
    else:
        nh, nkv = heads
        q = _block_diagonal(
            rng.randn(b, nh, KVD // nkv).astype(np.float32) * 0.3, nkv)
    q = jnp.asarray(q)
    newk = jnp.asarray(rng.randn(b, KVD).astype(np.float32))
    newv = jnp.asarray(rng.randn(b, KVD).astype(np.float32))
    kp = jnp.asarray(rng.randn(L, 12, KVD, BS).astype(np.float32))
    vp = jnp.asarray(rng.randn(L, 12, KVD, BS).astype(np.float32))
    tables = np.zeros((b, 3), np.int32)
    free = iter(range(1, 12))
    for i, pos in enumerate(positions):
        for j in range(pos // BS + 1 if pos else 0):
            tables[i, j] = next(free)
    sched, live = paged_update_walk(
        jnp.asarray(tables), jnp.asarray(positions, jnp.int32), BS)
    assert int(live) == sum(p // BS + 1 for p in positions) < sched.shape[1]

    def run(total):
        return jax.jit(lambda q, k, v, kp, vp, total: paged_attend_update(
            q, k, v, kp, vp, (sched, total), 1))(
                q, newk, newv, kp, vp, jnp.int32(total))

    ended = run(live)
    for whole, end in zip(run(sched.shape[1]), ended):
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(end))
    out, kp_u, vp_u = (np.asarray(a) for a in ended)
    for i, pos in enumerate(positions):
        blocks = [tables[i, j] for j in range(pos // BS + 1) if tables[i, j]]
        if not blocks:
            continue                        # a padding row
        kc = np.concatenate([kp_u[1, j] for j in blocks], -1)[:, :pos + 1]
        vc = np.concatenate([vp_u[1, j] for j in blocks], -1)[:, :pos + 1]
        assert (kc[:, pos] == np.asarray(newk)[i]).all()
        s = np.asarray(q)[i] @ kc                           # [NH, T]
        p = np.exp2(s - s.max(-1, keepdims=True))
        np.testing.assert_allclose(
            out[i], (p / p.sum(-1, keepdims=True)) @ vc.T, atol=2e-5,
            rtol=2e-5)


def test_schedule_dead_steps_replay_last_live():
    """Flat-schedule invariant: steps past the live total re-present the
    last live (seq, block) pair so Mosaic elides their DMAs, and per-seq
    boundaries carry first/last flags exactly once per sequence."""
    tables = np.asarray([[5, 2, 0], [1, 3, 7], [4, 0, 0]], np.int32)
    lens = np.asarray([129, 384, 17], np.int32)
    sched = np.asarray(paged_schedule(jnp.asarray(lens),
                                      jnp.asarray(tables), 9, BS))
    seq, blk, start, first, last, live = sched[:6]
    assert live.tolist() == [1, 1, 1, 1, 1, 1, 0, 0, 0]
    # live walk: seq0 blocks [5,2], seq1 [1,3,7], seq2 [4]; dead replays
    assert seq.tolist() == [0, 0, 1, 1, 1, 2, 2, 2, 2]
    assert blk.tolist() == [5, 2, 1, 3, 7, 4, 4, 4, 4]
    assert first.tolist() == [1, 0, 1, 0, 0, 1, 0, 0, 0]
    assert last.tolist() == [0, 1, 0, 0, 1, 1, 0, 0, 0]
    stats = paged_schedule_stats(lens, tables, 9, BS)
    assert stats["live_steps"] == 6 and stats["dead_steps"] == 3


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
