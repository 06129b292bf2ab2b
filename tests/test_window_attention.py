"""Sliding-window attention over a ring of blocks per sequence
(ops/paged_attention.py ``window_attend_update``,
``window_prefill_attention``), against their plain-XLA oracles, in
interpret mode on the CPU.

A window layer's query at position p sees the keys p - window < j <= p; a
sequence keeps them in the ring of its slot, logical block b at ring block
b mod ring. Pinned here:

  * the fused decode update: at contexts of 1, 511, 512 and 513 and with a
    ring that has wrapped more than four times, windows on and off the
    block grid, groups of 6 and 9 query heads a KV head: the attention
    matches the oracle to float32 rounding, and the ring holds the new
    column where the oracle writes it and nothing else changed;
  * the chunk: starts on and off the block grid, partly live chunks, the
    same windows and groups, a ring that has wrapped;
  * the walks: a decode row visits only the blocks its window reaches, a
    chunk's tile starts at its first query's lowest key.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import _common
from paddle_tpu.ops import paged_attention as pa

NKV, HD, L, SLOTS = 2, 16, 2, 3
KVD = NKV * HD
LAYER = 1
# (window, block size, chunk): the published 512 on blocks of 128, and a
# window off the block grid on blocks of 8
GEOMETRIES = {"512on128": (512, 128, 128), "13on8": (13, 8, 32)}
GROUPS = {"6": 6, "9": 9}


@pytest.fixture(autouse=True)
def _interpret():
    with _common.interpret_mode(True):
        yield


def _pools(seed, ring, bs):
    """k and v pools of every slot's ring, filled with finite garbage: a
    stale block of another sequence reads like this."""
    rng = np.random.default_rng(seed)
    shape = (L, SLOTS * ring, KVD, bs)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def _block_diagonal(q):
    """q [B, NH, HD] -> [B, NH, NKV * HD], head h in its KV head's columns
    (what ``rows_attend`` hands the kernel)."""
    b, nh, hd = q.shape
    out = np.zeros((b, nh, NKV * hd), q.dtype)
    for h in range(nh):
        g = h // (nh // NKV)
        out[:, h, g * hd:(g + 1) * hd] = q[:, h]
    return out


def _wrapped(window, bs, chunk):
    """A position whose ring has wrapped more than four times."""
    return 4 * pa.ring_blocks(window, chunk, bs) * bs + 37


@pytest.mark.parametrize("group", list(GROUPS.values()), ids=list(GROUPS))
@pytest.mark.parametrize("geometry", list(GEOMETRIES.values()),
                         ids=list(GEOMETRIES))
@pytest.mark.parametrize("context", [1, 511, 512, 513, "wrapped"])
def test_decode_update_matches_the_oracle(context, geometry, group):
    window, bs, chunk = geometry
    ring = pa.ring_blocks(window, chunk, bs)
    p = _wrapped(window, bs, chunk) if context == "wrapped" else context - 1
    rng = np.random.default_rng(p + group)
    nh = NKV * group
    q = _block_diagonal(rng.normal(size=(3, nh, HD)).astype(np.float32))
    new_k = jnp.asarray(rng.normal(size=(3, KVD)), jnp.float32)
    new_v = jnp.asarray(rng.normal(size=(3, KVD)), jnp.float32)
    kp, vp = _pools(p, ring, bs)
    # the row under test in slot 1, another at a shorter context in slot 2,
    # a padding row at the null slot 0
    slots = jnp.asarray([1, 2, 0], jnp.int32)
    positions = jnp.asarray([p, max(p - 300, 0), 0], jnp.int32)
    walk = pa.window_update_walk(slots, positions, ring, bs, window)
    out, kp_u, vp_u = pa.window_attend_update(
        jnp.asarray(q * (pa._LOG2E / HD ** 0.5)), new_k, new_v, kp, vp,
        walk, LAYER, window)
    ref, kp_r, vp_r = pa.window_attention_xla(
        jnp.asarray(q), new_k, new_v, kp, vp, slots, ring, positions, LAYER,
        window, 1 / HD ** 0.5)
    np.testing.assert_allclose(np.asarray(out)[:2], np.asarray(ref)[:2],
                               rtol=2e-5, atol=2e-5)
    # the live slots' rings: the new column written, nothing else moved
    # (slot 0, the null slot, takes the padding row's scribble)
    for got, want in ((kp_u, kp_r), (vp_u, vp_r)):
        np.testing.assert_array_equal(np.asarray(got)[:, ring:],
                                      np.asarray(want)[:, ring:])


@pytest.mark.parametrize("group", list(GROUPS.values()), ids=list(GROUPS))
@pytest.mark.parametrize("geometry", list(GEOMETRIES.values()),
                         ids=list(GEOMETRIES))
@pytest.mark.parametrize("start,n_live", [
    (0, 1), (0, None), (3, 19), (511, 2), (512 - 5, 5), ("wrapped", None)],
    ids=["first_token", "first_chunk", "off_grid", "at_511", "to_512",
         "wrapped"])
def test_chunk_matches_the_oracle(start, n_live, geometry, group,
                                  monkeypatch):
    window, bs, chunk = geometry
    # query tiles of 16 on the small geometry: a tile of padding alone too
    monkeypatch.setattr(pa, "PREFILL_BLOCK_Q", min(chunk, 16 if bs < 128
                                                   else 128))
    ring = pa.ring_blocks(window, chunk, bs)
    if start == "wrapped":
        start = _wrapped(window, bs, chunk)
    n_live = chunk if n_live is None else n_live
    nh = NKV * group
    q = jnp.asarray(np.random.default_rng(start + group).normal(
        size=(chunk, nh, HD)), jnp.float32)
    kp, vp = _pools(start + 1, ring, bs)
    out = pa.window_prefill_attention(
        q, kp, vp, jnp.int32(2), ring, jnp.int32(start), jnp.int32(n_live),
        jnp.int32(LAYER), window)
    ref = pa.window_prefill_attention_xla(q, kp, vp, 2, ring, start, LAYER,
                                          window)
    out = np.asarray(out)
    assert out.shape == (chunk, nh, HD) and np.isfinite(out).all()
    np.testing.assert_allclose(out[:n_live], np.asarray(ref)[:n_live],
                               rtol=2e-5, atol=2e-5)


def test_a_ring_holds_the_window_behind_a_whole_chunk():
    # 511 keys behind the chunk's first query and 512 queries, one block
    # more for a start inside a block: 9 blocks of 128
    assert pa.ring_blocks(512, 512, 128) == 9
    assert pa.ring_blocks(16, 8, 4) == 7


def test_the_walks_visit_the_window_alone():
    ring, bs, window = 6, 8, 13
    sched, live = pa.window_update_walk(
        jnp.asarray([1, 2], jnp.int32), jnp.asarray([200, 5], jnp.int32),
        ring, bs, window)
    sched = np.asarray(sched)
    # row 0 at 200: positions 188 .. 200, logical blocks 23 .. 25, in ring
    # blocks 6 + (23, 24, 25) % 6; row 1 at 5: block 0 of slot 2
    assert int(live) == 4
    assert sched[pa._BLK, :4].tolist() == [6 + 5, 6 + 0, 6 + 1, 12]
    blk, tiles = pa.window_prefill_schedule(2, ring, 200, 10, 2, 8, bs,
                                            window)
    blk, tiles = np.asarray(blk), np.asarray(tiles)
    # tile 0: queries 200 .. 207, keys from 188: logical 23 .. 25; tile 1:
    # queries 208 and 209, keys from 196: logical 24 .. 26
    assert tiles.tolist() == [[200, 208], [3, 3], [23, 24]]
    assert blk[0, :3].tolist() == [12 + 5, 12 + 0, 12 + 1]
    assert blk[1, :3].tolist() == [12 + 0, 12 + 1, 12 + 2]
    # steps past a tile's last block present its last again: no data moves
    assert (blk[:, 3:] == blk[:, 2:3]).all()
