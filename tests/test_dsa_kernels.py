"""Learned sparse attention's kernels against their XLA oracles, in the
interpreter on tiny shapes: the indexer's scores of a prefill chunk and of a
decode batch over a paged pool of index keys (fragmented tables, across
block boundaries, the decode's in-place key write), the exact selection
against ``lax.top_k`` as sets (ties too), and the latent kernels under a
selection against the oracle under the same one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.paged_attention import (
    _LOG2E, dsa_index_decode, dsa_index_prefill, dsa_index_xla, dsa_select,
    dsa_width, mla_paged_attention_xla, mla_paged_decode, mla_paged_prefill,
    mla_update_walk)

L, NP, BS, RANK, ROPE, NH = 2, 12, 16, 32, 8, 4
W = RANK + ROPE
HI, DI = 4, 16
SCALE = 0.21


def _pool(rng, width):
    return jnp.asarray(rng.standard_normal((L, NP, width, BS)), jnp.bfloat16)


def _tables(rng, b, max_nb):
    ids = rng.permutation(np.arange(1, NP))
    assert b * max_nb <= NP - 1 or b == 1
    return jnp.asarray(np.stack([ids[r * max_nb:(r + 1) * max_nb]
                                 for r in range(b)]).astype(np.int32))


def _random_select(rng, positions, t, k):
    """A 0/1 selection of min(position + 1, k) positions <= position a row."""
    sel = np.zeros((len(positions), t), np.float32)
    for r, p in enumerate(positions):
        sel[r, rng.permutation(p + 1)[:k]] = 1
    return jnp.asarray(sel, jnp.bfloat16)


@pytest.mark.parametrize("tied", [False, True])
@pytest.mark.parametrize("k", [1, 16, 40])
def test_selection_is_top_k_as_sets(k, tied):
    """Each row's selection is ``lax.top_k``'s set over the positions at or
    before its own: with fewer than k of them, all; among equal scores the
    lower positions (scores rounded to halves tie in dozens)."""
    rng = np.random.default_rng(k + tied)
    r, t = 9, 300
    sc = rng.standard_normal((r, t)).astype(np.float32)
    if tied:
        sc = np.round(sc * 2) / 2
    pos = rng.integers(0, t, r).astype(np.int32)
    pos[:3] = [0, 3, t - 1]
    # what lies past a row's position is whatever memory held
    junk = np.where(np.arange(t)[None] > pos[:, None], np.nan, sc)
    got = np.asarray(dsa_select(jnp.asarray(junk), jnp.asarray(pos), k,
                                jnp.float32))
    for i in range(r):
        n = int(pos[i]) + 1
        _, idx = lax.top_k(jnp.asarray(sc[i, :n]), min(k, n))
        want = np.zeros(t)
        want[np.asarray(idx)] = 1
        np.testing.assert_array_equal(got[i], want)


@pytest.mark.parametrize("start, n_live", [(0, 16), (0, 9), (16, 16),
                                           (23, 16), (40, 5)])
def test_prefill_index_scores_match_oracle(start, n_live):
    rng = np.random.default_rng(start + n_live)
    c, max_nb = 16, 5
    ipool = _pool(rng, DI)
    table = _tables(rng, 1, max_nb)[0]
    qi = jnp.asarray(rng.standard_normal((c, HI, DI)), jnp.bfloat16)
    wi = jnp.asarray(rng.standard_normal((c, HI)), jnp.float32)
    out = dsa_index_prefill(qi, wi, ipool, table, jnp.int32(start),
                            jnp.int32(n_live), 1)
    assert out.shape == (c, dsa_width(max_nb, BS))
    ref = np.asarray(dsa_index_xla(qi, wi, ipool,
                                   jnp.broadcast_to(table, (c, max_nb)), 1))
    for t in range(n_live):         # a row's scores up to its own position
        np.testing.assert_allclose(np.asarray(out[t, :start + t + 1]),
                                   ref[t, :start + t + 1],
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("positions", [[0, 15, 16], [31, 47, 5], [17, 17, 32]])
def test_decode_index_scores_match_oracle_and_write_the_key(positions):
    rng = np.random.default_rng(sum(positions))
    b, max_nb = len(positions), 3
    ipool = _pool(rng, DI)
    tables = _tables(rng, b, max_nb)
    pos = jnp.asarray(positions, jnp.int32)
    qi = jnp.asarray(rng.standard_normal((b, HI, DI)), jnp.bfloat16)
    wi = jnp.asarray(rng.standard_normal((b, HI)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((b, DI)), jnp.bfloat16)
    out, ipool2 = dsa_index_decode(qi, wi, new, ipool,
                                   mla_update_walk(tables, pos, BS), 1)
    want = np.asarray(ipool, np.float32).copy()
    for r, p in enumerate(positions):
        want[1, int(tables[r, p // BS]), :, p % BS] = np.asarray(
            new[r], np.float32)
    np.testing.assert_array_equal(np.asarray(ipool2, np.float32), want)
    assert out.shape == (b, 1, dsa_width(max_nb, BS))
    ref = np.asarray(dsa_index_xla(qi, wi, ipool2, tables, 1))
    for r, p in enumerate(positions):   # the new token's own score too
        np.testing.assert_allclose(np.asarray(out[r, 0, :p + 1]),
                                   ref[r, :p + 1], rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("k", [4, 64])
@pytest.mark.parametrize("positions", [[0, 15, 16], [31, 47, 5], [17, 17, 32]])
def test_decode_under_a_selection_matches_oracle(positions, k):
    """k = 64 selects every position: the kernel without a selection."""
    rng = np.random.default_rng(sum(positions) + k)
    b, max_nb = len(positions), 3
    pool = _pool(rng, W)
    tables = _tables(rng, b, max_nb)
    pos = jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, NH, W)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((b, W)), jnp.bfloat16)
    qs = (q.astype(jnp.float32) * (SCALE * _LOG2E)).astype(q.dtype)
    sel = _random_select(rng, positions, dsa_width(max_nb, BS), k)
    walk = mla_update_walk(tables, pos, BS)
    out, pool2 = mla_paged_decode(qs, new, pool, walk, 1, rank=RANK,
                                  select=sel[:, None])
    ref = mla_paged_attention_xla(qs.astype(jnp.float32) / _LOG2E / SCALE,
                                  pool2, tables, pos + 1, 1, SCALE, RANK,
                                  select=sel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    if k == 64:
        dense, _ = mla_paged_decode(qs, new, pool, walk, 1, rank=RANK)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))


@pytest.mark.parametrize("k", [4, 128])
@pytest.mark.parametrize("start, n_live", [(0, 16), (0, 9), (16, 16),
                                           (23, 16), (40, 5)])
def test_prefill_under_a_selection_matches_oracle(start, n_live, k):
    rng = np.random.default_rng(start + n_live + k)
    c, max_nb = 16, 5
    pool = _pool(rng, W)
    table = _tables(rng, 1, max_nb)[0]
    q = jnp.asarray(rng.standard_normal((c, NH, W)), jnp.bfloat16)
    qs = (q.astype(jnp.float32) * (SCALE * _LOG2E)).astype(q.dtype)
    positions = [start + i for i in range(c)]
    sel = _random_select(rng, positions, dsa_width(max_nb, BS), k)
    out = mla_paged_prefill(qs, pool, table, jnp.int32(start),
                            jnp.int32(n_live), 0, rank=RANK, select=sel)
    ref = mla_paged_attention_xla(
        qs.astype(jnp.float32) / _LOG2E / SCALE, pool,
        jnp.broadcast_to(table, (c, max_nb)), start + 1 + jnp.arange(c), 0,
        SCALE, RANK, select=sel)
    np.testing.assert_allclose(np.asarray(out[:n_live], np.float32),
                               np.asarray(ref[:n_live]),
                               rtol=3e-2, atol=3e-2)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    if k == 128:
        dense = mla_paged_prefill(qs, pool, table, jnp.int32(start),
                                  jnp.int32(n_live), 0, rank=RANK)
        np.testing.assert_allclose(np.asarray(out[:n_live], np.float32),
                                   np.asarray(dense[:n_live], np.float32),
                                   rtol=1e-2, atol=1e-2)
