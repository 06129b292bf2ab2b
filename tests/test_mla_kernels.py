"""The latent-attention (MLA) paged kernels against their XLA oracle, in the
interpreter on tiny shapes: across block boundaries, at several context
lengths, with fragmented tables, and the decode's in-place column write;
one walk made before the layers against a schedule made in every layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.paged_attention import (_LIVE, _LOG2E, _W_FIELDS,
                                            mla_paged_attention_xla,
                                            mla_paged_decode,
                                            mla_paged_prefill,
                                            mla_update_walk, paged_schedule)

L, NP, BS, RANK, ROPE, NH = 2, 12, 16, 32, 8, 4
W = RANK + ROPE
SCALE = 0.21


def _pool(rng):
    return jnp.asarray(rng.standard_normal((L, NP, W, BS)), jnp.bfloat16)


def _tables(rng, b, max_nb):
    """Fragmented tables: each row its own permutation of blocks 1.."""
    ids = rng.permutation(np.arange(1, NP))
    assert b * max_nb <= NP - 1 or b == 1
    return jnp.asarray(np.stack([ids[r * max_nb:(r + 1) * max_nb]
                                 for r in range(b)]).astype(np.int32))


@pytest.mark.parametrize("positions", [[0, 15, 16], [31, 47, 5], [17, 17, 32]])
def test_decode_matches_oracle_and_writes_the_column(positions):
    rng = np.random.default_rng(sum(positions))
    b, max_nb = len(positions), 3
    pool = _pool(rng)
    tables = _tables(rng, b, max_nb)
    pos = jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, NH, W)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((b, W)), jnp.bfloat16)
    qs = (q.astype(jnp.float32) * (SCALE * _LOG2E)).astype(q.dtype)
    out, pool2 = mla_paged_decode(qs, new, pool,
                                  mla_update_walk(tables, pos, BS), 1,
                                  rank=RANK)
    # the pool differs from the old one in the new columns of layer 1 only
    want = np.asarray(pool, np.float32).copy()
    for r, p in enumerate(positions):
        want[1, int(tables[r, p // BS]), :, p % BS] = np.asarray(
            new[r], np.float32)
    np.testing.assert_array_equal(np.asarray(pool2, np.float32), want)
    ref = mla_paged_attention_xla(qs.astype(jnp.float32) / _LOG2E / SCALE,
                                  pool2, tables, pos + 1, 1, SCALE, RANK)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("start, n_live", [(0, 16), (0, 9), (16, 16),
                                           (23, 16), (40, 5)])
def test_prefill_matches_oracle(start, n_live):
    rng = np.random.default_rng(start + n_live)
    c, max_nb = 16, 5
    pool = _pool(rng)
    table = _tables(rng, 1, max_nb)[0]
    q = jnp.asarray(rng.standard_normal((c, NH, W)), jnp.bfloat16)
    qs = (q.astype(jnp.float32) * (SCALE * _LOG2E)).astype(q.dtype)
    out = mla_paged_prefill(qs, pool, table, jnp.int32(start),
                            jnp.int32(n_live), 0, rank=RANK)
    assert out.shape == (c, NH, RANK) and out.dtype == q.dtype
    lengths = start + 1 + jnp.arange(c)
    ref = mla_paged_attention_xla(
        qs.astype(jnp.float32) / _LOG2E / SCALE, pool,
        jnp.broadcast_to(table, (c, max_nb)), lengths, 0, SCALE, RANK)
    np.testing.assert_allclose(np.asarray(out[:n_live], np.float32),
                               np.asarray(ref[:n_live]),
                               rtol=3e-2, atol=3e-2)
    assert np.isfinite(np.asarray(out, np.float32)).all()


@pytest.mark.parametrize("positions", [[0, 15, 16], [31, 0, 0], [17, 17, 32]])
def test_one_walk_for_every_layer_is_the_schedule_made_in_each(positions):
    """A step makes the batch's walk once and hands it to every layer's
    call. Until PR 34 each call made ``paged_schedule(positions + 1, ...)``
    and its live total itself: the same numbers, so outputs and pool are the
    same to the bit, padding rows (null block, position 0) among them."""
    rng = np.random.default_rng(7 + sum(positions))
    b, max_nb = len(positions), 3
    pool = _pool(rng)
    tables = np.array(_tables(rng, b, max_nb))
    tables[np.asarray(positions) == 0] = 0          # padding rows
    tables, pos = jnp.asarray(tables), jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.standard_normal((L, b, NH, W)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((L, b, W)), jnp.bfloat16)

    def each_layers_own():
        sched = paged_schedule(pos + 1, tables, b * max_nb, BS)
        return (sched[jnp.asarray(_W_FIELDS)],
                jnp.sum(sched[_LIVE], dtype=jnp.int32))

    def layers(pool, walk_of):
        outs = []
        for layer in range(L):
            o, pool = mla_paged_decode(q[layer], new[layer], pool, walk_of(),
                                       layer, rank=RANK)
            outs.append(o)
        return jnp.stack(outs), pool

    def made_once(pool):
        walk = mla_update_walk(tables, pos, BS)
        return layers(pool, lambda: walk)

    want_o, want_pool = jax.jit(lambda p: layers(p, each_layers_own))(pool)
    got_o, got_pool = jax.jit(made_once)(pool)
    live = np.asarray(positions) > 0
    np.testing.assert_array_equal(np.asarray(got_o)[:, live],
                                  np.asarray(want_o)[:, live])
    np.testing.assert_array_equal(np.asarray(got_pool, np.float32),
                                  np.asarray(want_pool, np.float32))
