"""The latent-attention (MLA) paged kernels against their XLA oracle, in the
interpreter on tiny shapes: across block boundaries, at several context
lengths, with fragmented tables, and the decode's in-place column write."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.paged_attention import (_LOG2E, mla_paged_attention_xla,
                                            mla_paged_decode,
                                            mla_paged_prefill)

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
    out, pool2 = mla_paged_decode(qs, new, pool, tables, pos, 1, rank=RANK)
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
