"""Pallas kernel numerics vs XLA references (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.nn.functional.attention import _xla_sdpa
from paddle_tpu.ops.flash_attention import flash_attention_bshd
from paddle_tpu.ops.rms_norm import fused_rms_norm
from paddle_tpu.ops.rope import apply_rope, build_rope_cache


@pytest.fixture
def qkv():
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 256, 4, 64
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward(qkv, causal):
    q, k, v = qkv
    o = flash_attention_bshd(q, k, v, causal=causal)
    ref = _xla_sdpa(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


def test_flash_backward(qkv):
    q, k, v = qkv
    gf = jax.grad(lambda *a: (flash_attention_bshd(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (_xla_sdpa(*a, is_causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_flash_gqa(qkv):
    q, k, v = qkv
    kg, vg = k[:, :, :2], v[:, :, :2]
    o = flash_attention_bshd(q, kg, vg, causal=True)
    ref = _xla_sdpa(q, kg, vg, is_causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


def test_rms_norm_fwd_bwd():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 512), jnp.float32)
    w = jnp.asarray(rng.randn(512), jnp.float32)
    out = fused_rms_norm(x, w)
    ref = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True)
                                  + 1e-6) * np.asarray(w)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    ref_fn = lambda x: (x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True)
                                          + 1e-6) * w).sum()
    gx = jax.grad(lambda x: fused_rms_norm(x, w).sum())(x)
    gx_ref = jax.grad(ref_fn)(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref), atol=1e-5)
    gw = jax.grad(lambda w_: fused_rms_norm(x, w_).sum())(w)
    gw_ref = jax.grad(lambda w_: (x * jax.lax.rsqrt(
        (x ** 2).mean(-1, keepdims=True) + 1e-6) * w_).sum())(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref), atol=1e-4)


def test_rope_properties():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 64, 4, 32), jnp.float32)
    cos, sin = build_rope_cache(64, 32)
    qr = apply_rope(q, cos, sin)
    # rotation preserves norms
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(qr, axis=-1)),
                               np.asarray(jnp.linalg.norm(q, axis=-1)),
                               atol=1e-4)
    # position 0 is the identity
    np.testing.assert_allclose(np.asarray(qr[:, 0]), np.asarray(q[:, 0]),
                               atol=1e-6)
    # relative property: scores depend only on distance
    k = jnp.asarray(rng.randn(2, 64, 4, 32), jnp.float32)
    kr = apply_rope(k, cos, sin)
    s1 = float((qr[0, 10, 0] * kr[0, 5, 0]).sum())
    # shift both positions by 7
    q2 = jnp.roll(jnp.zeros_like(q).at[:, 10].set(q[:, 10]), 7, axis=1)
    # simpler: recompute with shifted caches
    cos2, sin2 = build_rope_cache(64, 32, position_ids=jnp.arange(64) + 7)
    qr2 = apply_rope(q, cos2, sin2)
    kr2 = apply_rope(k, cos2, sin2)
    s2 = float((qr2[0, 10, 0] * kr2[0, 5, 0]).sum())
    np.testing.assert_allclose(s1, s2, rtol=1e-4)


def test_ring_attention_matches_dense():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.parallel.ring_attention import ring_attention
    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, axis_names=("sep",))
    rng = np.random.RandomState(3)
    B, S, H, D = 2, 128, 4, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    fn = shard_map(lambda q, k, v: ring_attention(q, k, v, causal=True),
                   mesh=mesh,
                   in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
                   out_specs=P(None, "sep"), check_vma=False)
    out = fn(q, k, v)
    ref = _xla_sdpa(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_ulysses_matches_dense():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.parallel.ring_attention import ulysses_attention
    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, axis_names=("sep",))
    rng = np.random.RandomState(4)
    B, S, H, D = 2, 128, 4, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    fn = shard_map(lambda q, k, v: ulysses_attention(q, k, v, causal=True),
                   mesh=mesh,
                   in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
                   out_specs=P(None, "sep"), check_vma=False)
    out = fn(q, k, v)
    ref = _xla_sdpa(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("s,sk", [(300, 300), (1500, 1500), (384, 640)])
def test_flash_ragged_lengths(s, sk):
    """Sequence lengths that are not block multiples: zero-pad + mask path
    (regression: clamped pl.ds slices silently double-counted rows)."""
    rng = np.random.RandomState(5)
    B, H, D = 1, 2, 64
    q = jnp.asarray(rng.randn(B, s, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, sk, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, sk, H, D), jnp.float32)
    causal = s == sk
    o = flash_attention_bshd(q, k, v, causal=causal)
    ref = _xla_sdpa(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)

    if causal:
        gf = jax.grad(lambda *a: (flash_attention_bshd(*a, causal=True)
                                  ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: (_xla_sdpa(*a, is_causal=True) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3)
