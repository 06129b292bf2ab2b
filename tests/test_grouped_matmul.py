"""Dropless MoE grouped matmul (ops/grouped_matmul.py) parity suite.

The ragged path's whole claim is that it computes EXACTLY what the dense
per-expert einsum computes, just without capacity buckets: full-K blocks
mean each row's reduction order matches a plain XLA dot, so on the CPU
test mesh forward and dX are asserted BITWISE against the dense
reference across adversarial group layouts (empty experts, one hot
expert, non-tile-multiple counts). dW accumulates tiles in f32 scratch
in tile order -- same order as the dense dot's row reduction, asserted
tight-allclose."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from op_test import max_ulps

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.grouped_matmul import (TILE_ROWS, _round_up,
                                           grouped_matmul, tile_schedule)

TM = 8  # small row tile keeps interpret-mode tests fast; 128 in prod


def _layout(counts, tile_rows=TM, extra_tail_tiles=1):
    """Schedule + static geometry for a python-int group layout."""
    counts = np.asarray(counts, np.int32)
    aligned = np.asarray(_round_up(jnp.asarray(counts), tile_rows))
    offsets = np.concatenate([[0], np.cumsum(aligned)]).astype(np.int64)
    m = int(offsets[-1]) + extra_tail_tiles * tile_rows
    sched = tile_schedule(jnp.asarray(counts), m // tile_rows, tile_rows)
    return counts, offsets, m, sched[:4], sched[4]


def _dense_ref(lhs, rhs, offsets, m):
    """Per-group dense dots at the same row positions (jnp: bitwise ref)."""
    E = rhs.shape[0]
    ref = jnp.zeros((m, rhs.shape[2]),
                    jnp.promote_types(lhs.dtype, rhs.dtype))
    for e in range(E):
        o0, o1 = int(offsets[e]), int(offsets[e + 1])
        if o1 > o0:
            ref = ref.at[o0:o1].set(lhs[o0:o1] @ rhs[e])
    return ref


LAYOUTS = [
    ("empty_experts", [0, 3, 0, 5]),        # empty groups + ragged counts
    ("all_one_expert", [20, 0, 0, 0]),      # worst-case skew
    ("non_tile_multiple", [5, 11, 7, 13]),  # every group needs a pad tile
    ("tile_aligned", [8, 16, 8, 8]),
    ("eight_experts", [0, 9, 1, 0, 24, 3, 0, 8]),
]


@pytest.mark.parametrize("name,counts", LAYOUTS, ids=[l[0] for l in LAYOUTS])
def test_gmm_forward_bitwise_vs_dense(name, counts):
    rng = np.random.RandomState(0)
    counts, offsets, m, sched, _ = _layout(counts)
    E, K, N = len(counts), 16, 8
    lhs = jnp.asarray(rng.randn(m, K).astype(np.float32))
    rhs = jnp.asarray(rng.randn(E, K, N).astype(np.float32))
    out = grouped_matmul(lhs, rhs, sched, TM)
    ref = _dense_ref(lhs, rhs, offsets, m)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # dead-tail rows come back exactly zero
    assert (np.asarray(out)[int(offsets[-1]):] == 0).all()


@pytest.mark.parametrize("name,counts",
                         [LAYOUTS[0], LAYOUTS[1], LAYOUTS[4]],
                         ids=[LAYOUTS[0][0], LAYOUTS[1][0], LAYOUTS[4][0]])
def test_gmm_grads_match_dense(name, counts):
    """dX is full-K dots (bitwise); dW accumulates f32 tiles in row order
    (tight allclose). Empty groups must get EXACT zero dW -- their output
    block is never presented to the kernel."""
    rng = np.random.RandomState(1)
    counts, offsets, m, sched, _ = _layout(counts)
    E, K, N = len(counts), 16, 8
    lhs = jnp.asarray(rng.randn(m, K).astype(np.float32))
    rhs = jnp.asarray(rng.randn(E, K, N).astype(np.float32))
    cot = jnp.asarray(rng.randn(m, N).astype(np.float32))

    def f(a, w):
        return (grouped_matmul(a, w, sched, TM) * cot).sum()

    def f_ref(a, w):
        return (_dense_ref(a, w, offsets, m) * cot).sum()

    gx, gw = jax.grad(f, argnums=(0, 1))(lhs, rhs)
    rx, rw = jax.grad(f_ref, argnums=(0, 1))(lhs, rhs)
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(rx))
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-5, atol=1e-5)
    for e in range(E):
        if counts[e] == 0:
            assert (np.asarray(gw)[e] == 0).all(), f"expert {e} dW not zero"


def test_tile_schedule_flags():
    counts, offsets, m, (expert, live, first, last), off = _layout(
        [0, 3, 0, 5], extra_tail_tiles=2)
    # offsets: [0, 0, 8, 8, 16]; 4 tiles total (2 live + 2 dead tail)
    assert list(np.asarray(off)) == [0, 0, 8, 8, 16]
    assert list(np.asarray(expert))[:2] == [1, 3]
    assert list(np.asarray(live)) == [1, 1, 0, 0]
    assert list(np.asarray(first)) == [1, 1, 0, 0]
    assert list(np.asarray(last)) == [1, 1, 0, 0]
    # a 3-tile group gets first only on its head, last only on its tail
    _, _, _, (e2, lv2, f2, l2), off2 = _layout([24], extra_tail_tiles=0)
    assert list(np.asarray(f2)) == [1, 0, 0]
    assert list(np.asarray(l2)) == [0, 0, 1]


def test_gmm_rejects_ragged_buffer():
    sched = tuple(jnp.zeros((1,), jnp.int32) for _ in range(4))
    with pytest.raises(AssertionError):
        grouped_matmul(jnp.zeros((TM + 1, 8)), jnp.zeros((1, 8, 8)),
                       sched, TM)


def test_gmm_default_tile_is_mxu_sized():
    assert TILE_ROWS == 128


# ---------------------------------------------------------------------------
# Dropless MoE layer built on the kernel
# ---------------------------------------------------------------------------

def _ragged_moe_ref(x, logits, w1, w2, k):
    """Dense einsum reference: every expert computes every token, the
    router's top-k renormalized weights pick. Same jnp ops as the ragged
    path's routing so weights are bitwise; expert compute runs as plain
    dense matmuls."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    denom = jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    weight = gates / denom * gates.sum(-1, keepdims=True)
    ys = jnp.stack([jax.nn.gelu(x @ w1[e]) @ w2[e]
                    for e in range(w1.shape[0])])          # [E, T, D]
    picked = ys[experts, jnp.arange(x.shape[0])[:, None]]  # [T, k, D]
    return jnp.einsum("tk,tkd->td", weight, picked)


@pytest.mark.parametrize("E,k", [(4, 1), (8, 2)])
def test_ragged_moe_bitwise_vs_dense_einsum(E, k):
    """THE acceptance property: the dropless path equals the dense einsum
    reference on the CPU mesh (full-K row dots, verbatim weight formula,
    gather-only dispatch) up to the backend's accumulation order: jaxlib
    0.9.0's XLA:CPU runs the [tile, K] row dots and the [T, K] einsum in
    different orders, measured <= 2.62 ULPs of the output's largest
    magnitude; a misrouted or dropped token is off by its whole value."""
    from paddle_tpu.parallel.moe import moe_ragged_dispatch_combine
    rng = np.random.RandomState(2)
    T, D, I = 96, 16, 32
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    logits = logits.at[:, 0].add(1.5)   # skew: would drop under capacity
    w1 = jnp.asarray(rng.randn(E, D, I).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.randn(E, I, D).astype(np.float32) * 0.1)
    out, aux = moe_ragged_dispatch_combine(x, logits, w1, w2, E, k=k,
                                           tile_rows=8)
    ref = _ragged_moe_ref(x, logits, w1, w2, k)
    assert max_ulps(out, ref) <= 8
    assert float(aux) > 0


def test_ragged_matches_no_drop_capacity_bitwise():
    """With capacity high enough that nothing drops, the slot-schedule
    capacity path and the ragged path are the same math in different
    buffers: outputs and aux losses must agree bitwise."""
    from paddle_tpu.parallel.moe import (moe_dispatch_combine,
                                         moe_ragged_dispatch_combine)
    rng = np.random.RandomState(3)
    T, D, I, E, k = 128, 16, 32, 4, 2
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    w1 = jnp.asarray(rng.randn(E, D, I).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.randn(E, I, D).astype(np.float32) * 0.1)

    def expert_fn(params, toks):
        a, b = params
        return jax.nn.gelu(toks @ a) @ b

    out_cap, aux_cap = moe_dispatch_combine(x, logits, expert_fn, (w1, w2),
                                            E, k=k, capacity_factor=8.0)
    out_rag, aux_rag = moe_ragged_dispatch_combine(x, logits, w1, w2, E, k=k)
    np.testing.assert_array_equal(np.asarray(out_rag), np.asarray(out_cap))
    np.testing.assert_array_equal(np.asarray(aux_rag), np.asarray(aux_cap))


def test_ragged_grads_flow_to_router_and_experts():
    from paddle_tpu.parallel.moe import moe_ragged_dispatch_combine
    rng = np.random.RandomState(4)
    T, D, I, E, k = 32, 8, 8, 4, 2
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    w1 = jnp.asarray(rng.randn(E, D, I).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.randn(E, I, D).astype(np.float32) * 0.1)

    def loss(x, logits, w1, w2):
        out, aux = moe_ragged_dispatch_combine(x, logits, w1, w2, E, k=k,
                                               tile_rows=8)
        return (out ** 2).sum() + aux

    gs = jax.grad(loss, argnums=(0, 1, 2, 3))(x, logits, w1, w2)
    for g in gs:
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).max() > 0


def test_ragged_routing_stats_dropless_contract():
    """Dropless stats: drops are an EXPLICIT zero (no fabricated capacity
    number), routed == T*k always, and live/padded split the tile-aligned
    buffer exactly; per-expert rows sum to the routed count."""
    from paddle_tpu.parallel.moe import moe_ragged_dispatch_combine
    rng = np.random.RandomState(5)
    T, D, I, E, k, tm = 100, 8, 16, 4, 2, 8
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    logits = logits.at[:, 1].add(3.0)   # heavy skew: capacity would drop
    w1 = jnp.asarray(rng.randn(E, D, I).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.randn(E, I, D).astype(np.float32) * 0.1)
    out, aux, st = moe_ragged_dispatch_combine(x, logits, w1, w2, E, k=k,
                                               tile_rows=tm,
                                               return_stats=True)
    assert float(st["moe_dropped_tokens"]) == 0.0
    assert float(st["moe_routed_tokens"]) == T * k
    assert float(st["moe_live_rows"]) == T * k
    assert st["moe_expert_rows"].shape == (E,)
    assert float(st["moe_expert_rows"].sum()) == T * k
    # alignment padding is bounded by one tile per expert -- the dropless
    # waste bound that replaces the capacity factor
    assert 0 <= float(st["moe_padded_rows"]) <= E * (tm - 1)
    assert "moe_capacity_util" not in st   # vacuous under dropless
    assert float(st["moe_load_imbalance"]) > 1.0  # skewed router


def test_dispatch_mode_env_default(monkeypatch):
    from paddle_tpu.parallel import moe as moe_mod
    monkeypatch.delenv("PADDLE_TPU_MOE_DROPLESS", raising=False)
    assert moe_mod.default_dispatch_mode() == "capacity"
    monkeypatch.setenv("PADDLE_TPU_MOE_DROPLESS", "1")
    assert moe_mod.default_dispatch_mode() == "ragged"
    monkeypatch.setenv("PADDLE_TPU_MOE_DROPLESS", "0")
    assert moe_mod.default_dispatch_mode() == "capacity"
    with pytest.raises(ValueError):
        moe_mod.moe_dispatch_combine(
            jnp.zeros((8, 4)), jnp.zeros((8, 2)),
            lambda w, t: t, (jnp.zeros((2, 4, 4)),) * 2, 2,
            dispatch_mode="bogus")
