"""Comm–compute overlap parity: the overlapped TP/DP/PP paths must match the
blocking paths on the virtual CPU mesh (mp=2, dp=2, pp=2 — the acceptance
bar): BIT-FOR-BIT where both sides run the same dots (DP buckets, PP
double-buffering, hop sub-tiling), and within a stated ULP bound where the
ring splits a dot the blocking form runs whole. jaxlib 0.9.0's XLA:CPU no
longer gives two differently shaped dots one accumulation order, so those
pairs (ring vs blocking matmul, fused FFN) differ by a few ULPs of
re-association; RING_ULPS/FFN_ULPS below carry the measurements. The mp>2
ring all-reduce keeps its documented fp tolerance (it re-associates the
partial-sum order; see parallel/collective_matmul.py docstring)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from op_test import max_ulps

from paddle_tpu.parallel import collective_matmul as cm
from paddle_tpu.parallel.pipeline import (last_stage_value, microbatch,
                                          pipeline_apply, stack_stage_params)

needs_devices = pytest.mark.skipif(
    len(jax.devices("cpu")) < 4, reason="needs >=4 virtual devices")


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(x, y) for x, y in zip(la, lb))


# Ring vs blocking collective matmul, loss + output + both grads, in ULPs
# of each leaf's largest magnitude (op_test.max_ulps). Measured on jaxlib
# 0.9.0 XLA:CPU: the [64, K] @ [K, N] outputs differ by <= 3, the loss by
# <= 9, and the grads (the output's error through d/do[o cos o] and one more
# K<=96 contraction) by <= 26.25. A dropped hop or a wrong chunk is off by
# ULPs in the millions.
RING_ULPS = 64
# fused column->swiglu->row island vs its blocking twin at mp=2: <= 2.5
FFN_ULPS = 8


# ---------------------------------------------------------------------------
# TP: ring collective matmuls vs fused collectives
# ---------------------------------------------------------------------------

def _tp_loss_grads(kernel, mesh, n, in_specs, x, w):
    f = shard_map(lambda a, b: kernel(a, b, n, "mp"), mesh=mesh,
                  in_specs=in_specs, out_specs=P(),
                  axis_names=frozenset(["mp"]), check_vma=False)

    def loss(a, b):
        o = f(a, b)
        return jnp.sum(o * jnp.cos(o)), o

    (l, o), g = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(x, w)
    return (np.asarray(l), np.asarray(o),
            jax.tree_util.tree_map(np.asarray, g))


@needs_devices
@pytest.mark.parametrize("mp", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_ring_allgather_matmul_bitwise(mp):
    """Column-parallel chunked-pipeline gather: no cross-rank reduction
    (every element computed once on its owner), so ring and blocking differ
    only by the backend's accumulation order inside differently shaped
    dots: within RING_ULPS at ANY degree."""
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(0)
    t, k, out = 64, 32, 48 * mp
    x = jnp.asarray(rng.randn(t, k), jnp.float32)
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    specs = (P(), P(None, "mp"))
    ring = _tp_loss_grads(cm.ring_allgather_matmul, mesh, mp, specs, x, w)
    blk = _tp_loss_grads(cm.blocking_allgather_matmul, mesh, mp, specs, x, w)
    assert max_ulps(ring, blk) <= RING_ULPS


@needs_devices
@pytest.mark.parametrize("mp", [2])
def test_ring_allreduce_matmul_bitwise_mp2(mp):
    """Row-parallel reduce-scatter ring: at mp=2 the ring reduction is a
    two-term sum, so forward AND backward match the fused psum up to the
    backend's accumulation order inside the dots (RING_ULPS)."""
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(1)
    t, k, out = 64, 32 * mp, 48
    x = jax.device_put(jnp.asarray(rng.randn(t, k), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P("mp", None)))
    specs = (P(None, "mp"), P("mp", None))
    ring = _tp_loss_grads(cm.ring_allreduce_matmul, mesh, mp, specs, x, w)
    blk = _tp_loss_grads(cm.blocking_allreduce_matmul, mesh, mp, specs, x, w)
    assert max_ulps(ring, blk) <= RING_ULPS


@needs_devices
@pytest.mark.slow
def test_ring_allreduce_matmul_mp4_tolerance():
    """mp>2 re-associates the partial-sum order: fp tolerance, not bitwise."""
    mp = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(2)
    t, k, out = 64, 32 * mp, 48
    x = jax.device_put(jnp.asarray(rng.randn(t, k), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P("mp", None)))
    specs = (P(None, "mp"), P("mp", None))
    ring = _tp_loss_grads(cm.ring_allreduce_matmul, mesh, mp, specs, x, w)
    blk = _tp_loss_grads(cm.blocking_allreduce_matmul, mesh, mp, specs, x, w)
    # the test loss's cos/sin backward amplifies the reassociation delta by
    # |o| (~30x at these magnitudes); 1e-3 still separates a real schedule
    # bug (the pre-fix wrong ring order was off by ~79 absolute) from fp
    # reassociation noise
    for r, b in zip(jax.tree_util.tree_leaves(ring),
                    jax.tree_util.tree_leaves(blk)):
        np.testing.assert_allclose(r, b, rtol=1e-3, atol=1e-3)


@needs_devices
def test_plan_gates_fall_back_to_fused():
    mesh2 = Mesh(np.array(jax.devices("cpu")[:2]), ("mp",))
    mesh1 = Mesh(np.array(jax.devices("cpu")[:1]), ("mp",))
    os.environ[cm.ENV_MIN_CHUNK] = "16"
    try:
        # viable: chunks >= min_chunk
        assert cm.plan_column_parallel((64, 32), (32, 64), mesh2) is not None
        assert cm.plan_row_parallel((64, 32), (32, 64), mesh2) is not None
        # mp == 1
        assert cm.plan_column_parallel((64, 32), (32, 64), mesh1) is None
        # sub-MXU chunk: 8 cols/shard < min_chunk
        assert cm.plan_column_parallel((64, 32), (32, 16), mesh2) is None
        # indivisible contraction dim
        assert cm.plan_row_parallel((64, 31), (31, 64), mesh2) is None
    finally:
        del os.environ[cm.ENV_MIN_CHUNK]


@needs_devices
def test_tp_overlap_flag_flips_layer_path(monkeypatch):
    """PADDLE_TPU_TP_OVERLAP=1 must route Column/RowParallelLinear through
    the ring kernels (plan non-None); off must keep the fused path."""
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers import \
        mp_layers
    mesh = Mesh(np.array(jax.devices("cpu")[:2]).reshape(1, 2), ("dp", "mp"))
    from paddle_tpu.distributed import sharding_utils

    class FakeTensor:
        shape = (4, 16, 32)

    class FakeW:
        shape = (32, 64)

    monkeypatch.setenv(cm.ENV_OVERLAP, "0")
    with sharding_utils.auto_shard(mesh):
        assert mp_layers._overlap_plan("column", FakeTensor, FakeW) is None
    monkeypatch.setenv(cm.ENV_OVERLAP, "1")
    monkeypatch.setenv(cm.ENV_MIN_CHUNK, "4")
    with sharding_utils.auto_shard(mesh):
        assert mp_layers._overlap_plan("column", FakeTensor, FakeW) \
            is not None
        assert mp_layers._overlap_plan("row", FakeTensor, FakeW) is not None
    # no mesh active -> fused
    assert mp_layers._overlap_plan("column", FakeTensor, FakeW) is None


# ---------------------------------------------------------------------------
# DP: explicit/bucketed grad sync vs GSPMD auto
# ---------------------------------------------------------------------------

def _dp_step(grad_sync, bucket_mb=None):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    paddle.set_device("cpu")
    paddle.seed(11)
    model = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
    opt = AdamW(learning_rate=1e-2, parameters=model.parameters(),
                weight_decay=0.01)
    mesh = Mesh(np.array(jax.devices("cpu")[:2]).reshape(2, 1), ("dp", "mp"))
    step = TrainStep(model, lambda o, l: paddle.mean((o - l) ** 2), opt,
                     mesh=mesh, batch_spec=P("dp"), grad_sync=grad_sync,
                     grad_bucket_mb=bucket_mb)
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
    losses = [float(step(x, labels=y)) for _ in range(3)]
    step.sync_to_model()
    params = {k: np.asarray(p._data) for k, p in model.named_parameters()}
    return step, losses, params


@needs_devices
def test_dp_bucketed_equals_explicit_bitwise():
    """Bucketing only changes collective granularity (psum is elementwise):
    bucketed grads == per-param explicit grads bit-for-bit at dp=2."""
    step_e, losses_e, params_e = _dp_step("explicit")
    step_b, losses_b, params_b = _dp_step("bucketed", bucket_mb=0.001)
    assert step_e.grad_sync_mode == "explicit"
    assert step_b.grad_sync_mode == "bucketed"
    assert len(step_b.grad_buckets) > 1  # cap actually split the params
    assert losses_e == losses_b
    assert _leaves_equal(params_e, params_b)


@needs_devices
@pytest.mark.slow
def test_dp_explicit_matches_auto():
    """The explicit island must reproduce the GSPMD auto path numerics."""
    _, losses_a, params_a = _dp_step(None)
    _, losses_e, params_e = _dp_step("explicit")
    np.testing.assert_allclose(losses_e, losses_a, rtol=1e-5)
    for k in params_a:
        np.testing.assert_allclose(params_e[k], params_a[k],
                                   rtol=1e-4, atol=1e-6)


def test_bucket_planning():
    from paddle_tpu.distributed.sharding_utils import plan_grad_buckets
    shapes = {f"p{i}": ((4, 4), 4) for i in range(6)}  # 64B each
    # reverse-topological (grads-ready-first) order, 128B cap -> pairs
    assert plan_grad_buckets(shapes, 128) == [
        ["p5", "p4"], ["p3", "p2"], ["p1", "p0"]]
    # oversized grad gets its own bucket
    shapes["big"] = ((100, 100), 4)
    assert plan_grad_buckets(shapes, 128)[0] == ["big"]


def test_bucket_planning_edge_cases():
    from paddle_tpu.distributed.sharding_utils import (bucket_bytes,
                                                       plan_grad_buckets)
    # a single oversized grad is its own (only) bucket, not dropped
    only_big = {"w": ((1000, 1000), 4)}
    assert plan_grad_buckets(only_big, 128) == [["w"]]
    assert bucket_bytes(only_big, [["w"]]) == [4_000_000]
    # empty shapes dict -> no buckets (and bucket_bytes agrees)
    assert plan_grad_buckets({}, 128) == []
    assert bucket_bytes({}, []) == []
    # reverse=False walks FORWARD (param-creation) order — the stage-3
    # param-gather prefetch planning order
    fwd = {f"p{i}": ((4, 4), 4) for i in range(4)}
    assert plan_grad_buckets(fwd, 128, reverse=False) == [
        ["p0", "p1"], ["p2", "p3"]]
    # zero-dim (scalar) params: 0 dims -> itemsize bytes, packed normally
    scalars = {"s0": ((), 4), "s1": ((), 4), "s2": ((), 4)}
    assert plan_grad_buckets(scalars, 8, reverse=False) == [
        ["s0", "s1"], ["s2"]]
    assert bucket_bytes(scalars, [["s0", "s1"], ["s2"]]) == [8, 4]


# ---------------------------------------------------------------------------
# Chunked per-hop ring tiles (mp>2) + the PR-3 overlap surfaces
# ---------------------------------------------------------------------------

def _tp_loss_grads_chunked(kernel, mesh, n, in_specs, x, w, nchunks):
    import functools
    f = shard_map(functools.partial(kernel, n=n, axis_name="mp",
                                    nchunks=nchunks),
                  mesh=mesh, in_specs=in_specs, out_specs=P(),
                  axis_names=frozenset(["mp"]), check_vma=False)

    def loss(a, b):
        o = f(a, b)
        return jnp.sum(o * jnp.cos(o)), o

    (l, o), g = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(x, w)
    return (np.asarray(l), np.asarray(o),
            jax.tree_util.tree_map(np.asarray, g))


@needs_devices
@pytest.mark.parametrize("nchunks", [2, 4])
def test_chunked_allreduce_ring_bitwise_vs_unchunked(nchunks):
    """Hop sub-tiling splits transfer granularity only (disjoint row slices
    reassembled by concat): chunked == unchunked BIT-FOR-BIT at mp=4,
    forward and backward."""
    mp = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(4)
    t, k, out = 64, 32 * mp, 48
    x = jax.device_put(jnp.asarray(rng.randn(t, k), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P("mp", None)))
    specs = (P(None, "mp"), P("mp", None))
    un = _tp_loss_grads_chunked(cm.ring_allreduce_matmul, mesh, mp, specs,
                                x, w, 1)
    ch = _tp_loss_grads_chunked(cm.ring_allreduce_matmul, mesh, mp, specs,
                                x, w, nchunks)
    assert _leaves_equal(un, ch)


@needs_devices
def test_chunked_allgather_ring_bitwise_vs_blocking():
    """The all-gather ring has no cross-rank reduction: chunked stays
    within RING_ULPS of the FUSED all-gather at mp=4 (forward and
    backward)."""
    mp = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(5)
    t, k, out = 64, 32, 48 * mp
    x = jnp.asarray(rng.randn(t, k), jnp.float32)
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    specs = (P(), P(None, "mp"))
    ch = _tp_loss_grads_chunked(cm.ring_allgather_matmul, mesh, mp, specs,
                                x, w, 4)
    blk = _tp_loss_grads(cm.blocking_allgather_matmul, mesh, mp, specs, x, w)
    assert max_ulps(ch, blk) <= RING_ULPS


@needs_devices
def test_mp2_ring_stays_unchunked_and_bitwise():
    """resolve_chunks pins mp<=2 to one tile per hop, and the mp=2 ring
    (the RING_ULPS-vs-blocking contract) is unaffected by the chunk knob."""
    assert cm.resolve_chunks(2, 4096) == 1
    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("mp",))
    rng = np.random.RandomState(6)
    t, k, out = 64, 64, 48
    x = jax.device_put(jnp.asarray(rng.randn(t, k), jnp.float32),
                       NamedSharding(mesh, P(None, "mp")))
    w = jax.device_put(jnp.asarray(rng.randn(k, out), jnp.float32),
                       NamedSharding(mesh, P("mp", None)))
    specs = (P(None, "mp"), P("mp", None))
    os.environ[cm.ENV_CHUNKS] = "8"
    try:
        ring = _tp_loss_grads(cm.ring_allreduce_matmul, mesh, 2, specs, x, w)
        blk = _tp_loss_grads(cm.blocking_allreduce_matmul, mesh, 2, specs,
                             x, w)
    finally:
        del os.environ[cm.ENV_CHUNKS]
    assert max_ulps(ring, blk) <= RING_ULPS


def test_resolve_chunks():
    # auto: ~min_chunk rows per sub-tile, snapped to a divisor
    os.environ[cm.ENV_MIN_CHUNK] = "64"
    try:
        assert cm.resolve_chunks(4, 256) == 4
        assert cm.resolve_chunks(4, 64) == 1
        assert cm.resolve_chunks(8, 96) == 1   # 96//64 -> 1
        assert cm.resolve_chunks(4, 192) == 3  # 192//64=3 divides
    finally:
        del os.environ[cm.ENV_MIN_CHUNK]
    # explicit knob wins when it divides, falls back to 1 when it doesn't
    os.environ[cm.ENV_CHUNKS] = "4"
    try:
        assert cm.resolve_chunks(4, 256) == 4
        assert cm.resolve_chunks(4, 6) == 1
        assert cm.resolve_chunks(2, 256) == 1  # mp=2 always unchunked
    finally:
        del os.environ[cm.ENV_CHUNKS]
    # 'auto'/'' mean auto, not an error
    os.environ[cm.ENV_CHUNKS] = "auto"
    try:
        assert cm.overlap_chunks() is None
    finally:
        del os.environ[cm.ENV_CHUNKS]


@pytest.mark.parametrize("var,fn", [
    (cm.ENV_MIN_CHUNK, cm.min_chunk),
    (cm.ENV_CHUNKS, cm.overlap_chunks),
])
@pytest.mark.parametrize("bad", ["banana", "12.5", "0", "-3"])
def test_env_parsing_rejects_junk(var, fn, bad):
    """Junk or non-positive values raise a ValueError NAMING the variable,
    not an opaque int() traceback."""
    os.environ[var] = bad
    try:
        with pytest.raises(ValueError, match=var):
            fn()
    finally:
        del os.environ[var]


def test_env_parsing_defaults():
    prev_min = os.environ.pop(cm.ENV_MIN_CHUNK, None)
    prev_chunks = os.environ.pop(cm.ENV_CHUNKS, None)
    try:
        assert cm.min_chunk() == 64
        assert cm.overlap_chunks() is None
        os.environ[cm.ENV_MIN_CHUNK] = " 32 "
        assert cm.min_chunk() == 32
    finally:
        os.environ.pop(cm.ENV_MIN_CHUNK, None)
        if prev_min is not None:
            os.environ[cm.ENV_MIN_CHUNK] = prev_min
        if prev_chunks is not None:
            os.environ[cm.ENV_CHUNKS] = prev_chunks


@needs_devices
def test_plans_are_memoized():
    """Same (shapes, mesh, kwargs, overlap env) -> the SAME plan object (no
    island rebuild, no tp.*.plans re-count); changing a knob or shape
    misses."""
    from paddle_tpu.observability import trace as obs
    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("mp",))
    os.environ[cm.ENV_MIN_CHUNK] = "16"
    try:
        cm.clear_plan_cache()  # noqa: PTA007 -- deliberate cold cache: the test must observe a fresh plan build; later tests replan lazily
        obs.reset_counters()
        p1 = cm.plan_column_parallel((64, 32), (32, 64), mesh)
        p2 = cm.plan_column_parallel((64, 32), (32, 64), mesh)
        assert p1 is not None and p1 is p2
        assert obs.counters().get("tp.column_parallel.plans") == 1
        p3 = cm.plan_column_parallel((128, 32), (32, 64), mesh)
        assert p3 is not None and p3 is not p1
        # env knobs key the cache: flipping MIN_CHUNK must re-plan
        os.environ[cm.ENV_MIN_CHUNK] = "8"
        assert cm.plan_column_parallel((64, 32), (32, 64), mesh) is not p1
        r1 = cm.plan_row_parallel((64, 32), (32, 64), mesh)
        assert r1 is cm.plan_row_parallel((64, 32), (32, 64), mesh)
    finally:
        del os.environ[cm.ENV_MIN_CHUNK]
        cm.clear_plan_cache()


def _fused_ffn_blocking_island(mesh, n, bax=None):
    """Blocking twin of plan_fused_ffn: same island layout, same local
    column matmuls + activation, fused psum instead of the ring."""
    def body(x, w_cols, w_row, b_cols):
        hs = [x @ w for w in w_cols]
        if b_cols:
            hs = [h + b for h, b in zip(hs, b_cols)]
        h = cm.swiglu(*hs)
        return jax.lax.psum(h @ w_row, "mp")
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(bax, None), (P(None, "mp"),) * 2, P("mp", None), ()),
        out_specs=P(bax, None), axis_names=frozenset(mesh.axis_names),
        check_vma=False)


@needs_devices
@pytest.mark.parametrize("mp", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_fused_ffn_parity(mp):
    """Single-island column->swiglu->row vs the blocking twin: within
    FFN_ULPS at mp=2 (two-term ring sum), fp tolerance at mp=4
    (reassociation)."""
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(7)
    t, k, inter = 64, 32, 32 * mp
    os.environ[cm.ENV_OVERLAP] = "1"
    os.environ[cm.ENV_MIN_CHUNK] = "8"
    try:
        cm.clear_plan_cache()  # noqa: PTA007 -- deliberate cold cache: the test must observe a fresh plan build; later tests replan lazily
        plan = cm.plan_fused_ffn((t, k), (k, inter), (inter, k), mesh,
                                 n_cols=2, activation=cm.swiglu,
                                 batch_axis=None)
        assert plan is not None
    finally:
        del os.environ[cm.ENV_OVERLAP]
        del os.environ[cm.ENV_MIN_CHUNK]
    x = jnp.asarray(rng.randn(t, k), jnp.float32)
    wg = jnp.asarray(rng.randn(k, inter) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.randn(k, inter) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.randn(inter, k) * 0.1, jnp.float32)
    blk = _fused_ffn_blocking_island(mesh, mp)

    def l_ring(a, g, u, d):
        o = plan(a, (g, u), d)
        return jnp.sum(o * jnp.cos(o))

    def l_blk(a, g, u, d):
        o = blk(a, (g, u), d, ())
        return jnp.sum(o * jnp.cos(o))

    ring = jax.jit(jax.value_and_grad(l_ring, argnums=(0, 1, 2, 3)))(
        x, wg, wu, wd)
    ref = jax.jit(jax.value_and_grad(l_blk, argnums=(0, 1, 2, 3)))(
        x, wg, wu, wd)
    if mp == 2:
        assert max_ulps(ring, ref) <= FFN_ULPS
    else:
        for r, b in zip(jax.tree_util.tree_leaves(ring),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_allclose(r, b, rtol=1e-3, atol=1e-4)


@needs_devices
def test_vocab_embed_ring_exact():
    """Masked local lookup + reduce ring: every row is non-zero on exactly
    one vocab shard, so the ring sum is EXACT (forward bitwise vs dense
    lookup; table grads match the dense scatter-add)."""
    mp = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(8)
    V, H, B, S = 32, 16, 4, 16
    os.environ[cm.ENV_OVERLAP] = "1"
    os.environ[cm.ENV_MIN_CHUNK] = "8"
    try:
        cm.clear_plan_cache()  # noqa: PTA007 -- deliberate cold cache: the test must observe a fresh plan build; later tests replan lazily
        plan = cm.plan_vocab_parallel_embedding((B, S), (V, H), mesh,
                                                batch_axis=None)
        assert plan is not None
    finally:
        del os.environ[cm.ENV_OVERLAP]
        del os.environ[cm.ENV_MIN_CHUNK]
    tab = jnp.asarray(rng.randn(V, H), jnp.float32)
    ids = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
    out = jax.jit(lambda i, w: plan(i, w))(ids, tab)
    assert np.array_equal(np.asarray(out), np.asarray(tab)[np.asarray(ids)])
    g_ring = jax.jit(jax.grad(lambda w: jnp.sum(jnp.sin(plan(ids, w)))))(tab)
    g_ref = jax.jit(jax.grad(lambda w: jnp.sum(jnp.sin(w[ids]))))(tab)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)


@needs_devices
def test_parallel_ce_ring_parity():
    """Ring-gathered (max, sumexp, picked) stats vs the replicated-logits
    logsumexp: fp tolerance (the log-sum is re-associated); the picked
    logit lives on one rank so its gathered sum is exact."""
    mp = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:mp]), ("mp",))
    rng = np.random.RandomState(9)
    B, S, V = 4, 8, 64
    os.environ[cm.ENV_OVERLAP] = "1"
    os.environ[cm.ENV_MIN_CHUNK] = "8"
    try:
        cm.clear_plan_cache()  # noqa: PTA007 -- deliberate cold cache: the test must observe a fresh plan build; later tests replan lazily
        plan = cm.plan_parallel_cross_entropy((B, S, V), mesh,
                                              batch_axis=None)
        assert plan is not None
    finally:
        del os.environ[cm.ENV_OVERLAP]
        del os.environ[cm.ENV_MIN_CHUNK]
    logits = jnp.asarray(rng.randn(B, S, V), jnp.float32)
    lbl = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)

    def ref(lg):
        l32 = lg.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(l32, axis=-1)
        return lse - jnp.take_along_axis(l32, lbl[..., None], -1)[..., 0]

    loss = jax.jit(lambda lg: plan(lg, lbl))(logits)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref(logits)),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.jit(jax.grad(lambda lg: jnp.sum(plan(lg, lbl))))(logits)
    g2 = jax.jit(jax.grad(lambda lg: jnp.sum(ref(lg))))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)


def _gpt2_mlp_losses(overlap):
    """Train a lone GPT2MLP through TrainStep at mp=2 (the same harness the
    fleet parity tests use) with the fused-FFN island on or off."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt2 import GPT2Config, GPT2MLP
    from paddle_tpu.optimizer import AdamW

    paddle.set_device("cpu")
    if overlap:
        os.environ[cm.ENV_OVERLAP] = "1"
        os.environ[cm.ENV_MIN_CHUNK] = "8"
    cm.clear_plan_cache()
    try:
        paddle.seed(13)
        cfg = GPT2Config(vocab_size=64, hidden_size=32, num_layers=1,
                         num_heads=2, max_position=32, intermediate_size=64,
                         dropout=0.0)
        model = GPT2MLP(cfg)
        opt = AdamW(learning_rate=1e-2, parameters=model.parameters())
        mesh = Mesh(np.array(jax.devices("cpu")[:2]).reshape(1, 2),
                    ("dp", "mp"))
        step = TrainStep(model, lambda o, l: paddle.mean((o - l) ** 2), opt,
                         mesh=mesh, batch_spec=P("dp"))
        rng = np.random.RandomState(10)
        x = paddle.to_tensor(rng.randn(4, 16, 32).astype(np.float32))
        y = paddle.to_tensor(rng.randn(4, 16, 32).astype(np.float32))
        return [float(step(x, labels=y)) for _ in range(3)]
    finally:
        if overlap:
            del os.environ[cm.ENV_OVERLAP]
            del os.environ[cm.ENV_MIN_CHUNK]
        cm.clear_plan_cache()


@needs_devices
def test_gpt2_mlp_fused_overlap_matches_blocking():
    """GPT2MLP trained through TrainStep must produce the same losses with
    the fused-FFN island on vs off at mp=2 (bitwise ring degree; only fp
    noise from GSPMD partitioning differences is tolerated)."""
    base = _gpt2_mlp_losses(False)
    fused = _gpt2_mlp_losses(True)
    np.testing.assert_allclose(fused, base, rtol=2e-6, atol=1e-7)


def _sp_ffn_losses(overlap):
    """Column->gelu->Row SP pair through fused_sequence_parallel_ffn, fused
    island on (overlap env) or the layer-by-layer fallback."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, RowParallelLinear)
    from paddle_tpu.distributed.fleet.utils.sequence_parallel_utils import \
        fused_sequence_parallel_ffn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    class SPBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc_in = ColumnParallelLinear(32, 64, gather_output=False)
            self.fc_out = RowParallelLinear(64, 32, input_is_parallel=True)

        def forward(self, x):
            return fused_sequence_parallel_ffn(self.fc_in, self.fc_out, x)

    paddle.set_device("cpu")
    if overlap:
        os.environ[cm.ENV_OVERLAP] = "1"
        os.environ[cm.ENV_MIN_CHUNK] = "8"
    cm.clear_plan_cache()
    try:
        paddle.seed(17)
        model = SPBlock()
        opt = AdamW(learning_rate=1e-2, parameters=model.parameters())
        mesh = Mesh(np.array(jax.devices("cpu")[:2]).reshape(1, 2),
                    ("dp", "mp"))
        step = TrainStep(model, lambda o, l: paddle.mean((o - l) ** 2), opt,
                         mesh=mesh, batch_spec=P("dp"))
        rng = np.random.RandomState(18)
        x = paddle.to_tensor(rng.randn(4, 16, 32).astype(np.float32))
        y = paddle.to_tensor(rng.randn(4, 16, 32).astype(np.float32))
        return [float(step(x, labels=y)) for _ in range(3)]
    finally:
        if overlap:
            del os.environ[cm.ENV_OVERLAP]
            del os.environ[cm.ENV_MIN_CHUNK]
        cm.clear_plan_cache()


@needs_devices
def test_sequence_parallel_fused_ffn_matches_fallback():
    """fused_sequence_parallel_ffn: the single-island route must match the
    layer-by-layer SP fallback at mp=2."""
    base = _sp_ffn_losses(False)
    fused = _sp_ffn_losses(True)
    np.testing.assert_allclose(fused, base, rtol=2e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# PP: async-p2p schedule vs blocking schedule
# ---------------------------------------------------------------------------

def _pp_loss_grads(S, M, overlap):
    H = 16
    mesh = Mesh(np.array(jax.devices("cpu")[:S]), ("pp",))
    rng = np.random.RandomState(0)
    per_stage = [{"w": jnp.asarray(rng.randn(H, H), jnp.float32) * 0.3,
                  "b": jnp.asarray(rng.randn(H), jnp.float32) * 0.1}
                 for _ in range(S)]
    stacked = stack_stage_params(per_stage)
    x_mb = microbatch(jnp.asarray(rng.randn(M * 2, H), jnp.float32), M)
    pipe = pipeline_apply(lambda p, h: jnp.tanh(h @ p["w"] + p["b"]),
                          S, M, "pp", remat=True, overlap_p2p=overlap)

    def island(params, xm):
        loss = jnp.sum(pipe(params, xm) ** 2)
        return last_stage_value(loss, S, "pp")

    f = shard_map(island, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
                  axis_names=frozenset(["pp"]), check_vma=False)
    loss, grads = jax.jit(
        jax.value_and_grad(lambda p: f(p, x_mb)))(stacked)
    return np.asarray(loss), jax.tree_util.tree_map(np.asarray, grads)


@needs_devices
@pytest.mark.parametrize("S,M", [(2, 4),
                                 pytest.param(4, 4, marks=pytest.mark.slow)])
def test_pp_overlap_bitwise(S, M):
    """The double-buffered schedule applies identical per-microbatch ops
    (one extra skew tick, same stage math): loss AND grads bitwise."""
    blk = _pp_loss_grads(S, M, overlap=False)
    ovl = _pp_loss_grads(S, M, overlap=True)
    assert np.array_equal(blk[0], ovl[0])
    assert _leaves_equal(blk[1], ovl[1])


@needs_devices
@pytest.mark.slow
def test_pp_overlap_via_llama_config():
    """overlap_p2p plumbs through ParallelConfig into the pp train step."""
    from paddle_tpu.models.llama import (ParallelConfig, build_train_step,
                                         llama_tiny, make_mesh)
    from paddle_tpu.ops import _common
    losses = {}
    with _common.interpret_mode(True):
        for ovl in (False, True):
            parallel = ParallelConfig(dp=1, pp=2, microbatches=4,
                                      use_flash=False, overlap_p2p=ovl)
            config = llama_tiny(vocab=64, hidden=32, layers=4, heads=4,
                                kv_heads=4, inter=64, seq=32)
            mesh = make_mesh(parallel, devices=jax.devices("cpu")[:2])
            step, params, opt = build_train_step(config, parallel, mesh=mesh,
                                                 lr=1e-3)
            rng = np.random.RandomState(0)
            ids = rng.randint(0, 64, (4, 32)).astype(np.int32)
            labels = np.roll(ids, -1, 1).astype(np.int32)
            _, _, loss = step(params, opt, ids, labels)
            losses[ovl] = float(jax.device_get(loss))
    assert losses[True] == losses[False]
