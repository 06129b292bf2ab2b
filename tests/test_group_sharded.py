"""GroupSharded / ZeRO stage 1-3 equivalence tests (SURVEY.md §4: sharded
training must match plain-DP numerics; ref test/collective/fleet group_sharded
suites compare stage-2/3 losses against DataParallel)."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from op_test import max_ulps

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.sharding import group_sharded_parallel
from paddle_tpu.jit import TrainStep
from paddle_tpu.optimizer import AdamW

HIDDEN = 32


def _make_model_and_opt():
    paddle.set_device("cpu")  # module fixture may run before conftest's autouse
    paddle.seed(7)
    model = nn.Sequential(
        nn.Linear(16, HIDDEN), nn.GELU(),
        nn.Linear(HIDDEN, HIDDEN), nn.GELU(),
        nn.Linear(HIDDEN, 4))
    opt = AdamW(learning_rate=1e-2, parameters=model.parameters(),
                weight_decay=0.01)
    return model, opt


def _loss_fn(out, label):
    return paddle.mean((out - label) ** 2)


def _batch():
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randn(8, 4).astype(np.float32)
    return paddle.to_tensor(x), paddle.to_tensor(y)


@pytest.fixture(scope="module")
def ref_losses():
    model, opt = _make_model_and_opt()
    step = TrainStep(model, _loss_fn, opt)
    x, y = _batch()
    return [float(step(x, labels=y)) for _ in range(3)]


def _mesh():
    devs = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
    return Mesh(devs, ("dp", "sharding"))


@pytest.mark.parametrize("level", ["os", "os_g", "p_g_os"])
def test_group_sharded_matches_serial(level, ref_losses):
    model, opt = _make_model_and_opt()
    model, opt, _ = group_sharded_parallel(model, opt, level)
    step = TrainStep(model, _loss_fn, opt, mesh=_mesh(), batch_spec=P("dp"))
    x, y = _batch()
    losses = [float(step(x, labels=y)) for _ in range(3)]
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)


def test_stage1_opt_state_is_sharded():
    model, opt = _make_model_and_opt()
    model, opt, _ = group_sharded_parallel(model, opt, "os")
    mesh = _mesh()
    step = TrainStep(model, _loss_fn, opt, mesh=mesh, batch_spec=P("dp"))
    # params replicated, moments sharded over 'sharding'
    sharded = replicated = 0
    for k in step.trainable_keys:
        p_spec = step.param_shardings[k].spec
        assert all(ax != "sharding" for ax in p_spec if ax), p_spec
        replicated += 1
        for leaf in jax.tree_util.tree_leaves(step.opt_states[k]):
            if leaf.ndim == step.params[k].ndim and max(leaf.shape) % 4 == 0:
                spec = leaf.sharding.spec
                if any(ax == "sharding" for ax in spec if ax):
                    sharded += 1
    assert replicated > 0 and sharded > 0


def test_stage3_params_are_sharded():
    model, opt = _make_model_and_opt()
    model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
    step = TrainStep(model, _loss_fn, opt, mesh=_mesh(), batch_spec=P("dp"))
    found = False
    for k in step.trainable_keys:
        spec = step.params[k].sharding.spec
        if any(ax == "sharding" for ax in spec if ax):
            found = True
    assert found


def _param_2d_shapes(step):
    """Full 2D parameter shapes (and transposes — XLA is free to carry
    either orientation through the backward)."""
    shapes = set()
    for k in step.trainable_keys:
        shp = tuple(int(s) for s in step.param_objs[k]._data.shape)
        if len(shp) == 2:
            shapes.add(shp)
            shapes.add(shp[::-1])
    return shapes


def test_stage3_params_allgathered_in_hlo():
    """Stage 3 (p_g_os), observable in the compiled HLO: parameters are
    STORED shard-sized ([HIDDEN/4, ...] between steps) and the program
    all-gathers the shard to the full shape before use — the same
    per-layer gather/free the reference's stage 3 hand-schedules on NCCL
    streams. Stage 2 must show neither (full params stored, no param
    all-gather)."""
    import re

    def build(level):
        model, opt = _make_model_and_opt()
        model, opt, _ = group_sharded_parallel(model, opt, level)
        return TrainStep(model, _loss_fn, opt, mesh=_mesh(),
                         batch_spec=P(("dp", "sharding")))

    def computation_bodies(hlo):
        """Map each HLO computation name to its body text (fusions pull
        dots out of the straight-line program, so consumer checks must
        look through ``calls=``)."""
        bodies, cur = {}, None
        for ln in hlo.splitlines():
            m = re.match(r"\s*(?:ENTRY\s+)?%?([\w.-]+)\s*\(.*->.*\{", ln)
            if m:
                cur = m.group(1)
                bodies[cur] = []
            elif ln.strip() == "}":
                cur = None
            elif cur is not None:
                bodies[cur].append(ln)
        return {k: "\n".join(v) for k, v in bodies.items()}

    def param_allgathers(hlo, param_shapes):
        # stage-3 signature: an all-gather PRODUCING a full param-shaped
        # value whose result feeds a dot (the forward/backward matmuls) —
        # the per-layer gather-before-use. Semantic on two counts: the
        # shape filter keeps batch/activation gathers out (the partitioner
        # is free to all-gather dp-sharded activations into dots — that is
        # data movement, not ZeRO-3), and the dot linkage keeps stage 2's
        # update-side gathers of NEW param shards out (those feed the
        # output tuple, not a matmul). The dot may sit behind a fusion —
        # follow its calls= into the fused computation.
        gathered = set()
        for ln in hlo.splitlines():
            m = re.match(r"\s*%?([\w.-]+)\s*=\s*f32\[(\d+),(\d+)\]\S*\s+"
                         r"all-gather\(", ln)
            if m and (int(m.group(2)), int(m.group(3))) in param_shapes:
                gathered.add(m.group(1))
        bodies = computation_bodies(hlo)
        hits = []

        def uses(ln, name):
            # operand use of %name (boundary: %all-gather must not match
            # %all-gather.4), excluding the defining line itself
            pat = rf"%{re.escape(name)}(?![\w.])"
            return (re.search(pat, ln)
                    and not re.match(rf"\s*{pat}\s*=", ln))

        for ln in hlo.splitlines():
            if not any(uses(ln, name) for name in gathered):
                continue
            if "dot(" in ln:
                hits.append(ln)
                continue
            m = re.search(r"calls=%([\w.-]+)", ln)
            if m and "dot(" in bodies.get(m.group(1), ""):
                hits.append(ln)
        return hits

    x, y = _batch()
    step3 = build("p_g_os")
    hlo3 = step3.compiled_hlo(x, labels=y)
    step2 = build("os_g")
    hlo2 = step2.compiled_hlo(x, labels=y)
    param_shapes = _param_2d_shapes(step3)

    # stored param arrays are shard-sized under stage 3: the [16, HIDDEN]
    # weight's addressable shard is [16, HIDDEN/4] (largest dim sharded)
    shard_sized = 0
    for k in step3.trainable_keys:
        arr = step3.params[k]
        spec = arr.sharding.spec
        if any(ax == "sharding" for ax in spec if ax):
            shard = arr.addressable_shards[0].data
            assert shard.size == arr.size // 4, (arr.shape, shard.shape)
            shard_sized += 1
        full2 = step2.params[k]
        assert all(ax != "sharding" for ax in (full2.sharding.spec or ())
                   if ax)
    assert shard_sized > 0

    assert param_allgathers(hlo3, param_shapes), \
        "stage 3 must all-gather param shards before use"
    assert not param_allgathers(hlo2, param_shapes), \
        "stage 2 must not all-gather params (they are stored full)"


def test_stage3_param_prefetch_bitwise():
    """Bucketed one-ahead param-gather prefetch only re-orders WHEN the
    stage-3 all-gathers are issued (optimization_barrier chaining +
    sharding constraints) — the gathered values are identical, so losses
    must match the non-prefetched step within 4 ULPs (measured on jaxlib
    0.9.0 XLA:CPU: two losses equal, the third 1 ULP apart, the barriers
    having moved a fusion boundary)."""

    def run(prefetch, spec):
        model, opt = _make_model_and_opt()
        model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
        step = TrainStep(model, _loss_fn, opt, mesh=_mesh(),
                         batch_spec=spec,
                         param_prefetch=prefetch, param_bucket_mb=0.001)
        x, y = _batch()
        return step, [float(step(x, labels=y)) for _ in range(3)]

    step_off, losses_off = run(False, P("dp"))
    step_on, losses_on = run(True, P("dp"))
    assert not step_off.param_gather_buckets
    # the tiny cap actually split the gathers into multiple buckets
    assert len(step_on.param_gather_buckets) > 1
    assert max_ulps(np.float32(losses_on), np.float32(losses_off)) <= 4

    # with the batch ALSO split over the sharding axis the replication
    # constraint changes how GSPMD partitions the activations around it
    # (fp-level reassociation only)
    _, off2 = run(False, P(("dp", "sharding")))
    _, on2 = run(True, P(("dp", "sharding")))
    np.testing.assert_allclose(on2, off2, rtol=1e-6)


def test_stage3_prefetch_defaults_to_overlap_env(monkeypatch):
    """param_prefetch=None follows PADDLE_TPU_TP_OVERLAP, and non-stage-3
    runs never build gather buckets."""
    from paddle_tpu.parallel import collective_matmul as cm

    def build(level, **kw):
        model, opt = _make_model_and_opt()
        model, opt, _ = group_sharded_parallel(model, opt, level)
        return TrainStep(model, _loss_fn, opt, mesh=_mesh(),
                         batch_spec=P(("dp", "sharding")), **kw)

    monkeypatch.setenv(cm.ENV_OVERLAP, "0")
    assert not build("p_g_os").param_gather_buckets
    monkeypatch.setenv(cm.ENV_OVERLAP, "1")
    assert build("p_g_os").param_gather_buckets
    # stage 2 stores params full: nothing to prefetch even when forced on
    assert not build("os_g", param_prefetch=True).param_gather_buckets


def test_save_group_sharded_model(tmp_path):
    from paddle_tpu.distributed.sharding import save_group_sharded_model
    model, opt = _make_model_and_opt()
    model, opt, _ = group_sharded_parallel(model, opt, "os_g")
    save_group_sharded_model(model, str(tmp_path), optimizer=opt)
    assert (tmp_path / "model.pdparams").exists()
    assert (tmp_path / "model.pdopt").exists()


def test_stage2_grads_reduce_scattered_vs_stage1():
    """The stage-1 vs stage-2 distinction, observable in the compiled HLO —
    asserted on SEMANTICS (what is reduced, over which replica groups),
    not on which exact shapes the partitioner's current schedule happens
    to materialize:

    - stage 1 keeps grads replicated: some full-param-shaped 2D grad is
      summed in ONE collective spanning the whole mesh (all 8 devices);
    - stage 2 constrains grads onto the 'sharding' axis: NO 2D grad is
      reduced whole-mesh; instead shard-sized 2D grad pieces (one param
      dim divided by the sharding degree) are reduced over group-local
      replica groups — the reduce-scatter traffic pattern where each rank
      only materializes its grad shard."""
    import re

    def build(level):
        model, opt = _make_model_and_opt()
        model, opt, _ = group_sharded_parallel(model, opt, level)
        # sharding subdivides data parallelism (reference ZeRO): batch is
        # split over dp AND sharding ranks
        return TrainStep(model, _loss_fn, opt, mesh=_mesh(),
                         batch_spec=P(("dp", "sharding")))

    def reduces_2d(hlo):
        """(shape, group_size) for every all-reduce/reduce-scatter whose
        line carries a 2D f32 operand. Handles both replica_groups
        encodings: the iota form [n_groups,size]<=... and the literal
        {{0,1},{2,3},...} form."""
        out = []
        for ln in hlo.splitlines():
            if not re.search(r"(all-reduce|reduce-scatter)\(", ln):
                continue
            shapes = [(int(a), int(b))
                      for a, b in re.findall(r"f32\[(\d+),(\d+)\]", ln)]
            if not shapes:
                continue
            m = re.search(r"replica_groups=\[(\d+),(\d+)\]", ln)
            if m:
                group_size = int(m.group(2))
            else:
                groups = re.findall(r"\{([\d,]+)\}", ln)
                group_size = (max(len(g.split(",")) for g in groups)
                              if groups else 0)
            for shp in set(shapes):
                out.append((shp, group_size))
        return out

    x, y = _batch()
    step1, step2 = build("os"), build("os_g")
    hlo1, hlo2 = (step1.compiled_hlo(x, labels=y),
                  step2.compiled_hlo(x, labels=y))
    mesh_size = 8
    degree = 4  # sharding axis size in _mesh()
    full = _param_2d_shapes(step1)
    shard = {(a // degree, b) for a, b in full if a % degree == 0} \
        | {(a, b // degree) for a, b in full if b % degree == 0}

    r1, r2 = reduces_2d(hlo1), reduces_2d(hlo2)
    assert any(shp in full and gs == mesh_size for shp, gs in r1), \
        f"stage 1 must reduce a full-shape 2D grad over the whole mesh " \
        f"(saw {r1})"
    assert not any(gs == mesh_size for shp, gs in r2), \
        f"stage 2 must not reduce any 2D grad over the whole mesh " \
        f"(saw {r2})"
    assert any(shp in shard and 1 < gs < mesh_size for shp, gs in r2), \
        f"stage 2 must reduce shard-sized 2D grad pieces over group-" \
        f"local replica groups (saw {r2})"


def test_shard_spec_divisibility():
    """A non-divisible largest dim must fall through to the next largest
    divisible one; no divisible dim at all -> unsharded (no GSPMD pad)."""
    from paddle_tpu.distributed.fleet.meta_parallel.sharding.group_sharded \
        import _shard_spec_for, mesh_resolved_spec

    # largest dim 34 not divisible by 4 -> shard dim 1 (16)
    assert _shard_spec_for((34, 16), None, degree=4) == P(None, "sharding")
    # divisible largest dim wins as before
    assert _shard_spec_for((32, 16), None, degree=4) == P("sharding", None)
    # nothing divisible -> unsharded
    assert _shard_spec_for((7, 5), None, degree=4) == P(None, None)
    # composes with an existing mp spec: dim 0 taken -> next largest free
    assert _shard_spec_for((64, 32), P("mp", None), degree=4) \
        == P("mp", "sharding")
    # no degree (mesh unknown at attach time): largest free dim
    assert _shard_spec_for((34, 16), None) == P("sharding", None)

    # end-to-end: attach-time guess is corrected at placement time
    paddle.set_device("cpu")
    model = nn.Linear(16, 34)  # weight [34,16] transposed storage is [16,34]
    opt = AdamW(learning_rate=1e-2, parameters=model.parameters())
    model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
    mesh = _mesh()  # sharding degree 4
    for p in model.parameters():
        spec = mesh_resolved_spec(p, mesh)
        shape = tuple(p._data.shape)
        for i, ax in enumerate(spec):
            if ax == "sharding":
                assert shape[i] % 4 == 0, (shape, spec)


def test_group_sharded_nondivisible_matches_serial():
    """Stage-3 training with a non-divisible hidden size still matches
    serial numerics (the uneven dim is simply left unsharded)."""
    paddle.set_device("cpu")

    def build():
        paddle.seed(11)
        m = nn.Sequential(nn.Linear(16, 34), nn.GELU(), nn.Linear(34, 4))
        o = AdamW(learning_rate=1e-2, parameters=m.parameters())
        return m, o

    x, y = _batch()
    m0, o0 = build()
    ref_step = TrainStep(m0, _loss_fn, o0)
    ref = [float(ref_step(x, labels=y)) for _ in range(3)]

    m1, o1 = build()
    m1, o1, _ = group_sharded_parallel(m1, o1, "p_g_os")
    step = TrainStep(m1, _loss_fn, o1, mesh=_mesh(), batch_spec=P("dp"))
    got = [float(step(x, labels=y)) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-5)
