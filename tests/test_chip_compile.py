"""The main path's programs, compiled for the chip without the chip.

The TPU's compiler is installed here and compiles for a v5e that is described,
not attached (``jax.experimental.topologies``). Interpret mode checks neither
the Mosaic lowering nor VMEM nor HBM, and every refusal below was found only
by such a compile: block shapes of the fused paged update and commit kernels,
16.02M of scoped VMEM in the flash forward inside a 7B-wide step, a
pool-sized relayout copy in the paged prefill, Mosaic kernels outside a
shard_map on a dp x mp mesh. Shapes are ``chip_smoke.FULL``'s: what the
smoke runs on the chip is what is compiled here.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every xdist worker imports
this file. Keep these tests in this one file for the same reason.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.models import llama as L  # noqa: E402
from paddle_tpu.ops import _common, flash_attention as fa  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402

FULL = chip_smoke.FULL
NH = NKV = 32
KVD, BS = 4096, 128
BATCH, T_FED = FULL.max_batch, FULL.draft_k + 1
MAX_NB = FULL.max_seq_len // BS
POOL_BLOCKS = 64         # the kernels see one block at a time
bf16, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """v5e 2x2, with the persistent compile cache off around these
    compiles: an entry written for a described chip cannot be read back
    without one, and the next run would warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # noqa: PTA007 -- process-lifetime: keeps the compiler's logs out of /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    """shape, dtype -> ShapeDtypeStruct on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def compile_for_chip(fn, *args):
    """Lower and compile with the kernels' interpret predicate held off
    (the backend here is the CPU); what the chip's compiler would raise,
    this raises. Returns the compiled program."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    with _common.interpret_mode(False):
        return jitted.lower(*args).compile()


def gib(n):
    return n / 2 ** 30


# -- the ten paged kernels, the four repaired ones first ----------------------

@pytest.fixture(scope="module")
def paged_args(sds):
    """name -> (function, abstract arguments) at the 7B widths."""
    q = sds((BATCH, NH, KVD), bf16)
    q_fed = sds((BATCH, T_FED * NH, KVD), bf16)
    pool = {False: sds((2, POOL_BLOCKS, KVD, BS), bf16),
            True: sds((2, POOL_BLOCKS, KVD, BS), i8)}
    scale = sds((2, POOL_BLOCKS, NKV, BS), f32)
    col = {False: sds((BATCH, KVD), bf16), True: sds((BATCH, KVD), i8)}
    col_s = sds((BATCH, NKV), f32)
    fed = {False: sds((2, BATCH, T_FED, KVD), bf16),
           True: sds((2, BATCH, T_FED, KVD), i8)}
    fed_s = sds((2, BATCH, T_FED, NKV), f32)
    tables, lens, layer = (sds((BATCH, MAX_NB), i32), sds((BATCH,), i32),
                           sds((), i32))
    kp, kq = pool[False], pool[True]
    q_chunk = sds((FULL.prefill_chunk, NH, KVD // NKV), bf16)
    chunk_at = (sds((MAX_NB,), i32), sds((), i32), sds((), i32), layer)
    return {
        "attend_update": (
            lambda q, k, v, kp, vp, tables, lens, layer:
            pa.paged_attend_update(
                q, k, v, kp, vp, pa.paged_update_walk(tables, lens, BS),
                layer),
            (q, col[False], col[False], kp, kp, tables, lens, layer)),
        "attend_update_quant": (
            lambda q, k, v, ks, vs, kp, vp, ksp, vsp, tables, lens, layer:
            pa.paged_attend_update_quant(
                q, k, v, ks, vs, kp, vp, ksp, vsp,
                pa.paged_update_walk(tables, lens, BS), layer),
            (q, col[True], col[True], col_s, col_s, kq, kq, scale, scale,
             tables, lens, layer)),
        "verify_commit": (pa.paged_verify_commit, (
            fed[False], fed[False], kp, kp, tables, lens, lens)),
        "verify_commit_quant": (pa.paged_verify_commit_quant, (
            fed[True], fed[True], fed_s, fed_s, kq, kq, scale, scale,
            tables, lens, lens)),
        "attention": (pa.paged_attention, (q, kp, kp, tables, lens, layer)),
        "attention_quant": (pa.paged_attention_quant, (
            q, kq, kq, scale, scale, tables, lens, layer)),
        "attention_verify": (pa.paged_attention_verify, (
            q_fed, kp, kp, tables, lens, layer)),
        "attention_verify_quant": (pa.paged_attention_verify_quant, (
            q_fed, kq, kq, scale, scale, tables, lens, layer)),
        "prefill_attention": (pa.paged_prefill_attention, (
            q_chunk, kp, kp, *chunk_at)),
        "prefill_attention_quant": (
            lambda q, k, v, ks, vs, *at: pa.paged_prefill_attention(
                q, k, v, *at, kv_scales=(ks, vs)),
            (q_chunk, kq, kq, scale, scale, *chunk_at)),
    }


@pytest.mark.parametrize("name", [
    "attend_update", "attend_update_quant", "verify_commit",
    "verify_commit_quant", "attention", "attention_quant",
    "attention_verify", "attention_verify_quant", "prefill_attention",
    "prefill_attention_quant"])
def test_paged_kernel_compiles(paged_args, name):
    fn, args = paged_args[name]
    assert "tpu_custom_call" in compile_for_chip(fn, *args).as_text()


# -- the engine's jitted steps at chip_smoke's serving size -------------------

@pytest.fixture(scope="module")
def serve_args(sds):
    """(frozen config, abstract weights, pools by quant) for the server."""
    config = dataclasses.replace(L.llama_7b(),
                                 num_hidden_layers=FULL.serve_layers)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: L.init_llama_params(config, 0)))
    shape = (FULL.serve_layers, FULL.num_blocks, KVD, BS)
    scale = sds((FULL.serve_layers, FULL.num_blocks, NKV, BS), f32)
    pools = {False: (sds(shape, bf16),) * 2,
             True: (sds(shape, i8),) * 2 + (scale, scale)}
    return L._freeze_config(config), params, pools


@pytest.mark.parametrize("kind, quant", [
    ("decode", False), ("decode", True), ("prefill", False),
    ("prefill", True), ("prefill+decode", False)],
    ids=["decode-fp", "decode-int8", "prefill-fp", "prefill-int8",
         "prefill+decode-fp"])
def test_engine_step_compiles_with_a_pool_half_of_hbm(serve_args, sds, kind,
                                                      quant):
    """The donated pools must alias through the step: the prefill once
    copied the whole pool to another layout and back (a second pool-sized
    buffer, refused outright at this pool size). The chunk that carries the
    decode batch writes the pools twice a layer (the batch's fused update,
    then the chunk's columns) before its attention reads them: still in
    place."""
    frozen, params, pools = serve_args
    rows = (sds((BATCH, MAX_NB), i32), sds((BATCH,), i32),
            sds((BATCH,), i32))
    chunk = (sds((MAX_NB,), i32), sds((), i32),
             sds((FULL.prefill_chunk,), i32), sds((), i32))
    args = {"decode": rows, "prefill": chunk,
            "prefill+decode": chunk + rows}[kind]
    fn = L._jitted_paged_step(kind, frozen, quant, None)
    compiled = compile_for_chip(fn, params, *pools[quant], *args)
    assert "tpu_custom_call" in compiled.as_text()
    # what the engine fetches: a token and a finite flag a row, not the
    # f32[BATCH, 32000] logits the steps return
    heads = jax.eval_shape(fn, params, *pools[quant],
                           *args)[:-len(pools[quant])]
    assert [(h.shape, h.dtype) for h in heads] == {
        "decode": [((BATCH,), i32), ((BATCH,), jnp.bool_)],
        "prefill": [((), i32), ((), jnp.bool_)]}.get(
            kind, [((), i32), ((), jnp.bool_),
                   ((BATCH,), i32), ((BATCH,), jnp.bool_)])
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                     for p in pools[quant])
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes
    assert gib(ma.temp_size_in_bytes) < 1.0, (
        f"{gib(ma.temp_size_in_bytes):.2f} GiB of temporaries beside a "
        f"{gib(pool_bytes):.2f} GiB pool: something pool-sized is copied")
    if kind != "decode":
        # the chunk's scores stay in VMEM: the dense path held them against
        # every slot of the table as f32 [chunk, heads, max_seq_len] in HBM
        scores = FULL.prefill_chunk * NH * FULL.max_seq_len * 4
        assert ma.temp_size_in_bytes < scores / 2, (
            f"{gib(ma.temp_size_in_bytes):.2f} GiB of temporaries: room for "
            f"the {gib(scores):.2f} GiB of scores against the whole table")


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_tp_prefill_compiles_on_four_chips(topo, quant):
    """The tensor-parallel prefill programs run the same attention inside
    their shard_map island, on the rank's own heads (8 of 32, 2 of 8 kv)."""
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("mp",))
    config = dataclasses.replace(L.llama_7b(), num_hidden_layers=2,
                                 num_key_value_heads=8)
    pspecs, _ = L._tp_specs(config, mesh)
    on = lambda shape, dtype, spec=P(): jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=NamedSharding(mesh, spec))
    params = jax.tree_util.tree_map(
        lambda a, s: on(a.shape, a.dtype, s),
        jax.eval_shape(lambda: L.init_llama_params(config, 0)), pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    pool = on((2, POOL_BLOCKS, 8 * 128, BS), i8 if quant else bf16,
              L._TP_POOL_SPEC)
    scale = on((2, POOL_BLOCKS, 8, BS), f32, L._TP_POOL_SPEC)
    fn = L._jitted_paged_step("prefill", L._freeze_config(config), quant,
                              mesh)
    compiled = compile_for_chip(
        fn, params, *((pool, pool, scale, scale) if quant else (pool, pool)),
        on((MAX_NB,), i32), on((), i32), on((FULL.prefill_chunk,), i32),
        on((), i32))
    assert "tpu_custom_call" in compiled.as_text()
    assert gib(compiled.memory_analysis().temp_size_in_bytes) < 0.1


# -- dense flash attention and the train step ---------------------------------

def test_flash_forward_and_fused_flat_backward_compile(sds):
    q = sds((FULL.train_batch, FULL.train_seq, NH, 128), bf16)
    stats = fa.dense_bwd_schedule_stats(
        FULL.train_batch * NH, FULL.train_seq, FULL.train_seq, 128, bf16,
        True)
    assert stats["path"] == "fused_flat"

    def loss(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True).astype(f32).sum()

    fwd = compile_for_chip(
        lambda q, k, v: fa.flash_attention_bshd(q, k, v, causal=True),
        q, q, q)
    bwd = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert fwd.as_text().count("tpu_custom_call") == 1
    assert bwd.as_text().count("tpu_custom_call") == 2    # fwd + one bwd


def abstract_train_step(parallel, mesh=None):
    """(jitted step, abstract params, abstract optimizer state) of
    build_train_step at chip_smoke's training size, nothing materialised."""
    config = dataclasses.replace(L.llama_7b(),
                                 num_hidden_layers=FULL.train_layers)
    made = {}

    def build():
        made["step"], params, opt = L.build_train_step(config, parallel,
                                                       mesh=mesh, lr=3e-4)
        return params, opt

    params, opt = jax.eval_shape(build)
    return config, made["step"].jitted, params, opt


def test_train_step_compiles_and_fits_one_chip(sds):
    """The 7B-wide step wanted 16.02M of scoped VMEM for the flash forward
    against a 16M default; and FULL's depth and batch must fit 15.75 GiB."""
    _, step, params, opt = abstract_train_step(
        L.ParallelConfig(remat=True, use_flash=True))
    on_chip = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: sds(a.shape, a.dtype), t)
    ids = sds((FULL.train_batch, FULL.train_seq), i32)
    compiled = compile_for_chip(step, on_chip(params), on_chip(opt), ids, ids)
    assert compiled.as_text().count("tpu_custom_call") >= 3
    ma = compiled.memory_analysis()
    need = gib(ma.argument_size_in_bytes + ma.temp_size_in_bytes)
    assert need < 15.0, f"{need:.2f} GiB on a 15.75 GiB chip"


def test_dp2_mp2_train_step_compiles_on_four_chips(topo):
    """On a mesh the GSPMD path's kernels run per shard in shard_map
    islands: outside one, the TPU lowering refuses the step ("Mosaic
    kernels cannot be automatically partitioned")."""
    parallel = L.ParallelConfig(dp=2, mp=2, remat=True, use_flash=True)
    mesh = L.make_mesh(parallel, devices=topo.devices)
    config, step, params, opt = abstract_train_step(parallel, mesh)
    specs = L.param_pspecs(config, parallel)

    def sharded(tree):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    opt = {"m": sharded(opt["m"]), "v": sharded(opt["v"]),
           "t": jax.ShapeDtypeStruct((), f32,
                                     sharding=NamedSharding(mesh, P()))}
    ids = jax.ShapeDtypeStruct((FULL.train_batch, FULL.train_seq), i32,
                               sharding=NamedSharding(mesh, P("dp", None)))
    text = compile_for_chip(step, sharded(params), opt, ids, ids).as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


# -- DeepSeek-V3 at published widths (PR 31) -----------------------------------

MLA_NH, MLA_RANK, MLA_W = 128, 512, 576
MLA_MAX_NB = 134                 # 17152 tokens of 128
MLA_POOL = (6, 2048, MLA_W, BS)  # the cell's own pool: 1.81 GB


@pytest.mark.parametrize("batch", [1, 64])
def test_mla_decode_kernel_compiles(sds, batch):
    """576 rows on the sublane axis, the new column padded to 640 lanes and
    transposed in the kernel, a dynamic grid over the live blocks, the pool
    aliased through the call."""
    out = compile_for_chip(
        jax.jit(lambda q, new, pool, tables, pos: pa.mla_paged_decode(
            q, new, pool, pa.mla_update_walk(tables, pos, BS), 2,
            rank=MLA_RANK), donate_argnums=(2,)),
        sds((batch, MLA_NH, MLA_W), bf16), sds((batch, MLA_W), bf16),
        sds(MLA_POOL, bf16), sds((batch, MLA_MAX_NB), i32),
        sds((batch,), i32))
    assert "tpu_custom_call" in out.as_text()
    # no second pool: the update is in place
    assert out.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_mla_prefill_kernel_compiles(sds):
    """A tile of 16 tokens x 128 heads against four table slots a step."""
    assert pa._fit_mla_prefill_tile(512, MLA_NH, MLA_W, MLA_RANK, BS, 2) == 16
    out = compile_for_chip(
        lambda q, pool, table, start, n: pa.mla_paged_prefill(
            q, pool, table, start, n, 2, rank=MLA_RANK),
        sds((512, MLA_NH, MLA_W), bf16), sds(MLA_POOL, bf16),
        sds((MLA_MAX_NB,), i32), sds((), i32), sds((), i32))
    assert "tpu_custom_call" in out.as_text()


@pytest.mark.parametrize("tile, rows", [(128, 6144), (16, 768)])
def test_live_grouped_matmul_compiles_at_expert_widths(sds, tile, rows):
    """80 stacked experts of 7168 x 2048, a chunk's and a decode batch's
    row buffers, the tile axis of the grid a traced count."""
    from paddle_tpu.ops.grouped_matmul import grouped_matmul_live
    n_t = rows // tile
    sched = tuple(sds((n_t,), i32) for _ in range(4))
    for k, n in ((7168, 2048), (2048, 7168)):
        out = compile_for_chip(
            lambda x, w, e, lv, f, l, n_live: grouped_matmul_live(
                x, w, (e, lv, f, l), n_live, tile),
            sds((rows, k), bf16), sds((80, k, n), bf16), *sched,
            sds((), i32))
        assert "tpu_custom_call" in out.as_text()


def test_deepseek_steps_compile_and_fit_one_chip(one_chip):
    """The cell's own programs (``chipbench/families/deepseek.py``
    ``aot_programs``): decode at the smallest and largest bucket and the
    512-token chunk, 11.0 GB of weights and the 1.81 GB pool on one chip."""
    from chipbench import spec
    cell = spec.load_cell("dsv3.serve.docqa")
    names = []
    for name, compile_ in cell.family.aot_programs(
            cell.model, cell.traffic, one_chip, False):
        ma = compile_().memory_analysis()
        held = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
        assert gib(held) < 15.0, (name, gib(held))
        assert 11.8 < gib(ma.argument_size_in_bytes) < 12.1, name
        names.append(name)
    assert names == ["decode, batch 1", "decode, batch 64",
                     "prefill chunk 512"]


def test_deepseek_chunk_with_decode_compiles_and_fits_one_chip(sds):
    """The chunk that carries the decode batch (PR 34) at the cell's size:
    512 chunk rows and 64 row slots through one pass of the layers. Built
    here from the cell's files (``aot_programs`` is the benchmark's and
    lists the two programs it had). A layer holds two latent kernels (the
    rows' fused update, the chunk's attention), two norm kernels and, in an
    expert layer, three grouped matmuls: 4 in the dense layer, 7 in the
    scanned one. The pool (1.69 GiB) stays in place: a copy of it would
    show among the temporaries."""
    from chipbench import spec
    from chipbench.weights import is_leaf
    from paddle_tpu.models import deepseek as D
    cell = spec.load_cell("dsv3.serve.docqa")
    fam, m, e = cell.family, cell.model, cell.traffic["engine"]
    config = fam.deepseek_config(m)
    params = jax.tree_util.tree_map(lambda leaf: sds(leaf.shape, bf16),
                                    fam.leaves(m), is_leaf=is_leaf)
    pool = sds((config.num_hidden_layers, e["num_blocks"],
                config.latent_width, e["block_size"]), bf16)
    max_nb = -(-e["max_seq_len"] // e["block_size"])
    c, b = e["prefill_chunk"], e["max_batch"]
    fn = D.DeepSeekServing.step_fn("prefill+decode", config, False, None)
    out = compile_for_chip(
        fn, params, pool, sds((max_nb,), i32), sds((), i32), sds((c,), i32),
        sds((), i32), sds((b, max_nb), i32), sds((b,), i32), sds((b,), i32))
    # outputs: the chunk's token and flag, the rows', the pool, the counts
    assert [(o.shape, str(o.dtype)) for o in out.out_info[:4]] == [
        ((), "int32"), ((), "bool"), ((b,), "int32"), ((b,), "bool")]
    ma = out.memory_analysis()
    held = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert 11.8 < gib(ma.argument_size_in_bytes) < 12.1
    assert gib(held) < 15.0, gib(held)
    assert gib(ma.temp_size_in_bytes) < 1.0, gib(ma.temp_size_in_bytes)
    assert out.as_text().count("tpu_custom_call") == 4 + 7


# -- GLM-5.2's learned sparse attention at published widths (PR 35) -------------

DSA_NH, DSA_HI, DSA_DI = 64, 32, 128
DSA_MAX_NB = 262                 # 33536 tokens of 128
DSA_POOL = (6, 64, MLA_W, BS)    # the kernels see one block at a time
DSA_IPOOL = (2, 64, DSA_DI, BS)


@pytest.mark.parametrize("batch", [1, 64])
def test_dsa_decode_kernels_compile(sds, batch):
    """The indexer's fused key write + scores and the latent decode under a
    selection, on the walk's 8 rows: at 64 rows x 262 table slots the 9-row
    schedule (padded to 16) alone is over SMEM's 1 MiB."""
    t = pa.dsa_width(DSA_MAX_NB, BS)
    assert t == 264 * BS

    def step(qi, wi, key, ipool, q, new, pool, tables, pos):
        walk = pa.mla_update_walk(tables, pos, BS)
        scores, ipool = pa.dsa_index_decode(qi, wi, key, ipool, walk, 1)
        sel = pa.dsa_select(scores[:, 0], pos, 2048)[:, None]
        out, pool = pa.mla_paged_decode(q, new, pool, walk, 2,
                                        rank=MLA_RANK, select=sel)
        return out, ipool, pool
    out = compile_for_chip(
        jax.jit(step, donate_argnums=(3, 6)),
        sds((batch, DSA_HI, DSA_DI), bf16), sds((batch, DSA_HI), f32),
        sds((batch, DSA_DI), bf16), sds(DSA_IPOOL, bf16),
        sds((batch, DSA_NH, MLA_W), bf16), sds((batch, MLA_W), bf16),
        sds(DSA_POOL, bf16), sds((batch, DSA_MAX_NB), i32),
        sds((batch,), i32))
    assert out.as_text().count("tpu_custom_call") == 2
    assert out.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_dsa_prefill_kernels_compile(sds):
    """A chunk of 512: index scores in tiles of 128 tokens x 32 heads
    against one block of keys a step, the exact selection (no sort), the
    latent prefill in tiles of 32 tokens x 64 heads under it, the selection
    spread to a token's head rows on the MXU."""
    assert pa._fit_mla_prefill_tile(512, DSA_NH, MLA_W, MLA_RANK, BS, 2,
                                    True) == 32

    def step(qi, wi, ipool, q, pool, table, start, n):
        scores = pa.dsa_index_prefill(qi, wi, ipool, table, start, n, 1)
        sel = pa.dsa_select(scores, start + jnp.arange(512, dtype=i32), 2048)
        return pa.mla_paged_prefill(q, pool, table, start, n, 2,
                                    rank=MLA_RANK, select=sel)
    out = compile_for_chip(
        step, sds((512, DSA_HI, DSA_DI), bf16), sds((512, DSA_HI), f32),
        sds(DSA_IPOOL, bf16), sds((512, DSA_NH, MLA_W), bf16),
        sds(DSA_POOL, bf16), sds((DSA_MAX_NB,), i32), sds((), i32),
        sds((), i32))
    text = out.as_text()
    assert text.count("tpu_custom_call") == 2 and " sort(" not in text


def test_glm_steps_compile_and_fit_one_chip(one_chip, capsys):
    """The cell's own programs (``chipbench/families/glm_dsa.py``
    ``aot_programs``): decode at the smallest and largest bucket, the
    512-token chunk and the chunk that carries 64 rows, 9.38 GB of weights
    and the two pools (3.62 + 0.27 GB) on one chip, both pools in place
    (aliased; a copy of either would show among the temporaries). The
    dense layer and the three runs of expert layers (shared x 3, full,
    shared) hold 23 kernels, 29 where the rows' index and latent kernels
    ride beside the chunk's; prints ``memory_analysis()``."""
    from chipbench import spec
    cell = spec.load_cell("glm52.serve.longdoc")
    seen = {}
    for name, compile_ in cell.family.aot_programs(
            cell.model, cell.traffic, one_chip, False):
        out = compile_()
        ma = out.memory_analysis()
        held = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
        with capsys.disabled():
            print(f"\n{name}: {ma}")
        assert gib(held) < 14.5, (name, gib(held))
        assert 12.2 < gib(ma.argument_size_in_bytes) < 12.5, name
        assert 3.6 < gib(ma.alias_size_in_bytes) < 3.7, name
        assert gib(ma.temp_size_in_bytes) < 1.0, name
        seen[name] = out.as_text().count("tpu_custom_call")
    assert seen == {"decode, batch 1": 23, "decode, batch 64": 23,
                    "prefill chunk 512": 23,
                    "prefill chunk 512 carrying batch 64": 29}


# -- Falcon-H1's state-space steps at published widths (PR 38) ------------------

def test_falcon_h1_steps_compile_and_fit_one_chip(one_chip, capsys):
    """The cell's own programs (``chipbench/families/falcon_h1.py``
    ``aot_programs``): decode at the smallest and largest bucket, the
    512-token chunk and the chunk that carries 64 rows, 10.5 GB of weights, the paged KV pool (1.13 GiB) and
    the recurrent state (65 slots, 1.53 GiB) on one chip, BOTH kinds in
    place (aliased: 2.66 GiB; a copy of the float32 state would show as
    1.5 GiB among the temporaries). Five kernels a program: the scan or the
    update, the paged attention, and the three RMSNorms (two in the layer
    loop, the head's); seven where the rows' update and attention ride
    beside the chunk's; prints ``memory_analysis()``."""
    from chipbench import spec
    cell = spec.load_cell("falconh1.serve.longreply")
    e = cell.traffic["engine"]
    state = (e["max_batch"] + 1) * 6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    kv = e["num_blocks"] * e["block_size"] * 6 * 2 * 512 * 2
    seen = {}
    for name, compile_ in cell.family.aot_programs(
            cell.model, cell.traffic, one_chip, False):
        out = compile_()
        ma = out.memory_analysis()
        held = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
        with capsys.disabled():
            print(f"\n{name}: {ma}")
        assert gib(held) < 14.5, (name, gib(held))
        assert 12.4 < gib(ma.argument_size_in_bytes) < 12.5, name
        # both caches whole, and a megabyte of the layout's padding
        assert state + kv <= ma.alias_size_in_bytes \
            < state + kv + (4 << 20), name
        assert gib(ma.temp_size_in_bytes) < 1.0, name
        seen[name] = out.as_text().count("tpu_custom_call")
    assert seen == {"decode, batch 1": 5, "decode, batch 64": 5,
                    "prefill chunk 512": 5,
                    "prefill chunk 512 carrying batch 64": 7}
