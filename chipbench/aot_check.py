"""Compile every program a cell needs, at its real size, for a described
v5e (no chip attached), from the cell's configuration and traffic files:

    JAX_PLATFORMS=cpu python3 chipbench/aot_check.py --workload <name> [--reference]

Prints ``memory_analysis()`` per device for each program. Run by hand before
a chip call; nothing runs, so it says nothing about results or times. The
compile cache is off around it (such a compile cannot be read back).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GIB = 2.0 ** 30


def report(name, compiled, t0):
    ma = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"{name}: arguments {ma.argument_size_in_bytes / GIB:.2f} GiB "
          f"(aliased {ma.alias_size_in_bytes / GIB:.2f}), outputs "
          f"{ma.output_size_in_bytes / GIB:.2f}, temporaries "
          f"{ma.temp_size_in_bytes / GIB:.2f}; {kernels} tpu_custom_call(s); "
          f"compiled in {time.perf_counter() - t0:.0f} s", flush=True)


def check_train(cell, topo, with_reference):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.models import llama as L
    from paddle_tpu.ops import _common
    from chipbench import reference, weights
    model, t = cell.model, cell.traffic
    parallel = weights.parallel_config(t)
    config = weights.llama_config(model)
    sds = jax.ShapeDtypeStruct
    shapes = weights.leaf_shapes(model)
    is_shape = lambda x: isinstance(x, tuple)
    one = SingleDeviceSharding(topo.devices[0])
    tree = lambda dtype: jax.tree_util.tree_map(
        lambda s: sds(s, dtype, sharding=one), shapes, is_leaf=is_shape)
    params, moments = tree(jnp.bfloat16), tree(jnp.float32)
    opt = {"m": moments, "v": moments,
           "t": sds((), jnp.float32, sharding=one)}
    batch = sds((t["batch"], t["seq"]), jnp.int32, sharding=one)
    # build_train_step makes real arrays; lower its step from shapes
    # instead, with the program's own pieces and use_flash on
    with _common.interpret_mode(False):
        step = _train_step_from_shapes(L, config, parallel, t["lr"])
        t0 = time.perf_counter()
        compiled = step.lower(params, opt, batch, batch).compile()
    report(f"{cell.name} train step", compiled, t0)
    if not with_reference:
        return
    hp = dict(t["adamw"], lr=t["lr"])
    progs = reference.train_programs(model, hp, "f32")
    lay = {k: sds(s[1:], jnp.bfloat16, sharding=one)
           for k, s in shapes["layers"].items()}
    lay32 = {k: sds(s[1:], jnp.float32, sharding=one)
             for k, s in shapes["layers"].items()}
    x = sds((t["batch"], t["seq"], model["hidden_size"]), jnp.float32,
            sharding=one)
    tt = sds((), jnp.float32, sharding=one)
    pick = lambda d, names: {n: d[n] for n in names}
    for name, names in (("attn_bwd", reference.ATTN_LEAVES),
                        ("mlp_bwd", reference.MLP_LEAVES)):
        t0 = time.perf_counter()
        c = progs[name].lower(pick(lay, names), pick(lay32, names),
                              pick(lay32, names), x, x, tt).compile()
        report(f"reference {name}", c, t0)
    n = t["batch"] * t["seq"]
    t0 = time.perf_counter()
    c = progs["head_bwd"].lower(
        sds(shapes["final_norm"], jnp.bfloat16, sharding=one),
        sds(shapes["lm_head"], jnp.bfloat16, sharding=one),
        sds((n, model["hidden_size"]), jnp.float32, sharding=one),
        sds((n,), jnp.int32, sharding=one), 1024).compile()
    report("reference head_bwd", c, t0)


def _train_step_from_shapes(L, config, parallel, lr):
    """``build_train_step``'s jitted step without its arrays: the same loss
    and AdamW update, traced from shapes."""
    import jax

    def step(p, opt, ids, labels):
        loss, grads = jax.value_and_grad(
            lambda p_: L.llama_loss(p_, ids, labels, config, parallel, None,
                                    use_flash=True))(p)
        new_p, new_opt = L._adamw_update(p, grads, opt, lr)
        return new_p, new_opt, loss
    return jax.jit(step, donate_argnums=(0, 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from chipbench import spec
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = spec.load_cell(args.workload)
    kind = cell.traffic["kind"]
    if kind == "train":
        check_train(cell, topo, args.reference)
    else:
        from chipbench import aot_serve
        aot_serve.check(cell, topo, args.reference, report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
