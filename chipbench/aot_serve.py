"""The serving half of ``aot_check.py``: the engine's own jitted decode and
prefill programs, lowered from the shapes the cell's files give."""
from __future__ import annotations

import time


def check(cell, topo, with_reference, report):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.models import llama as L
    from paddle_tpu.ops import _common
    from chipbench import reference, weights
    model, e = cell.model, cell.traffic["engine"]
    config = weights.llama_config(model)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.tree_util.tree_map(
        lambda s: sds(s, jnp.bfloat16), weights.leaf_shapes(model),
        is_leaf=lambda x: isinstance(x, tuple))
    kvd = config.num_key_value_heads * config.head_dim
    pool = sds((config.num_hidden_layers, e["num_blocks"], kvd,
                e["block_size"]), jnp.bfloat16)
    max_nb = -(-e["max_seq_len"] // e["block_size"])
    fz = L._freeze_config(config)
    i32 = jnp.int32
    buckets, b = [], 1
    while b < e["max_batch"]:
        buckets.append(b)
        b *= 2
    buckets.append(e["max_batch"])
    with _common.interpret_mode(False):
        for b in (buckets[0], buckets[-1]):
            t0 = time.perf_counter()
            c = L._jitted_paged_decode(fz).lower(
                params, pool, pool, sds((b, max_nb), i32), sds((b,), i32),
                sds((b,), i32)).compile()
            report(f"{cell.name} decode, batch {b}", c, t0)
        t0 = time.perf_counter()
        c = L._jitted_paged_prefill(fz).lower(
            params, pool, pool, sds((max_nb,), i32), sds((), i32),
            sds((e["prefill_chunk"],), i32), sds((), i32)).compile()
        report(f"{cell.name} prefill chunk {e['prefill_chunk']}", c, t0)
    if with_reference:
        from chipbench.serve import check_shape
        padded, last_max = check_shape(cell.traffic)
        t0 = time.perf_counter()
        c = reference._logits_jit.lower(
            params, sds((1, padded), i32), sds((), i32),
            m_items=reference._hashable(model), mode="f32",
            last=last_max).compile()
        report(f"reference forward at {padded} tokens", c, t0)
