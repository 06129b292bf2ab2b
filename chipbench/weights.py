"""Random weights from the seed, made on the device in one jitted call and in
the type they are held in. The tree has the layout the program's entry
points take (per-layer leaves stacked on axis 0); the reference takes the
same tree, made again from the same seed."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import flops

STD = 0.02
NORM_LEAVES = ("input_norm", "post_norm", "final_norm")


def llama_config(model: dict):
    """The program's own configuration object from the published keys."""
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        max_position_embeddings=model["max_position_embeddings"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        tie_word_embeddings=model["tie_word_embeddings"],
        dtype=jnp.dtype(model["torch_dtype"]))


def parallel_config(t: dict):
    """The program's layout object from a training mix's file."""
    from paddle_tpu.models.llama import ParallelConfig
    return ParallelConfig(remat=True, remat_policy=t["remat_policy"])


def fold_seed(seed: int) -> int:
    """Any whole number to what a 32-bit PRNG seed holds."""
    return int(seed) % (2 ** 31 - 1)


def leaf_shapes(m) -> dict:
    h, i, v, n = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                  m["num_hidden_layers"])
    q = m["num_attention_heads"] * flops.head_dim(m)
    kv = flops.kv_dim(m)
    return {
        "embed": (v, h),
        "layers": {
            "input_norm": (n, h), "q_proj": (n, h, q), "k_proj": (n, h, kv),
            "v_proj": (n, h, kv), "o_proj": (n, q, h), "post_norm": (n, h),
            "gate_proj": (n, h, i), "up_proj": (n, h, i),
            "down_proj": (n, i, h),
        },
        "final_norm": (h,),
        "lm_head": (h, v),
    }


def _make(key, shapes, dtype):
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        if path[-1].key in NORM_LEAVES:
            out.append(jnp.ones(shape, dtype))
        else:
            out.append((jax.random.normal(k, shape, dtype)
                        * jnp.asarray(STD, dtype)))
    return jax.tree_util.tree_unflatten(tree, out)


def make_weights(m, seed: int, dtype=jnp.bfloat16, shardings=None):
    """The whole tree in one jitted call. ``shardings`` (a tree of
    shardings, as the program laid its own parameters out) places it."""
    shapes = leaf_shapes(m)
    fn = jax.jit(functools.partial(_make, shapes=shapes, dtype=dtype),
                 out_shardings=shardings)
    return fn(jax.random.PRNGKey(fold_seed(seed)))
