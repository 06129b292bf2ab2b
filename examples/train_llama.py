"""Train the flagship Llama on synthetic data — the compiled SPMD step.

Single chip:      python examples/train_llama.py
Virtual 8-chip:   JAX_PLATFORMS=cpu \
                  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                  python examples/train_llama.py --dp 2 --mp 2 --pp 2
"""
import argparse

import os
import sys

import numpy as np

# runnable from the repo root without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    import jax
    from paddle_tpu.models.llama import (ParallelConfig, build_train_step,
                                         llama_tiny, make_mesh)
    parallel = ParallelConfig(dp=args.dp, mp=args.mp, pp=args.pp,
                              microbatches=2 if args.pp > 1 else 1)
    # the mesh takes the default backend's devices: the chips on a TPU
    # host, the virtual CPU devices under JAX_PLATFORMS=cpu
    mesh = make_mesh(parallel) if parallel.total > 1 else None
    config = llama_tiny(vocab=512, hidden=64, layers=4, heads=4, kv_heads=4,
                        inter=128, seq=64)
    step, params, opt = build_train_step(config, parallel, mesh=mesh,
                                         lr=1e-3)
    rng = np.random.RandomState(0)
    batch = max(4, parallel.dp * 2)
    ids = rng.randint(0, config.vocab_size, (batch, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    for i in range(args.steps):
        params, opt, loss = step(params, opt, ids, labels)
        print(f"step {i}: loss {float(jax.device_get(loss)):.4f}")


if __name__ == "__main__":
    main()
