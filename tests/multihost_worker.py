"""Worker for tests/test_multihost.py: one of two processes forming a
single jax.distributed world on the CPU backend (4 virtual devices per
process -> an 8-device dp-over-hosts x mp-within-host mesh).

Run via the launch CLI (which provides PADDLE_MASTER / PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM); argv[1] is the output JSON path rank 0 writes its
losses to. The launcher's caller sets JAX_PLATFORMS=cpu: two processes on
one host cannot share its chips.
"""
import json
import os
import sys

os.environ.setdefault(  # noqa: PTA007 -- process-lifetime: worker subprocess startup config
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402

from paddle_tpu.distributed import env as denv  # noqa: E402

denv.init_parallel_env()

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P)


def main():
    out_path = sys.argv[1]
    assert jax.process_count() == 2, jax.process_count()
    cpu_devs = [d for d in jax.devices() if d.platform == "cpu"]
    assert len(cpu_devs) == 8, len(cpu_devs)
    # dp (outer) maps across hosts — gradient all-reduce rides the
    # inter-host link; mp (inner) stays within a host. Device order from
    # jax.devices() is process-major, so the natural reshape gives that.
    mesh = Mesh(np.array(cpu_devs).reshape(2, 4), ("dp", "mp"))

    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randn(8, 4).astype(np.float32)
    w1 = rng.randn(16, 32).astype(np.float32) * 0.1
    w2 = rng.randn(32, 4).astype(np.float32) * 0.1

    def put(arr, spec):
        return jax.device_put(arr, NamedSharding(mesh, spec))

    xs = put(x, P("dp", None))
    ys = put(y, P("dp", None))
    w1s = put(w1, P(None, "mp"))   # column-parallel
    w2s = put(w2, P("mp", None))   # row-parallel

    def loss_fn(w1, w2, x, y):
        h = jax.nn.relu(x @ w1)
        return jnp.mean((h @ w2 - y) ** 2)

    @jax.jit
    def step(w1, w2, x, y):
        l, g = jax.value_and_grad(loss_fn, argnums=(0, 1))(w1, w2, x, y)
        return l, w1 - 0.1 * g[0], w2 - 0.1 * g[1]

    losses = []
    for _ in range(3):
        l, w1s, w2s = step(w1s, w2s, xs, ys)
        losses.append(float(jax.device_get(l)))

    if jax.process_index() == 0:
        with open(out_path, "w") as fh:
            json.dump({"losses": losses,
                       "world": jax.process_count(),
                       "devices": len(cpu_devs)}, fh)
    print(f"rank {jax.process_index()} done: {losses}")


if __name__ == "__main__":
    main()
