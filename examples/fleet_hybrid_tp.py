"""The Fleet collective workflow (the reference's primary distributed API):
fleet.init with a hybrid strategy -> fleet.distributed_model ->
fleet.distributed_optimizer -> compiled train step over the hybrid mesh.

Runs on 8 virtual CPU devices so it works anywhere (the mesh takes the
default backend's devices, so the backend is pinned to the CPU here):
  python examples/fleet_hybrid_tp.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
    from paddle_tpu.jit import TrainStep

    paddle.set_device("cpu")
    vocab, hidden, seq = 128, 64, 32

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln = nn.LayerNorm(hidden)
            self.fc_in = ColumnParallelLinear(hidden, 4 * hidden,
                                              gather_output=False)
            self.fc_out = RowParallelLinear(4 * hidden, hidden,
                                            input_is_parallel=True)

        def forward(self, x):
            return x + self.fc_out(F.gelu(self.fc_in(self.ln(x))))

    class GPT2Tiny(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = VocabParallelEmbedding(vocab, hidden)
            self.block = Block()
            self.head = ColumnParallelLinear(hidden, vocab, has_bias=False)

        def forward(self, ids):
            return self.head(self.block(self.emb(ids)))

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    print("hybrid mesh:", dict(hcg.mesh.shape))

    paddle.seed(0)
    model = fleet.distributed_model(GPT2Tiny())
    opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=1e-2, parameters=model.parameters()))

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, vocab]),
                               labels.reshape([-1])).mean()

    step = TrainStep(model, loss_fn, opt, mesh=hcg.mesh, batch_spec=P("dp"))
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, vocab, (8, seq)).astype(np.int32))
    labels = paddle.to_tensor(np.roll(ids.numpy(), -1, 1).astype(np.int64))
    for i in range(5):
        loss = step(ids, labels=labels)
        print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
