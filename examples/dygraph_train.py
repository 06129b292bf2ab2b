"""Dygraph-style training (the reference's eager workflow): Layer + eager
backward + optimizer, no explicit jit."""
import os
import sys

import numpy as np

# runnable from the repo root without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 64), nn.GELU(), nn.Linear(64, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 16).astype(np.float32))
    y = paddle.to_tensor(rng.randn(64, 4).astype(np.float32))
    for i in range(20):
        loss = paddle.nn.functional.mse_loss(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        if i % 5 == 0:
            print(f"step {i}: loss {float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
