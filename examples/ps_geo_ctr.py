"""Parameter-server flavors in one script: classic async sparse training,
geo-SGD dense sync, and a CTR table with show/click statistics + shrink.

Runs self-contained (server and workers share the process via the rpc
layer, exactly how tests drive the PS):
  python examples/ps_geo_ctr.py
"""
import os
import socket
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import paddle_tpu as paddle
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import PSClient

    paddle.set_device("cpu")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    rpc.init_rpc("ps_server:0", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{port}")

    # -- CTR sparse table: embeddings + show/click statistics ------------
    worker = PSClient("ps_server:0", async_push=True)
    worker.create_sparse_table(
        "ctr_emb", emb_dim=8,
        accessor={"type": "ctr", "lr": 0.1, "show_coeff": 0.2,
                  "click_coeff": 1.0})
    rng = np.random.RandomState(0)
    for step in range(5):
        ids = rng.randint(0, 100, 16)
        rows = worker.pull_sparse("ctr_emb", ids)      # gather embeddings
        grads = rng.randn(16, 8).astype(np.float32) * 0.01
        shows = np.ones(16, np.float32)
        clicks = (rng.rand(16) < 0.1).astype(np.float32)
        worker.push_sparse("ctr_emb", ids, grads, shows=shows,
                           clicks=clicks)
    worker.barrier()
    evicted = worker.shrink_sparse_table("ctr_emb", score_threshold=0.3,
                                         decay=0.9)
    print(f"CTR table: {evicted} low-score rows evicted on shrink")

    # -- geo-SGD: two workers train locally, sync deltas every 2 steps ---
    a = PSClient("ps_server:0")
    b = PSClient("ps_server:0")
    _, wa = a.init_geo("dense_w", [4, 4], sync_steps=2)
    _, wb = b.init_geo("dense_w", [4, 4], sync_steps=2)
    for _ in range(2):
        wa = a.geo_step("dense_w", wa - 0.1 * np.ones_like(wa))
    for _ in range(2):
        wb = b.geo_step("dense_w", wb - 0.2 * np.ones_like(wb))
    print("geo-SGD merged weight mean:",
          float(a.pull_dense("dense_w").mean()))  # -0.6 = A's -0.2 + B's -0.4

    worker.stop()


if __name__ == "__main__":
    main()
