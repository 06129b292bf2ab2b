"""Two-process multi-host mesh (SURVEY §5.8, §2c bootstrap): the launch CLI
spawns two local processes that form ONE jax.distributed world on the CPU
backend (4+4 virtual devices), run a dp-over-hosts x mp-within-host train
step, and the loss must match the single-process computation.

This is the multi-node story's CI proxy: real DCN-vs-ICI placement follows
the same axis order (dp outermost over hosts — see
fleet/topology.py HybridCommunicateGroup docs)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_two_process_mesh_loss_matches_serial(tmp_path):
    out = tmp_path / "out.json"
    env = dict(os.environ)
    # CPU-only children: a chip belongs to one process, so the launcher
    # refuses --nproc_per_node=2 unless the children's backend is the CPU
    env["PYTHONPATH"] = REPO
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node=2", "--job_id=mh",
           f"--log_dir={tmp_path / 'logs'}",
           "tests/multihost_worker.py", str(out)]
    p = subprocess.run(cmd, cwd=REPO, env=env, timeout=280,
                       capture_output=True, text=True)
    logs = ""
    logdir = tmp_path / "logs"
    if logdir.exists():
        for f in sorted(logdir.iterdir()):
            logs += f"\n--- {f.name} ---\n" + f.read_text()[-2000:]
    if p.returncode != 0 and \
            "Multiprocess computations aren't implemented" in (
                p.stdout + p.stderr + logs):
        pytest.skip("this jax build's CPU backend has no cross-process "
                    "computations; needs a real multi-host (or gloo) env")
    assert p.returncode == 0, f"launch failed\n{p.stdout}\n{p.stderr}\n{logs}"
    assert out.exists(), f"no output written\n{p.stdout}\n{logs}"
    got = json.loads(out.read_text())
    assert got["world"] == 2 and got["devices"] == 8

    # serial reference: same numerics in-process
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randn(8, 4).astype(np.float32)
    w1 = rng.randn(16, 32).astype(np.float32) * 0.1
    w2 = rng.randn(32, 4).astype(np.float32) * 0.1
    losses = []
    for _ in range(3):
        h = np.maximum(x @ w1, 0.0)
        pred = h @ w2
        losses.append(float(np.mean((pred - y) ** 2)))
        dl = 2.0 * (pred - y) / pred.size
        gw2 = h.T @ dl
        dh = dl @ w2.T
        dh[h <= 0] = 0.0
        gw1 = x.T @ dh
        w1 -= 0.1 * gw1
        w2 -= 0.1 * gw2
    np.testing.assert_allclose(got["losses"], losses, rtol=1e-4, atol=1e-6)
