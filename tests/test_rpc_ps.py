"""distributed.rpc + parameter-server mode: in-process and multi-process."""
import os
import pickle
import subprocess
import sys

import numpy as np

import paddle_tpu  # noqa: F401


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _double(x):
    return x * 2


def _boom():
    raise ValueError("intentional")


def test_rpc_single_world():
    from paddle_tpu.distributed import rpc
    port = _free_port()
    rpc.init_rpc("worker0", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{port}")
    assert rpc.rpc_sync("worker0", _double, args=(21,)) == 42
    fut = rpc.rpc_async("worker0", _double, args=(5,))
    assert fut.result(timeout=10) == 10
    info = rpc.get_current_worker_info()
    assert info.name == "worker0" and info.rank == 0
    rpc.shutdown()


def test_rpc_error_propagates():
    from paddle_tpu.distributed import rpc
    port = _free_port()
    rpc.init_rpc("workerE", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{port}")
    try:
        rpc.rpc_sync("workerE", _boom)
        raised = False
    except RuntimeError as e:
        raised = "intentional" in str(e)
    finally:
        rpc.shutdown()
    assert raised


def test_rpc_rejects_unauthenticated():
    """A connection without the shared-secret preamble must be dropped
    before any unpickling (no code execution for strangers)."""
    import socket
    import struct
    from paddle_tpu.distributed import rpc
    port = _free_port()
    rpc.init_rpc("workerA", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{port}")
    try:
        info = rpc.get_current_worker_info()
        payload = pickle.dumps({"op": "call", "fn": _double,
                                "args": (1,), "kwargs": {}})
        with socket.create_connection((info.ip, info.port), timeout=5) as s:
            # no token preamble: server must close without replying
            s.sendall(struct.pack(">I", len(payload)) + payload)
            s.settimeout(2.0)
            try:
                data = s.recv(1024)
            except (socket.timeout, ConnectionError):
                data = b""
        assert data == b""
        # wrong token: also dropped (single send so the server's early close
        # can't race a second sendall into BrokenPipeError)
        with socket.create_connection((info.ip, info.port), timeout=5) as s:
            s.sendall(b"\x00" * 32 + struct.pack(">I", len(payload)) + payload)
            s.settimeout(2.0)
            try:
                data = s.recv(1024)
            except (socket.timeout, ConnectionError):
                data = b""
        assert data == b""
        # the authenticated path still works
        assert rpc.rpc_sync("workerA", _double, args=(4,)) == 8
    finally:
        rpc.shutdown()


def test_ps_tables_inprocess():
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import PSClient, service
    service._TABLES.clear()
    port = _free_port()
    rpc.init_rpc("ps_server:0", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{port}")
    client = PSClient("ps_server:0")
    assert client.create_dense_table("w", [4, 3])
    w0 = client.pull_dense("w")
    assert w0.shape == (4, 3) and (w0 == 0).all()
    g = np.ones((4, 3), np.float32)
    client.push_dense("w", g, lr=0.1)
    np.testing.assert_allclose(client.pull_dense("w"), -0.1 * g)

    assert client.create_sparse_table("emb", 8)
    rows = client.pull_sparse("emb", [3, 7, 3])
    assert rows.shape == (3, 8)
    np.testing.assert_allclose(rows[0], rows[2])  # same id, same row
    client.push_sparse("emb", [3], np.ones((1, 8), np.float32), lr=0.5)
    rows2 = client.pull_sparse("emb", [3])
    np.testing.assert_allclose(rows2[0], rows[0] - 0.5)
    st = client.stat()
    assert st["w"][0] == "dense" and st["emb"] == ("sparse", 2)
    rpc.shutdown()
    service._TABLES.clear()


_WORKER_SCRIPT = r"""
import os, sys
import numpy as np
sys.path.insert(0, os.environ["REPO"])
from paddle_tpu.distributed import rpc

def fn(a, b):
    return a + b

rank = int(sys.argv[1])
port = sys.argv[2]
rpc.init_rpc(f"w{rank}", rank=rank, world_size=2,
             master_endpoint=f"127.0.0.1:{port}")
if rank == 0:
    out = rpc.rpc_sync("w1", fn, args=(40, 2))
    assert out == 42, out
    print("RPC_OK")
else:
    import time
    time.sleep(2.0)
rpc.shutdown()
"""


def test_rpc_two_processes(tmp_path):
    script = tmp_path / "rpc_worker.py"
    script.write_text(_WORKER_SCRIPT)
    port = str(_free_port())
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, REPO=repo,
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, str(script), str(r), port],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for r in (0, 1)]
    outs = [p.communicate(timeout=120)[0].decode() for p in procs]
    assert procs[0].returncode == 0, outs[0]
    assert procs[1].returncode == 0, outs[1]
    assert "RPC_OK" in outs[0]


_PS_SERVER_SCRIPT = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
from paddle_tpu.distributed import fleet

assert fleet.is_server()
print("PS_SERVER_STARTING", flush=True)  # before init: rendezvous blocks
fleet.init_server()                      # until the trainer joins
print("PS_SERVER_UP", flush=True)
fleet.run_server()                       # blocks; parent terminates us
"""


def test_fleet_ps_mode_cross_process(tmp_path):
    """Reference PS flow: a PSERVER process (init_server/run_server) and a
    TRAINER in this process (init_worker, table ops, stop_worker), roles and
    endpoints from the PADDLE_* env the launcher would set."""
    import time
    port = _free_port()
    saved_env = dict(os.environ)
    env = dict(os.environ)
    env.update({
        "REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "TRAINING_ROLE": "PSERVER",
        "PADDLE_PSERVERS_IP_PORT_LIST": f"127.0.0.1:{port}",
        "PADDLE_PSERVER_ID": "0",
        "PADDLE_MASTER": f"127.0.0.1:{port}",
        "PADDLE_WORLD_SIZE": "2",
        "PADDLE_RANK": "0",
        "JAX_PLATFORMS": "cpu",
    })
    script = tmp_path / "ps_server.py"
    script.write_text(_PS_SERVER_SCRIPT)
    srv = subprocess.Popen([sys.executable, str(script)], env=env,
                           stdout=subprocess.PIPE, text=True)
    try:
        line = srv.stdout.readline()
        assert "PS_SERVER_STARTING" in line, line

        os.environ.update({
            "TRAINING_ROLE": "TRAINER",
            "PADDLE_PSERVERS_IP_PORT_LIST": f"127.0.0.1:{port}",
            "PADDLE_MASTER": f"127.0.0.1:{port}",
            "PADDLE_WORLD_SIZE": "2",
            "PADDLE_RANK": "1",
            "PADDLE_TRAINER_ID": "0",
        })
        from paddle_tpu.distributed import fleet
        assert fleet.is_worker()
        client = fleet.init_worker()
        assert client.create_sparse_table("fleet_emb", 4)
        rows = client.pull_sparse("fleet_emb", [1, 2, 3])
        assert rows.shape == (3, 4)
        client.push_sparse("fleet_emb", [1], np.ones((1, 4)), lr=1.0)
        rows2 = client.pull_sparse("fleet_emb", [1])
        np.testing.assert_allclose(rows2[0], rows[0] - 1.0, atol=1e-6)
        fleet.stop_worker()
    finally:
        srv.terminate()
        srv.wait(timeout=10)
        os.environ.clear()
        os.environ.update(saved_env)


def test_ctr_accessor_stats_and_shrink():
    """CTR sparse table (ref: ctr_common_accessor): pushes carry show/click
    increments; shrink decays the stats and evicts low-score rows."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import PSClient, service
    service._TABLES.clear()
    port = _free_port()
    rpc.init_rpc("ps_server:0", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{port}")
    client = PSClient("ps_server:0")
    assert client.create_sparse_table(
        "ctr_emb", 4, accessor={"type": "ctr", "lr": 0.1,
                                "show_coeff": 0.2, "click_coeff": 1.0})
    client.pull_sparse("ctr_emb", [1, 2])     # materialize rows
    g = np.ones((2, 4), np.float32)
    # row 1: hot (many shows + clicks); row 2: cold
    client.push_sparse("ctr_emb", [1, 2], g, shows=[100.0, 1.0],
                       clicks=[10.0, 0.0])
    t = service._TABLES["ctr_emb"]
    assert t["rows"][1]["state"]["show"] == 100.0
    assert t["rows"][1]["state"]["click"] == 10.0
    # duplicate-id merge sums the stats too
    client.push_sparse("ctr_emb", [1, 1], np.zeros((2, 4), np.float32),
                       shows=[1.0, 2.0], clicks=[0.0, 1.0])
    assert t["rows"][1]["state"]["show"] == 103.0
    assert t["rows"][1]["state"]["click"] == 11.0
    # shrink: decay 0.5, threshold 1.0 -> cold row 2 evicted, hot row 1 kept
    evicted = client.shrink_sparse_table("ctr_emb", score_threshold=1.0,
                                         decay=0.5)
    assert evicted == 1
    assert 1 in t["rows"] and 2 not in t["rows"]
    assert t["rows"][1]["state"]["show"] == 103.0 * 0.5
    rpc.shutdown()
    service._TABLES.clear()


def test_geo_sgd_two_workers():
    """geo-SGD (ref: GeoCommunicator): two workers train locally and sync
    their parameter deltas every k steps; both converge to the merged
    global weights containing each other's updates."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import PSClient, service
    service._TABLES.clear()
    port = _free_port()
    rpc.init_rpc("ps_server:0", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{port}")
    a = PSClient("ps_server:0")
    b = PSClient("ps_server:0")
    _, w_a = a.init_geo("geo_w", [2, 2], sync_steps=2)
    _, w_b = b.init_geo("geo_w", [2, 2], sync_steps=2)

    # worker A: two local steps of +1 each; second geo_step syncs
    w_a = w_a + 1.0
    w_a = a.geo_step("geo_w", w_a)          # step 1: local only
    w_a = w_a + 1.0
    w_a = a.geo_step("geo_w", w_a)          # step 2: pushes delta=+2, pulls
    np.testing.assert_allclose(w_a, np.full((2, 2), 2.0))

    # worker B trained in parallel from the ORIGINAL zeros: -1 per step
    w_b = w_b - 1.0
    w_b = b.geo_step("geo_w", w_b)
    w_b = w_b - 1.0
    w_b = b.geo_step("geo_w", w_b)          # pushes delta=-2 onto A's +2
    np.testing.assert_allclose(w_b, np.zeros((2, 2)))
    # A's next sync sees B's contribution merged in
    w_a = a.geo_step("geo_w", w_a)
    w_a = a.geo_step("geo_w", w_a)          # delta 0, pulls merged global
    np.testing.assert_allclose(w_a, np.zeros((2, 2)))
    rpc.shutdown()
    service._TABLES.clear()
