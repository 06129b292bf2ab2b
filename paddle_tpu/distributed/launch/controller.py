"""Node controller: spawn/monitor/restart per-rank processes
(ref: python/paddle/distributed/launch/controllers/collective.py).

One process for each host. The design is single-controller SPMD: ONE
process drives every chip of its host through one jax mesh, and a chip
belongs to one process at a time. Children inherit the whole environment
and nothing partitions the chips among them, so several processes on a
host that has an accelerator would each try to take all of it, and hang.
``--nproc_per_node > 1`` is therefore the CPU simulation only: the
controller refuses it unless the children's ``JAX_PLATFORMS`` is ``cpu``.
The controller itself never touches jax.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

from ... import runtime as rt
from ...observability import get_logger, log_event


@dataclass
class LaunchConfig:
    script: str = ""
    script_args: List[str] = field(default_factory=list)
    nproc_per_node: int = 1
    nnodes: int = 1
    node_rank: int = 0
    master: Optional[str] = None      # "host:port"; None -> local ephemeral
    job_id: str = "default"
    log_dir: str = "log"
    max_restarts: int = 0
    devices: Optional[str] = None     # accepted for CLI parity, unused
    envs: dict = field(default_factory=dict)
    # run module (python -m mod) instead of a script
    run_module: bool = False
    heartbeat_interval: float = 5.0


class NodeController:
    def __init__(self, cfg: LaunchConfig):
        self.cfg = cfg
        self.server = None
        self.procs: List[subprocess.Popen] = []
        self.log_files = []

    # -- rendezvous bootstrap --------------------------------------------
    def _start_master(self):
        """Rank-0 node hosts the store. Its address is either fixed by
        --master (multi-node) or an ephemeral local port (single node)."""
        if self.cfg.master:
            host, port = self.cfg.master.rsplit(":", 1)
            if self.cfg.node_rank == 0:
                self.server = rt.TCPStoreServer(int(port))
            return host, int(port)
        self.server = rt.TCPStoreServer()
        return "127.0.0.1", self.server.port

    # -- child env --------------------------------------------------------
    def _child_env(self, local_rank: int, host: str, port: int,
                   restart_round: int) -> dict:
        world = self.cfg.nnodes * self.cfg.nproc_per_node
        rank = self.cfg.node_rank * self.cfg.nproc_per_node + local_rank
        env = dict(os.environ)
        env.update(self.cfg.envs)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_NNODES": str(self.cfg.nnodes),
            "PADDLE_NODE_RANK": str(self.cfg.node_rank),
            "PADDLE_MASTER": host,
            "MASTER_ADDR": host,
            "MASTER_PORT": str(port),
            "PADDLE_JOB_ID": self.cfg.job_id,
            "PADDLE_RESTART_ROUND": str(restart_round),
            "PADDLE_ELASTIC_MAX_RESTARTS": str(self.cfg.max_restarts),
            "PADDLE_HEARTBEAT_INTERVAL": str(self.cfg.heartbeat_interval),
        })
        return env

    # -- spawn ------------------------------------------------------------
    def _spawn(self, host: str, port: int, restart_round: int):
        os.makedirs(self.cfg.log_dir, exist_ok=True)
        self.procs, self.log_files = [], []
        for local_rank in range(self.cfg.nproc_per_node):
            rank = (self.cfg.node_rank * self.cfg.nproc_per_node + local_rank)
            cmd = [sys.executable]
            if self.cfg.run_module:
                cmd += ["-m", self.cfg.script]
            else:
                cmd += [self.cfg.script]
            cmd += self.cfg.script_args
            log_path = os.path.join(self.cfg.log_dir,
                                    f"workerlog.{rank}")
            # rank 0 tees to the controller's stdout like the reference.
            if rank == 0:
                lf = open(log_path, "wb")
                p = subprocess.Popen(
                    cmd, env=self._child_env(local_rank, host, port,
                                             restart_round),
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            else:
                lf = open(log_path, "wb")
                p = subprocess.Popen(
                    cmd, env=self._child_env(local_rank, host, port,
                                             restart_round),
                    stdout=lf, stderr=subprocess.STDOUT)
            self.procs.append(p)
            self.log_files.append(lf)

    def _pump_rank0(self):
        """Forward rank-0 output to our stdout AND its log file."""
        p0 = self.procs[0]
        if p0.stdout is None:
            return
        data = p0.stdout.read1(65536) if hasattr(p0.stdout, "read1") else b""
        if data:
            sys.stdout.buffer.write(data)
            sys.stdout.buffer.flush()
            self.log_files[0].write(data)
            self.log_files[0].flush()

    def _poll(self) -> Optional[int]:
        """None while all alive; else the first nonzero exit code or 0."""
        all_done = True
        for p in self.procs:
            rc = p.poll()
            if rc is None:
                all_done = False
            elif rc != 0:
                return rc
        return 0 if all_done else None

    def _terminate_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
        for lf in self.log_files:
            try:
                lf.close()
            except Exception:
                pass

    # -- main loop --------------------------------------------------------
    def _check_one_process_per_host(self):
        if self.cfg.nproc_per_node <= 1:
            return
        platforms = {**os.environ, **self.cfg.envs}.get("JAX_PLATFORMS")
        if platforms != "cpu":
            raise RuntimeError(
                f"--nproc_per_node={self.cfg.nproc_per_node} would start "
                f"{self.cfg.nproc_per_node} processes that each take every "
                f"chip of this host (JAX_PLATFORMS={platforms!r}), and a "
                "chip belongs to one process: they would hang. One process "
                "drives all local chips (use --nproc_per_node=1 and a mesh "
                "over jax.devices()); several processes per node are the "
                "CPU simulation, which needs JAX_PLATFORMS=cpu.")

    def run(self) -> int:
        self._check_one_process_per_host()
        host, port = self._start_master()
        restart_round = 0
        try:
            while True:
                self._spawn(host, port, restart_round)
                status = None
                while status is None:
                    self._pump_rank0()
                    status = self._poll()
                    if status is None:
                        time.sleep(0.05)
                self._pump_rank0()
                self._terminate_all()
                if status == 0:
                    return 0
                # rank-tagged structured logging (observability.get_logger
                # writes [ts] [rank N] ... to stderr)
                log = get_logger("paddle_tpu.launch")
                if restart_round >= self.cfg.max_restarts:
                    log.error("job failed with exit code %s after %s "
                              "restarts", status, restart_round)
                    log_event(log, "job_failed", exit_code=status,
                              restarts=restart_round)
                    return status
                restart_round += 1
                log.error("worker failed (exit %s); restart %s/%s",
                          status, restart_round, self.cfg.max_restarts)
                # Scrub job keys so the next round re-rendezvouses cleanly.
                if self.server is not None:
                    try:
                        c = rt.TCPStore(host, port, timeout=5.0)
                        c.set(f"{self.cfg.job_id}/restart_round",
                              str(restart_round).encode())
                        c.close()
                    except Exception:
                        pass
        finally:
            self._terminate_all()
            if self.server is not None:
                self.server.stop()


def launch(cfg: LaunchConfig) -> int:
    return NodeController(cfg).run()
