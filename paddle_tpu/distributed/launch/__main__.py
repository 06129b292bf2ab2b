"""CLI entry (ref: python/paddle/distributed/launch/main.py)."""
from __future__ import annotations

import argparse
import sys

from .controller import LaunchConfig, launch


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch distributed training: ONE process per host "
                    "drives all of its chips. --nproc_per_node>1 is the "
                    "CPU simulation and is refused unless "
                    "JAX_PLATFORMS=cpu")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--master", type=str, default=None,
                   help="host:port of the rendezvous store (multi-node)")
    p.add_argument("--job_id", type=str, default="default")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic checkpoint-restart rounds on failure")
    p.add_argument("--devices", "--gpus", type=str, default=None,
                   help="accepted for parity with the reference CLI and "
                        "ignored: nothing partitions a host's chips")
    p.add_argument("--heartbeat_interval", type=float, default=5.0)
    p.add_argument("-m", "--module", action="store_true",
                   help="run script as a module (python -m)")
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    cfg = LaunchConfig(
        script=args.script,
        script_args=args.script_args,
        nproc_per_node=args.nproc_per_node,
        nnodes=args.nnodes,
        node_rank=args.node_rank,
        master=args.master,
        job_id=args.job_id,
        log_dir=args.log_dir,
        max_restarts=args.max_restarts,
        devices=args.devices,
        run_module=args.module,
        heartbeat_interval=args.heartbeat_interval,
    )
    return launch(cfg)


if __name__ == "__main__":
    sys.exit(main())
