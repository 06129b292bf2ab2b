"""Hybrid-parallel topology (ref: python/paddle/distributed/fleet/base/topology.py).

The reference's ``HybridCommunicateGroup`` builds an N-D rank mesh with axis
order [dp, pp, sharding, sep, mp] and one NCCL communicator per axis. The
TPU-native equivalent builds ONE ``jax.sharding.Mesh`` over the physical
devices with the same named axes; "communicators" are just the axis names —
XLA emits the ICI collectives when sharded computations reference them.
Axis order matters for locality exactly like NCCL ring order did: mp (heaviest
traffic) is innermost so it maps to adjacent ICI neighbors, dp outermost.
An optional ep degree (expert parallel) reuses the sharding×sep×mp submesh.

Multi-host (DCN vs ICI): ``jax.devices()`` enumerates process-major, so the
OUTERMOST axes of the [dp, pp, sharding, sep, mp] order land across hosts —
dp's once-per-step gradient all-reduce rides the slow DCN link, while mp/sep
(per-layer collectives) stay on intra-host ICI. This is the same
dp-outer-over-nodes placement the reference's HybridCommunicateGroup
produces with its rank-ordered NCCL subgroups. Proven end-to-end by
tests/test_multihost.py (two jax.distributed processes, dp over hosts,
mp within, loss equal to serial).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from ..communication import Group

_AXIS_ORDER = ["dp", "pp", "sharding", "sep", "mp"]


def _pick_devices(n: int):
    """The first n devices of the default backend, and of no other: the
    chip's on a TPU host, the virtual CPU devices under JAX_PLATFORMS=cpu.
    Too few of them is an error, never a mesh built on the host's CPU
    beside an accelerator."""
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"need {n} devices for the hybrid topology but the default "
            f"backend ({devs[0].platform}) has {len(devs)} (for CPU tests "
            "set JAX_PLATFORMS=cpu and "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return devs[:n]


class CommunicateTopology:
    def __init__(self, hybrid_group_names=None, dims=None):
        self._parallel_names = hybrid_group_names or _AXIS_ORDER
        self._dims = dims or [1] * len(self._parallel_names)
        self._world_size = int(np.prod(self._dims))

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    def world_size(self):
        return self._world_size


class HybridCommunicateGroup:
    def __init__(self, topology: CommunicateTopology = None, *,
                 dp_degree=1, mp_degree=1, pp_degree=1, sharding_degree=1,
                 sep_degree=1, ep_degree=1, devices=None):
        if topology is not None:
            dims = {n: topology.get_dim(n) for n in topology.get_hybrid_group_names()}
            dp_degree = dims.get("dp", 1)
            pp_degree = dims.get("pp", 1)
            sharding_degree = dims.get("sharding", 1)
            sep_degree = dims.get("sep", 1)
            mp_degree = dims.get("mp", 1)
        self._dp_degree = dp_degree
        self._mp_degree = mp_degree
        self._pp_degree = pp_degree
        self._sharding_degree = sharding_degree
        self._sep_degree = sep_degree
        self._ep_degree = ep_degree
        total = dp_degree * mp_degree * pp_degree * sharding_degree * sep_degree
        if ep_degree > 1 and ep_degree > sharding_degree * sep_degree * mp_degree:
            raise ValueError(
                f"ep_degree {ep_degree} must divide into the non-dp/pp submesh "
                f"(sharding*sep*mp = {sharding_degree * sep_degree * mp_degree})")
        self.nranks = total
        devs = list(devices) if devices is not None else _pick_devices(total)
        dev_array = np.array(devs[:total]).reshape(
            dp_degree, pp_degree, sharding_degree, sep_degree, mp_degree)
        self.mesh = Mesh(dev_array, axis_names=tuple(_AXIS_ORDER))
        self.global_rank = 0  # single controller

    # -- degree / rank queries (reference API surface) ---------------------
    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sep_parallel_world_size(self):
        return self._sep_degree

    def get_expert_parallel_world_size(self):
        return self._ep_degree

    # In SPMD there is no per-process rank; ranks are symbolic (axis_index
    # inside compiled code). These return 0 for host-side logic, like rank 0.
    def get_data_parallel_rank(self):
        return 0

    def get_model_parallel_rank(self):
        return 0

    def get_stage_id(self):
        return 0

    def get_sharding_parallel_rank(self):
        return 0

    # -- groups ------------------------------------------------------------
    def get_data_parallel_group(self) -> Group:
        return Group("dp", self._dp_degree)

    def get_model_parallel_group(self) -> Group:
        return Group("mp", self._mp_degree)

    def get_pipe_parallel_group(self) -> Group:
        return Group("pp", self._pp_degree)

    def get_sharding_parallel_group(self) -> Group:
        return Group("sharding", self._sharding_degree)

    def get_sep_parallel_group(self) -> Group:
        return Group("sep", self._sep_degree)

    def get_expert_parallel_group(self) -> Group:
        return Group("ep", self._ep_degree)

    def get_check_parallel_group(self, *a, **k) -> Group:
        return Group("mp", self._mp_degree)

    def get_data_parallel_group_src_rank(self):
        return 0

    def get_model_parallel_group_src_rank(self):
        return 0

    # -- pipeline helpers --------------------------------------------------
    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self._pp_degree - 1

    def get_rank_from_stage(self, stage_id, **kwargs):
        return stage_id

    def topology(self):
        return CommunicateTopology(_AXIS_ORDER,
                                   [self._dp_degree, self._pp_degree,
                                    self._sharding_degree, self._sep_degree,
                                    self._mp_degree])


_hcg: Optional[HybridCommunicateGroup] = None


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup):
    global _hcg
    _hcg = hcg


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _hcg
