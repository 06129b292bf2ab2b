"""Required operations and bytes become the least time the chip could take.

Required means what the algorithm needs once: matmul parameters only (an
embedding lookup is no matmul), causal attention counted as the half square
it is, nothing recomputed. What a model requires is counted by its family
(``families/<name>.py``), from shapes alone; the roofline is here.
"""
from __future__ import annotations


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
