"""The comparison that decides ``correct``: each number beside its limit."""
from __future__ import annotations

import math
import statistics
import sys
from typing import Dict, List, Tuple


class Compared:
    """Numbers compared, each with its limit; ``correct`` iff all hold."""

    def __init__(self):
        self.rows: List[Tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def print(self, file=sys.stderr) -> None:
        for n, v, lim in self.rows:
            ok = "ok" if math.isfinite(v) and v <= lim else "FAILS"
            print(f"compared {n} = {v:.6g} limit {lim:.6g} {ok}", file=file)
        print(f"correct = {self.correct}", file=file, flush=True)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves=None) -> Tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Norms, not squares."""
    leaves = list(leaves if leaves is not None else ref)
    median = statistics.median(ref[k] for k in ref)
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
        if not gap <= worst:      # a NaN counts as the worst
            worst, at = gap, k
    return worst, at


def moved_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose gradient in the reference is not nought to rounding:
    at least a thousandth of the median leaf's. The others move under Adam
    by round-off alone and are left out of the change."""
    median = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= 1e-3 * median]
