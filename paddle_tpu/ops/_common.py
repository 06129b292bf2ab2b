"""Shared kernel plumbing."""
from __future__ import annotations

import jax

_FORCE_INTERPRET = None


def set_interpret(value: bool | None):
    """Override interpret-mode detection (None = auto)."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = value


def mosaic_trace_ctx():
    """Trace Pallas kernels with x64 off: the package enables jax_enable_x64
    globally (Paddle dtype semantics), but Mosaic cannot legalize the 64-bit
    index/constant types that leak into the kernel trace ("failed to legalize
    operation 'func.return'" on v5e). Kernel inputs/outputs are explicit f32/
    bf16, so disabling x64 inside the trace is semantics-preserving."""
    return jax.enable_x64(False)


# Latest cost_estimate() values per named kernel site, recorded at TRACE
# time (cost_estimate() runs while jax traces the enclosing function, so
# after one lowering of a program the table holds the exact FLOPs/bytes
# each kernel site claimed for the shapes that program runs). Keys are the
# stable ``name=`` strings threaded through every pallas_call site;
# RooflineLedger (observability) joins this against the per-platform
# roofline tables for per-kernel compute/memory-bound attribution.
_KERNEL_COSTS: dict = {}


def cost_estimate(flops, transcendentals=0, bytes_accessed=0, name=None):
    """``pl.CostEstimate`` for a ``pallas_call`` site, clamped to ints.

    Without it, XLA costs a custom call at zero FLOPs, so StepMetrics MFU
    (observability) under-reports every kernel-backed step. Values are
    ESTIMATES for attribution, not exact op counts — kernels pass the
    matmul/exp/traffic totals of the tile schedule they actually run
    (live tiles only for the varlen flat schedules). The AST lint
    tests/test_pallas_cost_lint.py keeps every kernel site honest.

    ``name=`` is the site's stable kernel name: when given, the clamped
    values are recorded into the process-wide table behind
    :func:`kernel_cost_table` (keyed by that name, latest trace wins,
    ``calls`` counts how many traces hit the site)."""
    from jax.experimental import pallas as pl
    fl = max(int(flops), 0)
    tr = max(int(transcendentals), 0)
    ba = max(int(bytes_accessed), 0)
    if name is not None:
        rec = _KERNEL_COSTS.setdefault(
            name, {"flops": 0, "transcendentals": 0, "bytes_accessed": 0,
                   "calls": 0, "total_flops": 0, "total_transcendentals": 0,
                   "total_bytes_accessed": 0})
        rec["flops"] = fl
        rec["transcendentals"] = tr
        rec["bytes_accessed"] = ba
        rec["calls"] += 1
        # cumulative totals: a kernel called L times while one program
        # traces fires this L times, so the WINDOW DELTA of the totals
        # (snapshot_kernel_costs / kernel_costs_since) is that program's
        # exact per-step cost for the site — what RooflineLedger ingests
        rec["total_flops"] += fl
        rec["total_transcendentals"] += tr
        rec["total_bytes_accessed"] += ba
    return pl.CostEstimate(flops=fl, transcendentals=tr, bytes_accessed=ba)


def snapshot_kernel_costs() -> dict:
    """Opaque marker for :func:`kernel_costs_since` (per-name cumulative
    totals at this instant)."""
    return {name: (rec["calls"], rec["total_flops"],
                   rec["total_transcendentals"], rec["total_bytes_accessed"])
            for name, rec in _KERNEL_COSTS.items()}


def kernel_costs_since(snapshot: dict) -> dict:
    """Per-kernel cost accumulated since ``snapshot`` — trace one program
    between the two calls and this is its exact per-execution kernel cost,
    summed over every invocation (layers, chunks) of each named site."""
    out = {}
    for name, rec in _KERNEL_COSTS.items():
        c0, f0, t0, b0 = snapshot.get(name, (0, 0, 0, 0))
        calls = rec["calls"] - c0
        if calls <= 0:
            continue
        out[name] = {"calls": calls,
                     "flops": rec["total_flops"] - f0,
                     "transcendentals": rec["total_transcendentals"] - t0,
                     "bytes_accessed": rec["total_bytes_accessed"] - b0}
    return out


def reset_kernel_costs() -> None:
    """Clear the observed-cost table (test isolation; static sites stay)."""
    _KERNEL_COSTS.clear()


def _static_cost_sites():
    """AST enumeration of every ``pallas_call(..., cost_estimate=...)``
    site under ``ops/`` — the same sites the PTA003 lint floors — with the
    ``name=`` string literal pulled out of the cost-estimate call. Sites
    without a literal name key as ``<module>:<line>``."""
    import ast
    import os
    out = {}
    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    for fname in sorted(os.listdir(pkg_dir)):
        if not fname.endswith(".py") or fname.startswith("__"):
            continue
        with open(os.path.join(pkg_dir, fname), encoding="utf-8") as fh:
            try:
                tree = ast.parse(fh.read())
            except SyntaxError:
                continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            ident = (fn.attr if isinstance(fn, ast.Attribute)
                     else fn.id if isinstance(fn, ast.Name) else None)
            if ident != "pallas_call":
                continue
            ce = next((kw.value for kw in node.keywords
                       if kw.arg == "cost_estimate"), None)
            if ce is None:
                continue
            name = None
            if isinstance(ce, ast.Call):
                for kw in ce.keywords:
                    if kw.arg == "name" and isinstance(kw.value,
                                                       ast.Constant):
                        name = kw.value.value
            key = name or f"{fname[:-3]}:{node.lineno}"
            out[key] = {"module": fname[:-3], "line": node.lineno,
                        "named": name is not None}
    return out


def kernel_cost_table() -> dict:
    """Every registered pallas_call cost site, keyed by stable kernel name.

    Merges the static AST enumeration (all sites, whether or not they have
    traced yet this process) with the runtime-observed values recorded by
    :func:`cost_estimate` ``name=``: each entry carries ``module``/``line``
    (where the site lives), ``named`` (has a stable name literal), and —
    once a program using the kernel has been traced — the latest
    ``flops``/``bytes_accessed``/``transcendentals`` plus a ``calls`` trace
    count (None/0 for sites not yet traced). PTA003 floors the site count;
    the unit test floors this table against the same constant."""
    table = _static_cost_sites()
    for name, rec in _KERNEL_COSTS.items():
        entry = table.setdefault(name, {"module": None, "line": None,
                                        "named": True})
        entry.update(rec)
    for entry in table.values():
        entry.setdefault("flops", None)
        entry.setdefault("bytes_accessed", None)
        entry.setdefault("transcendentals", None)
        entry.setdefault("calls", 0)
    return table


class _InterpretOverride:
    """Context manager that forces interpret mode for one block and
    restores the PREVIOUS override (not a hard-coded value) on exit —
    the restore discipline PTA007 enforces. Reentrant-safe: nesting
    saves/restores like a stack."""

    def __init__(self, value):
        self._value = value
        self._prev = None

    def __enter__(self):
        global _FORCE_INTERPRET
        self._prev = _FORCE_INTERPRET
        _FORCE_INTERPRET = self._value
        return self._value

    def __exit__(self, *exc):
        global _FORCE_INTERPRET
        _FORCE_INTERPRET = self._prev
        return False


_UNSET = object()


def interpret_mode(value=_UNSET):
    """Dual-purpose.

    ``interpret_mode()`` (no args) — predicate: Pallas kernels run
    interpreted if and only if the computation's devices are CPU. Those
    are the devices of ``jax_default_device`` where the caller pinned
    one, and of the default backend otherwise: on a TPU host the kernels
    compile, under ``JAX_PLATFORMS=cpu`` (the tests) they interpret.

    ``with interpret_mode(True):`` — scoped override of the predicate
    that saves and restores the previous override, replacing bare
    ``set_interpret(True)`` / ``set_interpret(False)`` pairs (the PR-10
    leak class: teardown that hard-codes ``False`` clobbers any outer
    override and poisons later tests in the same process).
    ``with interpret_mode(False):`` is also how a test compiles a kernel
    for a described (not attached) TPU while the backend is CPU."""
    if value is not _UNSET:
        return _InterpretOverride(value)
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    pinned = jax.config.jax_default_device
    platform = (getattr(pinned, "platform", pinned) if pinned is not None
                else jax.default_backend())
    return platform == "cpu"
