"""Trace attribution primitives for the hybrid-parallel hot path.

Three mechanisms:

- ``span(name, **args)`` — a host span on the profiler's own clock, for
  code that runs on the host EVERY step (the serving engine's scheduler
  phases). It is a ``jax.profiler.TraceAnnotation`` and nothing else: it
  lands in the profiler's ``.xplane.pb`` beside the device's "XLA Ops"
  line, with ``args`` as event stats, while a profiler session runs, and
  costs a fraction of a microsecond while none does. The session is the
  switch; there is no other.

- ``comm_span(name)`` — for collective sites INSIDE traced programs only:
  a context manager entered while the site is being TRACED into a jitted
  program. It pushes a ``jax.named_scope`` (the name lands in the HLO op
  metadata, so XLA's xplane profile attributes the device time of that
  ppermute/psum to the span name in TensorBoard/Perfetto) plus a host
  ``jax.profiler.TraceAnnotation`` so tracing itself shows up in host
  timelines. No code runs per executed step; on a per-step host path its
  locked counters and named scope are the wrong tool — use ``span``.

- counters — a process-global tally ``comm_span`` (and planners) bump at trace
  time: ppermute hop counts, grad-sync bucket bytes, overlap on/off. Because
  instrumented code runs when a program is traced, counters are STATIC
  attribution of the compiled step (like HLO op counts), not execution
  counts: a kernel retraced for fwd+bwd or under remat tallies each trace.
  ``reset_counters()`` before building a step and ``counters()`` after gives
  the per-program attribution the StepMetrics collector surfaces.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

import jax

from .. import envs

ENV_TELEMETRY = "PADDLE_TPU_TELEMETRY"
ENV_TELEMETRY_DIR = "PADDLE_TPU_TELEMETRY_DIR"

_TRUTHY = ("1", "true", "on", "yes")


def telemetry_enabled(explicit: Optional[bool] = None) -> bool:
    """Telemetry switch: an explicit argument wins, else ``PADDLE_TPU_TELEMETRY``."""
    if explicit is not None:
        return bool(explicit)
    return envs.get(ENV_TELEMETRY)


def telemetry_dir() -> Optional[str]:
    """Step-log directory from ``PADDLE_TPU_TELEMETRY_DIR`` (None: no file)."""
    return envs.get(ENV_TELEMETRY_DIR)


_counters: Dict[str, float] = {}
_lock = threading.Lock()


def record_counter(name: str, value: float = 1.0) -> None:
    """Add ``value`` to counter ``name`` (creates at 0)."""
    with _lock:
        _counters[name] = _counters.get(name, 0.0) + float(value)


def set_counter(name: str, value: float) -> None:
    with _lock:
        _counters[name] = float(value)


def counters() -> Dict[str, float]:
    """Snapshot of every counter."""
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    with _lock:
        _counters.clear()


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span in the profiler's trace: ``with span("serve.step",
    iteration=3) as sp: ...``. ``args`` become the event's stats;
    ``sp.set_metadata(rows=3)`` adds those known only later. No named
    scope, no counter, no lock: free to leave on a per-step path."""
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def comm_span(name: str, nbytes: Optional[int] = None,
              site: Optional[str] = None):
    """Attribute a collective site: named HLO scope + host trace annotation +
    ``{name}.calls`` / ``{name}.bytes`` counters. For code being traced into
    a jit/shard_map/scan (where it tallies once per trace); host code that
    runs every step takes ``span``.

    ``site=`` is the STABLE straggler-attribution key (PR 15): unlike
    ``name`` — often per-instance, e.g. ``grad_sync.bucket07`` — the site
    label is a static string shared by every instance of one collective
    family, tallied as ``site.<site>.{calls,bytes,ms}`` counters so the
    FleetMonitor can compare the same site across ranks. The ``.ms``
    tally is host time inside the span (trace time under jit)."""
    record_counter(name + ".calls", 1)
    if nbytes is not None:
        record_counter(name + ".bytes", int(nbytes))
    if site is not None:
        record_counter(f"site.{site}.calls", 1)
        if nbytes is not None:
            record_counter(f"site.{site}.bytes", int(nbytes))
    ann = None
    try:
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
    except Exception:
        ann = None
    t0 = time.perf_counter()
    try:
        with jax.named_scope(name):
            yield
    finally:
        if site is not None:
            record_counter(f"site.{site}.ms",
                           (time.perf_counter() - t0) * 1e3)
        if ann is not None:
            ann.__exit__(None, None, None)


def overlap_flags() -> Dict[str, int]:
    """The PR-1 overlap switches as 0/1 counters (tp ring, pp async-p2p,
    grad-sync mode is per-TrainStep and recorded there)."""
    from ..parallel import collective_matmul as _cm
    from ..parallel import pipeline as _pl
    return {
        "tp.overlap_on": int(_cm.overlap_enabled()),
        "pp.overlap_on": int(_pl.p2p_overlap_enabled()),
    }
