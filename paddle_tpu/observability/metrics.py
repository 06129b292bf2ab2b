"""Step-level telemetry: wall step time, compile time, tokens/sec, MFU.

Design constraint: NOTHING here may synchronize the device. Step wall time is
the host-side interval between consecutive ``step()`` calls (in steady state
with donated buffers the dispatch of step N+1 cannot run ahead of step N's
completion, so the interval converges to true device step time without any
``block_until_ready``); memory stats come from the PJRT host-side
``device.memory_stats()`` query; FLOPs are captured once per compile from the
program's cost analysis, not per step. MFU is FLOPs-per-step over
(step_time x peak FLOPs of the slice), the paper's target metric.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional

import jax

from .. import envs
from . import trace as _trace
from .histogram import LogHistogram

ENV_PEAK_FLOPS = "PADDLE_TPU_PEAK_FLOPS"

# Per-chip peak FLOP/s by PJRT device_kind substring (bf16 with int8-free
# MXU peaks, the denominators MFU papers use). Matched case-insensitively,
# FIRST match wins. A device with no row has no rate: there is no CPU row
# (a CPU run reports no MFU) and no bare "v5" row (it would claim every
# later "v5..." kind); PADDLE_TPU_PEAK_FLOPS states a rate by hand.
PEAK_FLOPS_TABLE = (
    ("v6e", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


# device_kinds already warned about this process: an unknown platform must
# not fall back SILENTLY (roofline/MFU fractions would be quietly wrong or
# quietly absent), but it must also not spam one warning per StepMetrics
_PEAK_WARNED: set = set()


def peak_flops_info(device=None):
    """(per-device peak FLOP/s, source) — source is ``"env"`` (the
    PADDLE_TPU_PEAK_FLOPS override), ``"table:<key>"`` (the device_kind
    row that matched), or ``"unknown:<kind>"`` with a once-per-run warning
    NAMING the platform so an MFU/roofline gap is never a silent None."""
    env = envs.get(ENV_PEAK_FLOPS)
    if env is not None:
        return env, "env"
    if device is None:
        devs = jax.devices()
        if not devs:
            return None, "unknown:no-devices"
        device = devs[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key, flops in PEAK_FLOPS_TABLE:
        if key in kind:
            return flops, f"table:{key}"
    if kind != "cpu" and kind not in _PEAK_WARNED:
        # a CPU has no rate by design (source "unknown:cpu", MFU None);
        # an accelerator the table does not know is worth a warning
        _PEAK_WARNED.add(kind)
        import warnings
        warnings.warn(
            f"no peak-FLOPs table entry for device_kind {kind!r}: MFU and "
            f"roofline fractions will be unavailable for this platform — "
            f"set PADDLE_TPU_PEAK_FLOPS or extend "
            f"observability.metrics.PEAK_FLOPS_TABLE", stacklevel=2)
    return None, f"unknown:{kind or '?'}"


def require_peak_flops(device=None) -> float:
    """Peak FLOP/s for a MEASUREMENT (a reported MFU or roofline share):
    a device without a rate raises, where the telemetry path
    (:func:`peak_flops_info`) carries ``None`` and its source."""
    flops, source = peak_flops_info(device)
    if flops is None:
        raise RuntimeError(
            f"no peak FLOP/s for this device ({source}): add its "
            f"device_kind to observability.metrics.PEAK_FLOPS_TABLE or set "
            f"{ENV_PEAK_FLOPS}; a rate is never assumed")
    return flops


def peak_flops_per_device(device=None) -> Optional[float]:
    """Peak FLOP/s for one device, from ``PADDLE_TPU_PEAK_FLOPS`` (wins) or
    the device_kind table; None (with a once-per-run warning via
    :func:`peak_flops_info`) when the kind is unknown."""
    return peak_flops_info(device)[0]


class StepMetrics:
    """Per-step telemetry collector (the xplane-pipeline-shaped summary view).

    Typical wiring (``jit.TrainStep`` does this when telemetry is on)::

        m = StepMetrics(n_devices=mesh.size)
        m.attach(JsonlWriter(path))
        m.record_compile(compile_s=..., trace_s=..., flops=...)   # per compile
        m.step(tokens=B * S)                                      # per step

    ``step()`` builds one record dict, appends it to a bounded window, and
    hands it to every attached exporter. ``summary()`` aggregates the window
    and folds in the trace-time comm counters (hop counts, bucket bytes,
    overlap flags).
    """

    def __init__(self, name: str = "train", tokens_per_step: Optional[int] = None,
                 n_devices: Optional[int] = None,
                 peak_flops: Optional[float] = None, window: int = 512):
        self.name = name
        self.tokens_per_step = tokens_per_step
        self.n_devices = n_devices if n_devices is not None else jax.device_count()
        if peak_flops is not None:
            per_dev, self.mfu_peak_source = peak_flops, "arg"
        else:
            per_dev, self.mfu_peak_source = peak_flops_info()
        self.peak_flops_total = (per_dev * self.n_devices
                                 if per_dev is not None else None)
        self.flops_per_step: Optional[float] = None
        self.compile_time_s = 0.0
        self.trace_time_s = 0.0
        self.compiles = 0
        self.recompiles = 0  # compiles beyond the first
        self.steps = 0
        self.records: collections.deque = collections.deque(maxlen=window)
        # full-run step-time distribution at fixed memory (the bounded
        # records window only covers the last `window` steps); seconds,
        # 10 µs .. 10 ks span
        self.step_time_hist = LogHistogram(lo=1e-5, hi=1e4)
        self._last_t: Optional[float] = None
        self._exporters: List = []
        self._mem_fams = None  # (in_use, peak) gauge families, per device

    def register_into(self, registry) -> None:
        """Expose this collector through a :class:`MetricsRegistry`: the
        full-run step-time histogram (by reference), compile accounting
        gauges, and per-device memory gauge families keyed ``device=``
        (refreshed on every :meth:`device_memory` poll, i.e. each step)."""
        registry.summary("step_time_seconds", hist=self.step_time_hist,
                         help="training step wall time (steady-state "
                              "dispatch interval)")
        registry.gauge("steps", fn=lambda: self.steps,
                       help="steps recorded this run")
        registry.gauge("compiles", fn=lambda: self.compiles,
                       help="program (re)compilations observed")
        registry.gauge("recompiles", fn=lambda: self.recompiles,
                       help="compilations beyond the first")
        registry.gauge("compile_time_seconds",
                       fn=lambda: self.compile_time_s,
                       help="cumulative wall time spent compiling")
        self._mem_fams = (
            registry.family("device_mem_bytes_in_use", "gauge",
                            labelnames=("device",),
                            help="live HBM bytes per local device"),
            registry.family("device_mem_peak_bytes_in_use", "gauge",
                            labelnames=("device",),
                            help="peak HBM bytes per local device"))

    # -- wiring -------------------------------------------------------------

    def attach(self, exporter) -> "StepMetrics":
        """Attach an exporter with a ``write(record: dict)`` method."""
        self._exporters.append(exporter)
        return self

    def close(self) -> None:
        for e in self._exporters:
            try:
                e.close()
            except Exception:
                pass

    # -- recording ----------------------------------------------------------

    def record_compile(self, compile_s: float = 0.0, trace_s: float = 0.0,
                       flops: Optional[float] = None) -> None:
        """One (re)compilation: wall compile/trace seconds and, when known,
        the program's cost-analysis FLOPs per executed step."""
        self.compiles += 1
        if self.compiles > 1:
            self.recompiles += 1
        self.compile_time_s += float(compile_s)
        self.trace_time_s += float(trace_s)
        if flops:
            self.flops_per_step = float(flops)
        # a compile step's wall time is compile, not execution: restart the
        # steady-state interval clock
        self._last_t = None

    def device_memory(self) -> Dict:
        """Host-side PJRT memory stats over ALL ``jax.local_devices()``
        (no sync; {} on backends like CPU that report none). The scalar
        roll-ups keep the pre-PR-15 record keys — ``mem_bytes_in_use``
        is now the SUM across local devices and ``mem_peak_bytes_in_use``
        the max — while ``mem_per_device`` carries each device's stats
        (the devices[0]-only sampling hid every non-0 device's headroom).
        When registered into a MetricsRegistry the per-device values also
        refresh the ``device=``-labeled gauge families."""
        per_dev = []
        try:
            devices = jax.local_devices()
        except Exception:
            devices = []
        for i, dev in enumerate(devices):
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            per_dev.append({"device": i,
                            "bytes_in_use": stats.get("bytes_in_use"),
                            "peak_bytes_in_use":
                                stats.get("peak_bytes_in_use")})
        if not per_dev:
            return {}
        if self._mem_fams is not None:
            fam_use, fam_peak = self._mem_fams
            for e in per_dev:
                if e["bytes_in_use"] is not None:
                    fam_use.labels(device=str(e["device"])).set(
                        e["bytes_in_use"])
                if e["peak_bytes_in_use"] is not None:
                    fam_peak.labels(device=str(e["device"])).set(
                        e["peak_bytes_in_use"])
        in_use = [e["bytes_in_use"] for e in per_dev
                  if e["bytes_in_use"] is not None]
        peaks = [e["peak_bytes_in_use"] for e in per_dev
                 if e["peak_bytes_in_use"] is not None]
        return {"mem_bytes_in_use": sum(in_use) if in_use else None,
                "mem_peak_bytes_in_use": max(peaks) if peaks else None,
                "mem_per_device": per_dev}

    def mfu(self, step_time_s: Optional[float]) -> Optional[float]:
        if (not step_time_s or step_time_s <= 0 or not self.flops_per_step
                or not self.peak_flops_total):
            return None
        return self.flops_per_step / (step_time_s * self.peak_flops_total)

    def step(self, step_time_s: Optional[float] = None,
             tokens: Optional[int] = None, **extra) -> Dict:
        """Record one training step. With no explicit ``step_time_s`` the
        steady-state interval since the previous ``step()`` call is used
        (None on the first step after a (re)compile — no fake numbers)."""
        now = time.perf_counter()
        if step_time_s is None and self._last_t is not None:
            step_time_s = now - self._last_t
        self._last_t = now
        self.steps += 1
        if step_time_s is not None:
            self.step_time_hist.record(step_time_s)
        tokens = tokens if tokens is not None else self.tokens_per_step
        rec: Dict = {
            "name": self.name,
            "step": self.steps,
            "step_time_ms": (step_time_s * 1e3
                             if step_time_s is not None else None),
            "tokens": tokens,
            "tokens_per_sec": (tokens / step_time_s
                               if tokens and step_time_s else None),
            "mfu": self.mfu(step_time_s),
            # provenance of the MFU denominator, so a reader of the JSONL
            # can tell a table-backed fraction from an env override or an
            # unknown-platform None at a glance
            "mfu_peak_source": self.mfu_peak_source,
        }
        rec.update(self.device_memory())
        rec.update(extra)
        self.records.append(rec)
        for e in self._exporters:
            e.write(rec)
        return rec

    # -- aggregation --------------------------------------------------------

    def summary(self) -> Dict:
        """Aggregate view: timing stats over the window, compile accounting,
        MFU at the best step time, and the trace-time comm counters."""
        times = [r["step_time_ms"] for r in self.records
                 if r.get("step_time_ms")]
        best = min(times) if times else None
        mean = sum(times) / len(times) if times else None
        toks = [r["tokens_per_sec"] for r in self.records
                if r.get("tokens_per_sec")]
        out: Dict = {
            "name": self.name,
            "steps": self.steps,
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            "compile_time_s": self.compile_time_s,
            "trace_time_s": self.trace_time_s,
            "flops_per_step": self.flops_per_step,
            "peak_flops_total": self.peak_flops_total,
            "mfu_peak_source": self.mfu_peak_source,
            "n_devices": self.n_devices,
            "step_time_ms_best": best,
            "step_time_ms_mean": mean,
            "tokens_per_sec_best": max(toks) if toks else None,
            "mfu_best": self.mfu(best / 1e3) if best else None,
        }
        # streaming (full-run, fixed-memory) step-time distribution —
        # the window stats above forget everything past `window` steps
        if self.step_time_hist.count:
            for q in (50, 90, 99):
                p = self.step_time_hist.percentile(q)
                out[f"step_time_ms_p{q}"] = p * 1e3 if p is not None else None
        out.update(self.device_memory())
        try:
            out["overlap"] = _trace.overlap_flags()
        except Exception:
            pass
        out["counters"] = _trace.counters()
        return out

    def summary_lines(self) -> List[str]:
        """Human-readable summary (the Profiler.summary telemetry section)."""
        s = self.summary()
        lines = [f"StepMetrics[{self.name}]: {s['steps']} steps, "
                 f"{s['compiles']} compiles ({s['recompiles']} re), "
                 f"compile {s['compile_time_s']:.2f}s"]
        if s["step_time_ms_best"] is not None:
            lines.append(
                f"  step time best {s['step_time_ms_best']:.2f} ms / "
                f"mean {s['step_time_ms_mean']:.2f} ms")
        if s["tokens_per_sec_best"]:
            lines.append(f"  tokens/sec best {s['tokens_per_sec_best']:.0f}")
        if s["mfu_best"] is not None:
            lines.append(f"  MFU best {s['mfu_best'] * 100:.2f}% "
                         f"({s['flops_per_step']:.3g} FLOPs/step over "
                         f"{s['peak_flops_total']:.3g} peak FLOP/s)")
        cnt = s.get("counters") or {}
        for key in sorted(cnt):
            lines.append(f"  {key}: {cnt[key]:.0f}")
        for key, val in (s.get("overlap") or {}).items():
            lines.append(f"  {key}: {val}")
        return lines


_active: Optional[StepMetrics] = None


def set_active(metrics: Optional[StepMetrics]) -> None:
    """Install the process-wide collector ``Profiler.summary()`` reports."""
    global _active
    _active = metrics


def active() -> Optional[StepMetrics]:
    return _active
