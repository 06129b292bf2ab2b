"""What every kind of cell shares: the clock, the device, tracing, the
result line."""
from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time
from typing import Dict, Optional

from chipbench import peaks, readers, spec, trace
from chipbench.check import Compared

OUT_DIR = os.path.join(spec.ROOT, ".chipbench_out")


def now() -> float:
    return time.perf_counter()


def say(*parts) -> None:
    print("chipbench:", *parts, file=sys.stderr, flush=True)


MARKS = []      # (what has just ended, when): the phases of set-up


def mark(what: str) -> None:
    MARKS.append((what, now()))


def phases(clock_start: float) -> str:
    """Set-up by its phases, for standard error: which one a slow set-up
    lost its time in."""
    out, last = [], clock_start
    for what, t in MARKS:
        out.append(f"{what} {t - last:.2f} s")
        last = t
    return ", ".join(out)


def annotate(name: str):
    """A host span in the profiler's own trace (free when none is taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_facts(rehearse: bool, chips: int) -> dict:
    """What JAX reports; exits non-zero unless it is the chips the cell
    asks for (a rehearsal takes the CPU and says so)."""
    import jax
    devs = jax.devices()
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs)}
    if rehearse:
        if d["count"] < chips:
            raise SystemExit(f"chipbench: rehearsal of a {chips}-chip cell "
                             f"needs {chips} virtual devices, found {d}")
        return d
    if d["platform"] != "tpu" or d["count"] != chips:
        say(f"needs {chips} TPU chip(s); JAX found {d} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). No result.")
        raise SystemExit(3)
    return d


KINDS = {"train": "chipbench.train", "serve_open": "chipbench.serve"}


def open_cell(workload: str, rehearse: bool):
    """What every entry point does first: the cell's files (at the
    rehearsal's tiny size if asked), the device or no result, the peaks, the
    program's compile cache. Returns (cell, device, peak, runner module)."""
    cell = spec.load_cell(workload)
    if rehearse:
        cell.model = spec.rehearsal_model(cell.model)
        cell.traffic = dict(cell.traffic, **cell.traffic.get("rehearse", {}))
    device = device_facts(rehearse, cell.chips)
    peak = (peaks.PEAKS["TPU v5 lite"] if rehearse
            else peaks.peak(device["kind"]))
    import paddle_tpu  # noqa: F401  (alone in a directory this fails: no result)
    cache = enable_compile_cache()
    say(f"{cell.name}: {device}, compile cache {cache}")
    mark("imports and the device")
    return cell, device, peak, importlib.import_module(
        KINDS[cell.traffic["kind"]])


def readings_file(kind: str, cell_name: str, rehearse: bool) -> str:
    """Where a hand-run tool keeps its lines: ``chiprun_out/``, which comes
    back from the chip; a rehearsal's lines are kept nowhere."""
    if rehearse:
        return os.devnull
    out_dir = os.path.join(spec.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(out_dir, f"{kind}.{cell_name}.jsonl")


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's fixed place: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` inside the
    checkout. Every program is kept, however quick its compile."""
    import jax
    from paddle_tpu.utils.compile_cache import enable_compile_cache as on
    path = on()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Tracer:
    """One traced window inside a run."""

    def __init__(self, cell_name: str):
        self.dir = os.path.join(OUT_DIR, "trace", cell_name)
        self.t0 = self.t1 = None
        # on the driver's clock (serving): when the trace was asked for,
        # when it ran from and to
        self.asked = self.c0 = self.c1 = None
        # the program's own registry as the traced part began and ended
        self.registry0 = self.registry1 = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # TraceAnnotations stay; no
        opts.host_tracer_level = 2        # per-call Python events
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = now()

    def stop(self) -> None:
        import jax
        self.t1 = now()
        jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def events(self):
        evs = trace.load(trace.find_xplane(self.dir))
        if os.environ.get("CHIPBENCH_KEEP_TRACE"):
            with open(os.path.join(self.dir, "summary.txt"), "w") as f:
                f.write(trace.summary(evs))
        else:
            shutil.rmtree(self.dir, ignore_errors=True)
        return evs


def host_spans(events, prefix="chipbench."):
    return [e for e in events if e.name.startswith(prefix)
            and not e.plane.startswith("/device:")]


def breakdown(events) -> dict:
    """The device operations that took most time, and the longest idle gaps
    named by the harness span that covers most of each."""
    spans = host_spans(events)
    gaps = sorted(trace.idle_gaps(events), key=lambda g: g[0] - g[1])[:200]
    named: Dict[str, float] = {}
    for s, e in gaps:
        best, cover = "no harness span", 0.0
        for sp in spans:
            c = min(e, sp.end) - max(s, sp.start)
            if c > cover:
                best, cover = sp.name, c
        named[best] = named.get(best, 0.0) + (e - s)
    idle = [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])]
    return {"device_ops": trace.top_ops(events, 10), "idle_gaps": idle[:10]}


def per_layer_metrics(cell, facts: readers.Facts) -> dict:
    readers.load_extensions()
    out = {}
    for m in cell.per_layer:
        value = readers.read(m["file"], facts)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced(cell, args, tracer: Tracer, counters: dict):
    """What a traced run adds: (its per-layer metrics, ``busy_s`` and
    ``window_s`` for the device, the breakdown)."""
    events = tracer.events()
    facts = readers.Facts(cell.model, cell.traffic, cell.chips, args.peak,
                          events, tracer.seconds, counters)
    return (per_layer_metrics(cell, facts),
            {"busy_s": trace.busy_seconds(events),
             "window_s": tracer.seconds},
            breakdown(events))


def result_line(*, compared: Compared, attempted: int, failed: int,
                metrics: dict, device: dict, rehearse: bool,
                breakdown_: Optional[dict] = None) -> str:
    line = {"correct": bool(compared.correct) and not rehearse,
            "attempted": attempted, "failed": failed}
    if rehearse:
        # a CPU number is never written under a device metric's name
        line["metrics"] = {}
        line["rehearsal_values"] = metrics
    else:
        line["metrics"] = metrics
    line["device"] = device
    if breakdown_ is not None:
        line["breakdown"] = breakdown_
    line["compared"] = compared.as_dict()
    return json.dumps(line)
