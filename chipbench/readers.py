"""Readers of per-layer metrics, by name. A metric's file
(``metrics/<name>.json``) names its reader and the reader's arguments. A
reader gets the run's ``Facts`` and returns a number, or ``None`` where it
finds nothing to read: the harness then leaves the metric out. A later PR
that needs a new reader adds ``readers_<x>.py`` beside this file; every such
module is imported and registers itself."""
from __future__ import annotations

import dataclasses
import glob
import importlib
import os
import statistics
import sys
from typing import Any, Callable, Dict, List, Optional

from chipbench import stats, trace

READERS: Dict[str, Callable] = {}


def reader(name: str):
    def deco(fn):
        READERS[name] = fn
        return fn
    return deco


@dataclasses.dataclass
class Facts:
    """What one traced run knows."""
    model: dict                    # the configuration as run
    traffic: dict
    chips: int
    peak: dict
    events: List[trace.Event]      # the device trace, reduced to tuples
    traced_s: float                # host seconds of the traced window
    # the harness's own counts, the family's counters for its kernels and
    # the change of the program's own registry over the traced part
    counters: Dict[str, Any]


def load_extensions() -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    for path in sorted(glob.glob(os.path.join(here, "readers_*.py"))):
        importlib.import_module(
            "chipbench." + os.path.basename(path)[:-3])


def read(metric_file: dict, facts: Facts) -> Optional[float]:
    fn = READERS[metric_file["reader"]]
    return fn(facts, **metric_file.get("args", {}))


# -- device trace --------------------------------------------------------------

@reader("device_idle_pct")
def device_idle_pct(f: Facts):
    if not f.events or f.traced_s <= 0:
        return None
    busy = trace.busy_seconds(f.events)
    return 100.0 * (1.0 - busy / f.traced_s) if busy > 0 else None


@reader("module_dev_ms")
def module_dev_ms(f: Facts, match: str):
    """Median device time of the executions of one compiled program."""
    d = [e.dur for e in trace.matching(f.events, match,
                                       line=trace.MODULES_LINE)]
    return 1e3 * statistics.median(d) if d else None


@reader("kernel_roofline_pct")
def kernel_roofline_pct(f: Facts, match: str, counter: str, module=None):
    """The least time the chip could take for the calls seen, over the time
    they took. ``counter`` names the harness's count for ONE device: either
    ``per_call_least_s`` (every call alike) or ``least_s`` (the sum over
    the calls of the traced window). ``module`` keeps only the calls made
    inside executions of the compiled program of that name."""
    evs = trace.matching(f.events, match)
    if module is not None:
        runs = trace.matching(f.events, module, line=trace.MODULES_LINE)
        evs = trace.inside(evs, runs)
        print(f"chipbench: {counter}: {len(evs)} kernel calls in "
              f"{len(runs)} executions of {module}", file=sys.stderr)
    work = f.counters.get(counter)
    if not evs or not work:
        return None
    devices = max(1, len(trace.device_planes(f.events)))
    seconds = sum(e.dur for e in evs) / devices
    if "per_call_least_s" in work:
        least = work["per_call_least_s"] * len(evs) / devices
    else:
        least = work["least_s"]
    return 100.0 * least / seconds if seconds > 0 else None


@reader("mfu_pct")
def mfu_pct(f: Facts, counter: str):
    """Required FLOPs of the traced window over window x chips x peak."""
    need = f.counters.get(counter)
    if not need or f.traced_s <= 0:
        return None
    return 100.0 * need / (f.traced_s * f.chips * f.peak["flops_per_s"])


# -- the harness's own spans and counts ------------------------------------------

@reader("counter_percentile")
def counter_percentile(f: Facts, counter: str, q: float, scale: float = 1.0):
    values = f.counters.get(counter)
    if not values:
        return None
    return stats.percentile(values, q) * scale


@reader("counter_mean")
def counter_mean(f: Facts, counter: str, scale: float = 1.0):
    values = f.counters.get(counter)
    if not values:
        return None
    return statistics.fmean(values) * scale
