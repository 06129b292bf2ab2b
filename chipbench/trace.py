"""From the profiler's ``.xplane.pb`` to numbers.

``load`` turns the file into plain tuples, so that everything after it can be
tested on hand-built input. A device plane is one whose name starts with
``/device:TPU:`` (``/device:`` in general, the host excluded); on it the line
``XLA Ops`` holds one event per executed HLO operation (fusions, custom
calls = Pallas kernels, collectives) and ``XLA Modules`` one per executed
program (``jit_step(...)``). Any other plane is the host's: its events are
spans (``TraceAnnotation``s), and each keeps the arguments it was opened
with as its ``stats``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import types
from typing import Any, Iterable, List, Mapping, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_STATS: Mapping[str, Any] = types.MappingProxyType({})


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float        # seconds
    dur: float          # seconds
    # a host span's arguments (``serve.decode``: ``rows``, ``bucket``); a
    # device event's are not kept, there are a dozen to each of 100,000
    stats: Mapping[str, Any] = dataclasses.field(default=NO_STATS,
                                                 compare=False)

    @property
    def end(self) -> float:
        return self.start + self.dur


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        host = not plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                                 dict(ev.stats) if host else NO_STATS))
    return out


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith("/device:")
                   and "host" not in e.plane.lower()
                   and e.line == OPS_LINE})


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def ops(events, plane=None):
    return [e for e in events if e.line == OPS_LINE
            and (plane is None or e.plane == plane)]


def busy_seconds(events) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(union_seconds([(e.start, e.end) for e in ops(events, p)])
               for p in planes) / len(planes)


def matching(events, match: str, line=OPS_LINE, plane=None):
    planes = {plane} if plane else set(device_planes(events))
    return [e for e in events if e.line == line and e.plane in planes
            and re.search(match, e.name)]


def inside(events, spans):
    """The events that lie within one of ``spans`` on the same plane."""
    import bisect
    by_plane = {}
    for sp in spans:
        by_plane.setdefault(sp.plane, []).append((sp.start, sp.end))
    out = []
    for iv in by_plane.values():
        iv.sort()
    for e in events:
        iv = by_plane.get(e.plane, [])
        i = bisect.bisect_right(iv, (e.start, float("inf"))) - 1
        if i >= 0 and iv[i][0] <= e.start and e.end <= iv[i][1] + 1e-9:
            out.append(e)
    return out


CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* = ")
LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name: str, width: int = 160) -> str:
    """An HLO operation's event name, which is its whole text, cut to its
    name, the shapes it writes and its kind."""
    if " = " not in name:
        return name[:width]
    head, rest = name.split(" = ", 1)
    rest = LAYOUT.sub("", LAYOUT.sub("", rest))
    m = re.match(r"(\(.*?\)|\S+) ([\w-]+)\(", rest)
    text = f"{head} {m.group(2)} -> {m.group(1)}" if m else f"{head} {rest}"
    return text[:width]


def top_ops(events, n=10):
    """[[name, seconds]] of the operations that took most device time,
    summed over calls and averaged over devices. Loops and calls, which
    only hold other operations, are left out."""
    planes = device_planes(events)
    sums = {}
    for e in ops(events):
        if e.plane in planes and not CONTAINER.match(e.name):
            key = short_name(e.name)
            sums[key] = sums.get(key, 0.0) + e.dur
    k = max(1, len(planes))
    return [[name, s / k] for name, s in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, plane=None):
    """[(start, end)] in which nothing ran on the (first) device."""
    planes = device_planes(events)
    if not planes:
        return []
    iv = sorted((e.start, e.end) for e in ops(events, plane or planes[0]))
    gaps, cur = [], iv[0][1] if iv else 0.0
    for s, e in iv[1:]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    return gaps


def summary(events, limit=40) -> str:
    """What a trace holds, for reading one by hand."""
    lines = {}
    for e in events:
        key = (e.plane, e.line)
        d = lines.setdefault(key, {})
        c = d.setdefault(e.name, [0, 0.0])
        c[0] += 1
        c[1] += e.dur
    out = []
    for (plane, line), names in sorted(lines.items()):
        out.append(f"== {plane} | {line}: {len(names)} names, "
                   f"{sum(c[0] for c in names.values())} events")
        for name, (n, s) in sorted(names.items(),
                                   key=lambda kv: -kv[1][1])[:limit]:
            out.append(f"   {s * 1e3:12.3f} ms {n:7d} x  {name[:150]}")
    return "\n".join(out)
