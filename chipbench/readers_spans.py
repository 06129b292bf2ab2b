"""Readers of the program's own host spans: the ``serve.*``
``TraceAnnotation``s that ``InferenceEngine`` emits at its phase boundaries
land in the profiler's trace beside the device's "XLA Ops" line, on one
clock. A program without them (any commit before they were added) gives
every reader here nothing to read, and the metric is left out."""
from __future__ import annotations

import bisect
import re
from typing import List, Optional, Sequence, Tuple

from chipbench import stats, trace
from chipbench.readers import Facts, reader

Interval = Tuple[float, float]


def host_spans(events, match: str) -> List[trace.Event]:
    """The host events (any plane that is no device's) whose name matches."""
    pat = re.compile(match)
    return [e for e in events if not e.plane.startswith("/device:")
            and pat.search(e.name)]


def merged(intervals: Sequence[Interval]) -> List[Interval]:
    """The union of ``intervals`` as disjoint, sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_seconds(gaps: Sequence[Interval],
                    cover: Sequence[Interval]) -> float:
    """Seconds of ``gaps`` that lie under ``cover`` (disjoint, sorted)."""
    starts = [s for s, _ in cover]
    total = 0.0
    for g0, g1 in gaps:
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(cover) and cover[i][0] < g1:
            total += max(0.0, min(g1, cover[i][1]) - max(g0, cover[i][0]))
            i += 1
    return total


@reader("span_ms")
def span_ms(f: Facts, match: str, q: float,
            minus: Optional[str] = None):
    """Percentile ``q`` of the durations of the host spans whose name
    matches, in ms. With ``minus``: each span less the spans matching
    ``minus`` that lie inside it on its own thread (its self time, if they
    are its children). A trace that holds no span matching ``minus`` at all
    comes from a program without them (the commit before the phase spans
    emitted a ``serve.prefill`` around launch and wait together): nothing
    to read."""
    spans = host_spans(f.events, match)
    if not spans:
        return None
    durs = [sp.dur for sp in spans]
    if minus is not None:
        inner = {}
        for c in host_spans(f.events, minus):
            inner.setdefault((c.plane, c.line), []).append(c)
        if not inner:
            return None
        durs = [sp.dur - sum(c.dur for c in inner.get((sp.plane, sp.line), ())
                             if sp.start <= c.start and c.end <= sp.end)
                for sp in spans]
    return 1e3 * stats.percentile(durs, q)


@reader("idle_under_pct")
def idle_under_pct(f: Facts, match: Optional[str] = None,
                   outside: Optional[str] = None):
    """100 x the seconds in which nothing ran on the device
    (``trace.idle_gaps``) and some host span matching ``match`` was open
    (or, with ``outside``, no span matching it was), over the traced
    seconds. Spans that overlap each other count once."""
    if not trace.device_planes(f.events) or f.traced_s <= 0:
        return None
    spans = host_spans(f.events, match if match is not None else outside)
    if not spans:
        return None
    gaps = trace.idle_gaps(f.events)
    under = overlap_seconds(gaps, merged([(e.start, e.end) for e in spans]))
    if match is None:
        under = sum(g1 - g0 for g0, g1 in gaps) - under
    return 100.0 * under / f.traced_s
