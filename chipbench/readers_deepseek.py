"""Readers for the per-layer metrics the DeepSeek family brought: shares of
the device's busy time by operation, ratios of the program's own registry
counters, and the routed experts' roofline, whose work is counted from
those counters by the configuration's family. A program without the
counters (any commit before them) or a trace without the operations gives
every reader here nothing to read, and the metric is left out."""
from __future__ import annotations

from chipbench import flops, spec, trace
from chipbench.readers import Facts, reader


@reader("ops_share_pct")
def ops_share_pct(f: Facts, match: str, module=None):
    """100 x the device time of the operations whose text matches (inside
    executions of the compiled programs matching ``module``, if given) over
    the device's busy time."""
    busy = trace.busy_seconds(f.events) if f.events else 0.0
    evs = trace.matching(f.events, match)
    if module is not None:
        evs = trace.inside(evs, trace.matching(f.events, module,
                                               line=trace.MODULES_LINE))
    if not evs or busy <= 0:
        return None
    devices = max(1, len(trace.device_planes(f.events)))
    return 100.0 * sum(e.dur for e in evs) / devices / busy


@reader("counter_ratio")
def counter_ratio(f: Facts, num: str, den: str, scale: float = 1.0):
    """One counter's change over the traced part over another's."""
    n, d = f.counters.get(num), f.counters.get(den)
    if n is None or not d:
        return None
    return scale * n / d


@reader("moe_experts_roofline_pct")
def moe_experts_roofline_pct(f: Facts, match: str, pairs: str, hits: str):
    """The least time the chip could take for the routed experts' grouped
    matmuls of the traced part, over the time their calls took: the work is
    the family's count (``moe_experts_call``) from the registry's change:
    ``pairs`` (token, held expert) pairs and ``hits`` experts whose weights
    a call had to read. The larger of the two sums' bounds: a bound on the
    sum."""
    evs = trace.matching(f.events, match)
    n_pairs, n_hits = f.counters.get(pairs), f.counters.get(hits)
    fam = spec.family(f.model)
    if not evs or not n_pairs or not n_hits \
            or not hasattr(fam, "moe_experts_call"):
        return None
    devices = max(1, len(trace.device_planes(f.events)))
    seconds = sum(e.dur for e in evs) / devices
    least = flops.min_seconds(*fam.moe_experts_call(f.model, n_pairs, n_hits),
                              f.peak)
    return 100.0 * least / seconds if seconds > 0 else None
