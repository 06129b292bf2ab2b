"""Readers of the program's own per-request counters: the request-seconds
``InferenceEngine`` keeps in its registry for the time before each first
token, by where a request is (queued, its prompt waiting, a chunk of its
prompt launched and unread). A program without the counters (any commit
before them) gives every reader here nothing to read, and the metric is left
out."""
from __future__ import annotations

from typing import Sequence

from chipbench.readers import Facts, reader


@reader("counter_share")
def counter_share(f: Facts, num: str, parts: Sequence[str],
                  scale: float = 100.0):
    """``scale`` x the change of ``num`` over the traced part over the sum
    of the changes of ``parts``: with ``num`` among them, one part's share
    of the whole. Nothing to read where a counter is missing or the sum is
    0."""
    n, got = f.counters.get(num), [f.counters.get(p) for p in parts]
    if n is None or None in got or not sum(got):
        return None
    return scale * n / sum(got)
