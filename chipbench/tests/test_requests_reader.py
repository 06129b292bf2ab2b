"""The reader of the engine's request-seconds before first tokens
(``readers_requests.py``) and the three share metrics on it."""
import json
import os

import pytest

from chipbench import readers, readers_requests, spec
from chipbench.readers import Facts

PARTS = ["request_queued_seconds_total", "request_prompt_wait_seconds_total",
         "request_prompt_launched_seconds_total"]
SHARES = ("ttft_queued_share.serve", "ttft_prompt_wait_share.serve",
          "ttft_launched_share.serve")


def facts(counters):
    return Facts({}, {}, 1, {}, [], 3.0, counters)


def metric(name):
    with open(os.path.join(spec.HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def test_nothing_to_read_without_the_counters_or_with_no_time():
    share = readers_requests.counter_share
    assert share(facts({}), PARTS[0], PARTS) is None
    # a program before the counters: the registry's other names only
    assert share(facts({"decode_rows_total": 40}), PARTS[0], PARTS) is None
    assert share(facts({PARTS[0]: 1.0, PARTS[1]: 2.0}), PARTS[0],
                 PARTS) is None
    assert share(facts(dict.fromkeys(PARTS, 0.0)), PARTS[0], PARTS) is None
    assert share(facts({PARTS[0]: 1.0, PARTS[1]: 3.0}), PARTS[0],
                 PARTS[:2]) == 25.0


def test_the_three_shares_sum_to_100():
    f = facts({PARTS[0]: 0.25, PARTS[1]: 1.75, PARTS[2]: 6.0,
               "decode_rows_total": 900})
    got = [readers.read(metric(n), f) for n in SHARES]
    assert got == pytest.approx([3.125, 21.875, 75.0])
    assert sum(got) == pytest.approx(100.0, abs=1e-9)


def test_the_entries_name_existing_cells_that_report_ttft():
    bench = spec.benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    ttft = next(m for m in bench["end_to_end"] if m["name"] == "ttft_p95_ms")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in SHARES:
        m = entries[name]
        assert m["moves"] == "ttft_p95_ms" and m["unit"] == "%"
        assert m["source"] == "program_counter"
        assert m["layer"] == "server host side"
        assert m["workloads"] == ["mistral7b.serve.chat", "dsv3.serve.docqa",
                                  "glm52.serve.longdoc"]
        assert set(m["workloads"]) <= cells & set(ttft["workloads"])
        assert metric(name)["reader"] in readers.READERS
