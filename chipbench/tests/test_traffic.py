"""The traffic generator: the seed changes the inputs, never the work."""
import json
import os

import pytest

from chipbench import spec, traffic

MIXES = sorted({w["traffic"] for w in spec.benchmark()["workloads"]
                if spec.load_cell(w["name"]).traffic["kind"] == "serve_open"})


def mix(name, **over):
    with open(os.path.join(spec.HERE, "traffic", name + ".json")) as f:
        return dict(json.load(f), **over)


def shape(plan):
    return [(p.due, len(p.prompt), p.max_new_tokens) for p in plan]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_inputs(name):
    t = mix(name)
    a = traffic.plan(t, 2147483659, 32768, 45.0)
    b = traffic.plan(t, 2147483659, 32768, 45.0)
    assert [(p.rid, p.due, p.prompt, p.max_new_tokens) for p in a] \
        == [(p.rid, p.due, p.prompt, p.max_new_tokens) for p in b]
    assert all(0 <= tok < 32768 for p in a for tok in p.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    t = mix(name)
    a = traffic.plan(t, 1, 32768, 45.0)
    b = traffic.plan(t, 2, 32768, 45.0)
    assert shape(a) == shape(b)              # one schedule for every seed
    assert a[0].prompt != b[0].prompt        # the ids are the seed's
    dues = [p.due for p in a]
    assert dues == sorted(dues) and 0.0 < dues[0] and dues[-1] < 45.0
    assert len(a) == pytest.approx(t["rate_per_s"] * 45.0, rel=0.25)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_the_mix(name):
    t = mix(name)
    for p in traffic.plan(t, 3, 32768, 600.0):
        assert t["prompt_tokens"]["min"] <= len(p.prompt) \
            <= t["prompt_tokens"]["max"]
        assert t["output_tokens"]["min"] <= p.max_new_tokens \
            <= t["output_tokens"]["max"]
