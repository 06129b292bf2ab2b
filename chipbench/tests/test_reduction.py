"""The trace reduction on hand-built input, and the window arithmetic."""
import math

import pytest

from chipbench import stats, trace
from chipbench.trace import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def op(plane, name, start, dur, line=trace.OPS_LINE):
    return Event(plane, line, name, start, dur)


def test_union_counts_overlap_once():
    assert trace.union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4)
    assert trace.union_seconds([]) == 0.0


def test_busy_is_union_per_device_averaged():
    evs = [op(DEV0, "%while.1 = x", 0.0, 1.0),     # holds the next two
           op(DEV0, "%fusion.1 = x", 0.0, 0.4),
           op(DEV0, "%fusion.2 = x", 0.5, 0.5),
           op(DEV1, "%fusion.1 = x", 0.0, 0.5),
           op(HOST, "chipbench.wait", 0.0, 9.0, line="python3")]
    assert trace.device_planes(evs) == [DEV0, DEV1]
    assert trace.busy_seconds(evs) == pytest.approx((1.0 + 0.5) / 2)


def test_per_name_sums_and_top_ops_skip_containers():
    evs = [op(DEV0, "%while.1 = (s32[]) while(s32[] %a)", 0.0, 1.0),
           op(DEV0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0.0, 0.3),
           op(DEV0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0.5, 0.3),
           op(DEV0, "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", 0.9, 0.1)]
    assert sum(e.dur for e in trace.matching(evs, "fusion")) \
        == pytest.approx(0.6)
    top = trace.top_ops(evs, 5)
    assert top[0] == ["%fusion.1 fusion -> f32[8]", pytest.approx(0.6)]
    assert all("while" not in name for name, _ in top)


def test_idle_gaps_and_inside():
    evs = [op(DEV0, "a", 0.0, 1.0), op(DEV0, "b", 1.5, 0.5),
           op(DEV0, "c", 3.0, 1.0)]
    assert trace.idle_gaps(evs) == [(1.0, 1.5), (2.0, 3.0)]
    mods = [op(DEV0, "jit_decode(1)", 1.4, 1.0, line=trace.MODULES_LINE)]
    assert [e.name for e in trace.inside(evs, mods)] == ["b"]


def test_short_name_keeps_name_kind_and_shapes():
    long = ('%decoder.attn.29 = (bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}, '
            'bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}) custom-call(s32[10]{0} '
            '%copy-done.79), custom_call_target="tpu_custom_call"')
    assert trace.short_name(long) == ("%decoder.attn.29 custom-call -> "
                                      "(bf16[64,4096,128], bf16[64,4096,128])")


def test_percentile():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs + [math.inf], 95) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_stall_moves_the_tail_and_the_rate():
    steady = [[0.1 * i for i in range(100)]]             # one token / 100 ms
    stalled = [[0.1 * i + (2.0 if i >= 50 else 0.0) for i in range(100)]]
    for times, rate, p95 in ((steady, 10.0, 0.1), (stalled, 8.0, 0.1)):
        n = stats.tokens_in_window(times[0], 0.0, 10.0)
        assert n / 10.0 == pytest.approx(rate)
        gaps = stats.gaps_in_window(times, 0.0, 10.0)
        assert stats.percentile(gaps, 95) == pytest.approx(p95)
    # one stall among ~80 gaps is below the 95th percentile but is the
    # worst, and ten of them pass it
    gaps = stats.gaps_in_window(stalled, 0.0, 10.0)
    assert max(gaps) == pytest.approx(2.1)
    many = [[0.1 * i + 2.0 * (i // 10) for i in range(100)]]
    assert stats.percentile(stats.gaps_in_window(many, 0.0, 30.0), 95) \
        == pytest.approx(2.1)
