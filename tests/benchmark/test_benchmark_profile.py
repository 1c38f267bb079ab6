"""The profiler reduction — busy/idle union, the kernel's events by
name, gap attribution — on the small trace recorded on the chip
(benchmark/fixtures/verify_trace_small.json, a run of PR 22)."""
import json
import os

import pytest

from _bench_util import ROOT
from benchmark.lib import profile

with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "verify_trace_small.json")) as f:
    FIX = json.load(f)
TRACE, SPANS = FIX["trace"], FIX["spans"]


@pytest.mark.parametrize("intervals,want", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 15)], 15),
    ([(0, 10), (20, 30)], 20), ([(0, 10), (2, 3), (10, 12)], 12),
    ([(5, 5), (7, 6)], 0)])
def test_union_of_intervals(intervals, want):
    assert profile.union_ns(intervals) == want


def test_window_is_anchor_to_anchor():
    lo, hi = profile.window_ns(TRACE)
    assert hi - lo == pytest.approx(330e6)


def test_busy_and_idle_share_of_the_recorded_trace():
    b = profile.busy(TRACE)
    assert b["devices"] == 1
    assert b["window_s"] == pytest.approx(0.33)
    # seven 256-lane dispatches of ~0.68 ms in 330 ms
    assert b["busy_s"] == pytest.approx(0.00477, rel=0.01)
    assert profile.idle_share(TRACE) == pytest.approx(98.55, abs=0.02)


def test_kernel_events_are_found_by_name():
    evs = profile.kernel_events(TRACE)
    assert len(evs) == 7
    assert all(profile.KERNEL_MARK in e[0] for e in evs)
    assert sum(e[2] for e in evs) / len(evs) == \
        pytest.approx(681e3, rel=0.02)
    assert profile.kernel_events(TRACE, mark="no-such-kernel") == []


def test_top_ops_names_are_short_and_the_kernel_leads():
    top = profile.top_ops(TRACE)
    assert top[0][0] == "_pallas_verify.1"
    assert all(len(name) <= 120 and " = " not in name
               for name, _ in top)
    assert len(top) <= 10


def test_idle_gaps_tile_the_window_with_the_busy_time():
    gaps = profile.idle_gaps(TRACE)
    lo, hi = profile.window_ns(TRACE)
    idle = sum(e - s for s, e in gaps)
    assert idle + profile.busy(TRACE)["busy_s"] * 1e9 == \
        pytest.approx(hi - lo)


def test_gaps_are_attributed_to_the_innermost_span():
    by = dict(profile.attribute_gaps(TRACE, SPANS))
    # after the kernel ends the host still sits in kernel_execute
    # (mask read-back): ~2.5 ms a dispatch, innermost of batch_verify
    assert by["kernel_execute"] == pytest.approx(0.0186, rel=0.05)
    assert by["kernel_execute"] > by["batch_verify"]
    assert by[profile.UNATTRIBUTED] > 0.25     # waiting for requests
    assert sum(by.values()) == pytest.approx(
        sum(e - s for s, e in profile.idle_gaps(TRACE)) / 1e9)
    # the driver's own span takes what no program span covers
    lo, hi = TRACE["anchors"][0][0], TRACE["anchors"][-1][0]
    waiting = [{"name": "await_next_request", "ts_ns": lo,
                "dur_ns": hi - lo}]
    by2 = dict(profile.attribute_gaps(TRACE, SPANS + waiting))
    assert profile.UNATTRIBUTED not in by2
    assert by2["kernel_execute"] == pytest.approx(by["kernel_execute"])


def test_a_trace_without_device_ops_reads_as_nothing():
    empty = {"devices": [], "anchors": TRACE["anchors"]}
    assert profile.busy(empty) is None
    assert profile.idle_share(empty) is None
    assert profile.kernel_events(empty) == []


def test_short_name():
    assert profile.short_name(
        "%fusion.3 = s32[8,256]{1,0} fusion(s32[8] %x)") == "fusion.3"
    assert profile.short_name("jit_f(123)") == "jit_f(123)"
