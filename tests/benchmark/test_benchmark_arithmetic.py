"""Percentile, spread, histogram-quantile and open-loop schedule
arithmetic: the yardstick's own sums."""
import asyncio

import pytest

import _bench_util  # noqa: F401 — puts the repo root on sys.path
from benchmark.lib import probes, schedule, stats


@pytest.mark.parametrize("q,want", [
    (0, 1.0), (50, 3.0), (100, 5.0), (25, 2.0), (95, 4.8)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_is_none_and_range_is_checked():
    assert stats.percentile([], 50) is None
    assert stats.median([]) is None
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_beyond_counts_the_tail():
    xs = list(range(400))
    assert stats.beyond(xs, 95) == 20
    assert stats.beyond([], 95) == 0


def test_spread_is_iqr_over_median():
    assert stats.spread([10, 10, 10, 10]) == 0
    assert stats.spread([8, 9, 10, 11, 12]) == pytest.approx(0.2)
    assert stats.spread([]) is None


def test_histogram_quantile_matches_the_programs_estimate():
    from cometbft_tpu.libs.metrics import Histogram
    h = Histogram("h", "", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in [0.0005] * 10 + [0.005] * 70 + [0.05] * 15 + [0.5] * 5:
        h.observe(v)
    for q in (0.5, 0.9, 0.95, 0.99):
        assert stats.histogram_quantile(
            h.buckets, h._counts, h._count, q) == \
            pytest.approx(h.quantile(q))
    assert stats.histogram_quantile((1.0,), [0], 0, 0.5) is None


def test_exposition_delta_and_histogram_reading():
    from cometbft_tpu.libs.metrics import Registry
    reg = Registry()
    hist = reg.histogram("x", "seconds", labels=("kind",),
                         buckets=(0.001, 0.01, 0.1))
    ctr = reg.counter("x", "things")
    hist.with_labels("a").observe(0.5)          # before the window
    before = probes.metrics_snapshot(reg)
    for _ in range(10):
        hist.with_labels("a").observe(0.004)
    hist.with_labels("b").observe(0.05)
    ctr.add(3)
    delta = probes.metrics_delta(before, probes.metrics_snapshot(reg))
    assert probes.total(delta, "cometbft_x_things") == 3
    assert probes.total(delta, "cometbft_x_seconds_count") == 11
    assert probes.total(delta, "cometbft_x_seconds_count",
                        kind="a") == 10
    assert probes.hist_mean_ms(delta, "cometbft_x_seconds",
                               kind="a") == pytest.approx(4.0)
    assert 1.0 < probes.hist_quantile_ms(
        delta, "cometbft_x_seconds", 0.5) <= 10.0
    assert probes.hist_mean_ms(delta, "cometbft_x_none") is None


def test_due_times_cover_the_window_half_open():
    due = list(schedule.due_times(100.0, 0.05, 20))
    assert len(due) == 400 == schedule.count_due(0.05, 20)
    assert due[0] == (0, 100.0)
    assert due[-1][1] == pytest.approx(100.0 + 399 * 0.05)
    assert schedule.count_due(0.5, 1.2) == 3
    with pytest.raises(ValueError):
        list(schedule.due_times(0, 0, 1))


@pytest.mark.parametrize("spin_s", [0.0, 0.002])
def test_paced_sends_on_schedule_and_reports_lateness(spin_s):
    async def go():
        start = schedule.now() + 0.01
        seen = []
        async for i, due, late in schedule.paced(start, 0.01, 0.1,
                                                 spin_s=spin_s):
            seen.append((i, due, late, schedule.now()))
        return start, seen
    start, seen = asyncio.run(go())
    assert [s[0] for s in seen] == list(range(10))
    for i, due, late, at in seen:
        assert due == pytest.approx(start + i * 0.01)
        assert at >= due and late >= 0
        assert late <= at - due + 1e-9


def test_a_stalled_generator_catches_up_and_the_window_ends_on_time():
    async def go(behind, **kw):
        start = schedule.now() - behind
        return [(i, late) async for i, _, late in
                schedule.paced(start, 0.01, 0.08, **kw)]
    ticks = asyncio.run(go(0.05))           # already five ticks late
    assert [i for i, _ in ticks] == list(range(8))
    assert ticks[0][1] >= 0.05 - 1e-6       # charged from the due time
    # a generator more than OVERRUN_S behind when the window closes
    # sends nothing more: the driver counts the rest as failed
    behind = 0.2 + schedule.OVERRUN_S
    assert asyncio.run(go(behind)) == []
    # a cell far below capacity allows more: every due request is
    # still served, each charged its full wait
    late = asyncio.run(go(behind, overrun_s=schedule.OVERRUN_S + 5))
    assert [i for i, _ in late] == list(range(8))
    assert late[-1][1] >= behind - 0.07 - 1e-6
