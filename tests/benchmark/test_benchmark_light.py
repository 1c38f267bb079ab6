"""The readers of the light client's spans (PR 31: light_* and
trusting_walk_ms, sig_cache_hit_share) and lib/lightspans under them:
on a hand-made span list, and on two requests of light-1k.skip recorded
on the chip (benchmark/fixtures/spans_light1k.json), which pin the
cell's traffic: 4 verified hops, 3 refusals, 8 dispatches a request.
A program that records none of these spans gives every reader
nothing to read."""
import json
import os

import pytest

from _bench_util import ROOT
from benchmark.lib import lightspans, loader
from benchmark.lib.session import Obs

BENCH = loader.Bench(ROOT)
CELL = "light-1k.skip"
NEW = ["light_hops_per_request", "light_refusals_per_request",
       "light_dispatches_per_request", "light_hop_ms",
       "light_refusal_ms", "light_header_ms", "trusting_walk_ms",
       "sig_cache_hit_share", "light_store_ms", "light_store_read_ms",
       "light_fetch_ms", "light_unattributed_share"]
LAYER = dict.fromkeys(NEW, "light client") | {
    "trusting_walk_ms": "commit verification",
    "sig_cache_hit_share": "commit verification",
    "light_fetch_ms": "light provider"}
# the accepted metrics the cell is appended to whose readers read
# spans alone
ACCEPTED = ["seam_ms", "fallback_share", "host_prep_ms",
            "kernel_execute_ms", "pad_share", "commit_walk_ms", "h2d_ms",
            "launch_ms", "device_wait_ms", "d2h_ms", "after_walk_ms",
            "eager_tiles_per_commit"]
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_light1k.json")) as f:
    REC = json.load(f)
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_valset10k.json")) as f:
    OTHER = json.load(f)     # a program's spans with no light client


def ev(i, parent, name, start, end, **attrs):
    out = {"ts_ns": start, "dur_ns": end - start, "category": "x",
           "name": name, "height": 9, "id": i, "parent": parent,
           "tid": 1}
    if attrs:
        out["attrs"] = attrs
    return out


def hop(i, parent, start, end, outcome):
    return ev(i, parent, "light_hop", start, end, trusted=1,
              candidate=9, outcome=outcome)


# one request: the store read, a refusal (header checks, a whole walk
# by address, no batch), a fetch, a verified hop of two checks, its
# save, the witness check; then a second request that ends in a forged
# signature
SPANS = [
    ev(1, 0, "light_sync", 0, 1000, **{"from": 1, "to": 9}),
    ev(19, 1, "light_store_read", 0, 10),
    ev(2, 1, "light_fetch", 10, 50),
    hop(3, 1, 60, 160, "cant_trust"),
    ev(4, 3, "header_checks", 60, 90),
    ev(5, 3, "commit_verify", 95, 158),
    ev(6, 5, "commit_walk", 96, 156, lookup="address", walked=40,
       cache_hits=0),
    ev(7, 1, "light_fetch", 170, 230),
    hop(8, 1, 240, 740, "verified"),
    ev(9, 8, "header_checks", 240, 280),
    ev(10, 8, "commit_verify", 290, 500),
    ev(11, 10, "commit_walk", 292, 332, lookup="address", walked=30,
       cache_hits=0),
    ev(12, 10, "batch_verify", 340, 498, batch=14, backend="tpu"),
    ev(13, 8, "commit_verify", 510, 738),
    ev(14, 13, "commit_walk", 512, 552, lookup="index", walked=27,
       cache_hits=9),
    ev(15, 13, "batch_verify", 560, 736, batch=18, backend="tpu"),
    ev(16, 1, "light_store_save", 750, 850),
    ev(17, 1, "light_detect", 860, 990),
    ev(18, 17, "light_fetch", 862, 902),
    ev(30, 0, "light_sync", 2000, 2400, **{"from": 1, "to": 9}),
    ev(37, 30, "light_store_read", 2000, 2006),
    ev(31, 30, "light_fetch", 2010, 2040),
    hop(32, 30, 2050, 2390, "invalid"),
    ev(33, 32, "header_checks", 2050, 2080),
    ev(34, 32, "commit_verify", 2090, 2380),
    ev(35, 34, "commit_walk", 2092, 2112, lookup="address", walked=20,
       cache_hits=0),
    ev(36, 34, "batch_verify", 2120, 2378, batch=14, backend="tpu"),
]


def obs(spans):
    return Obs(cell=BENCH.cell(CELL), spans=spans, setup_spans=[],
               metrics={}, samples={}, compiles_in_window=0, laps={},
               device_kind="TPU v5 lite", trace=None, trace_spans=spans)


def read(metric, spans):
    return BENCH.reader(metric).read(obs(spans))


@pytest.mark.parametrize("metric,want", [
    # medians over two requests: (1, 0), (1, 0), (2, 1)
    ("light_hops_per_request", 0.5),
    ("light_refusals_per_request", 0.5),
    ("light_dispatches_per_request", 1.5),
    ("light_hop_ms", 500e-6),
    ("light_refusal_ms", 100e-6),
    ("light_header_ms", 30e-6),             # 30, 40, 30
    ("trusting_walk_ms", 40e-6),            # 60, 40, 20
    ("sig_cache_hit_share", 100 * 9 / 27),
    ("light_store_ms", 100e-6),
    ("light_store_read_ms", 8e-6),          # 10, 6
    ("light_fetch_ms", 40e-6),              # 40, 60, 40, 30
    # the first request leaves [50,60) [160,170) [230,240) [740,750)
    # [850,860) [990,1000) to no child, the second [2006,2010)
    # [2040,2050) and [2390,2400): 84 of 1,400
    ("light_unattributed_share", 100 * 84 / 1400),
])
def test_readers_on_the_hand_made_requests(metric, want):
    assert read(metric, SPANS) == pytest.approx(want)


def test_per_sync_counts_whatever_lies_below_a_request():
    walks = lightspans.per_sync(
        SPANS, lambda e: e["name"] == "commit_walk")
    assert walks == 2       # 3 and 1
    assert lightspans.hops_per_sync(SPANS, "invalid") == 0.5
    # a span above no request counts for none
    stray = SPANS + [ev(90, 0, "batch_verify", 5000, 5100, batch=2)]
    assert read("light_dispatches_per_request", stray) == 1.5
    assert lightspans.median_ms(SPANS, "light_hop", outcome="lost") \
        is None


@pytest.mark.parametrize("metric,want", [
    ("light_hops_per_request", 4),
    ("light_refusals_per_request", 3),
    ("light_dispatches_per_request", 8),
])
def test_the_recorded_requests_pin_the_cells_traffic(metric, want):
    assert REC["requests"] == 2
    assert read(metric, REC["spans"]) == want


@pytest.mark.parametrize("metric,lo,hi", [
    ("light_hop_ms", 10, 25),
    ("light_refusal_ms", 2, 8),
    ("light_header_ms", 2, 6),
    ("trusting_walk_ms", 0.8, 3),
    ("sig_cache_hit_share", 33, 38),
    ("light_store_ms", 6, 16),
    ("light_store_read_ms", 12, 32),
    ("light_fetch_ms", 2, 6),
    ("light_unattributed_share", 0, 2),
])
def test_readers_on_the_recorded_requests(metric, lo, hi):
    assert lo <= read(metric, REC["spans"]) <= hi


@pytest.mark.parametrize("metric", ACCEPTED)
def test_accepted_readers_read_the_recorded_requests(metric):
    """The accepted readers, unedited, find something to read in the
    cell's spans."""
    value = read(metric, REC["spans"])
    assert value is not None
    if metric == "pad_share":       # 334- and ~430-lane batches at 512
        assert value == 100 * (1 - 3050 / 4096)
    if metric in ("fallback_share", "eager_tiles_per_commit"):
        assert value == 0


def test_the_recorded_requests_as_the_drivers_check_reads_them():
    skip = BENCH.traffic("skip")
    hops = [h[1:] for h in skip.observed_hops(REC["spans"])]
    assert len(hops) == 14
    for request in (hops[:7], hops[7:]):
        assert [h for h, _ in request] == [
            (1, 257, "cant_trust"), (1, 129, "cant_trust"),
            (1, 65, "verified"), (65, 129, "verified"),
            (129, 257, "cant_trust"), (129, 193, "verified"),
            (193, 257, "verified")]
        for (_, _, outcome), checks in request:
            if outcome == "cant_trust":
                assert checks == {"trusting": (1000, 0, 0)}
                continue
            walked, batched, hits = checks["trusting"]
            assert (batched, hits) == (334, 0) and 900 < walked < 960
            walked, batched, hits = checks["light"]
            assert walked == 667 == batched + hits and 400 < batched


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_gives_nothing_to_read(metric):
    assert read(metric, OTHER["spans"]) is None
    assert read(metric, []) is None


def test_what_the_cell_reports():
    """The accepted metrics it was appended to, and the twelve readers
    of the light client's spans, each an entry of its own with these
    fields."""
    listed = {m["name"]: m for m in BENCH.manifest["per_layer"]}
    reported = [m["name"] for m in BENCH.metrics("per_layer", CELL)]
    for metric in ACCEPTED:
        assert CELL in listed[metric]["workloads"]
        assert metric in reported
    for metric in NEW:
        assert callable(BENCH.reader(metric).read)
        assert reported.count(metric) == 1
        entry = dict(listed[metric])
        assert entry.pop("unit") == (
            "ms" if metric.endswith("_ms") else
            "%" if metric.endswith("_share") else "count")
        assert entry.pop("better") == (
            "higher" if metric == "sig_cache_hit_share" else "lower")
        assert entry == {
            "name": metric, "source": "program_span",
            "layer": LAYER[metric], "moves": "verify_p50_ms",
            "workloads": [CELL]}


def test_a_traced_run_reads_every_light_reader_through_the_manifest():
    """No reader of the light client's spans is left unlisted: the
    harness's own reading of a traced run over the recorded requests
    (run.py's per_layer_metrics) holds all twelve, each at its reader's
    reading, beside the accepted ones; over a program without the
    light client's spans it holds none of them."""
    from benchmark.run import per_layer_metrics
    line = per_layer_metrics(BENCH, CELL, obs(REC["spans"]))
    assert [m for m in NEW + ACCEPTED if m not in line] == []
    units = {m["name"]: m["unit"] for m in BENCH.manifest["per_layer"]}
    for metric in NEW:
        assert line[metric] == {"value": read(metric, REC["spans"]),
                                "unit": units[metric]}
    assert [line[m]["value"] for m in NEW[:3]] == [4, 3, 8]
    other = per_layer_metrics(BENCH, CELL, obs(OTHER["spans"]))
    assert [m for m in NEW if m in other] == []
    assert "seam_ms" in other
