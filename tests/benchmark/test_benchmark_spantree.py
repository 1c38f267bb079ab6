"""lib/spantree on a hand-made span list (numbers small enough to
check by eye), and every reader built on it on spans and a trace
recorded on the chip (benchmark/fixtures/spans_small.json, runs of
PR 24).  A span list without ids and parents — what a program from
before PR 24 records — must give every one of them nothing to read."""
import copy
import json
import os

import pytest

from _bench_util import ROOT
from benchmark.lib import loader, spantree
from benchmark.lib.session import Obs

OFF = 1_000_000          # profiler clock - monotonic clock, ns


def ev(i, parent, name, start, end, height=5, **attrs):
    out = {"ts_ns": start, "dur_ns": end - start, "category": "x",
           "name": name, "height": height, "id": i, "parent": parent,
           "tid": 1}
    if attrs:
        out["attrs"] = attrs
    return out


SPANS = [
    ev(1, 0, "sync_height", 0, 1000, outcome="applied"),
    ev(2, 1, "part_set", 10, 60),
    ev(3, 1, "commit_verify", 100, 400),
    ev(4, 3, "commit_walk", 110, 200),
    ev(5, 3, "batch_verify", 210, 390, batch=6),
    ev(6, 5, "kernel_execute", 220, 380, warm=True, bucket=128),
    ev(7, 6, "h2d", 225, 245),
    ev(8, 6, "launch", 250, 290),
    ev(9, 6, "device_wait", 300, 360),
    ev(10, 6, "d2h", 362, 378),
    ev(11, 1, "apply_block", 500, 900),
    ev(12, 11, "consensus/finalize_block", 510, 700),
    ev(13, 12, "state_root", 520, 690, hashes=40),
    ev(14, 11, "state_save", 690, 800),              # overlaps #12
    ev(15, 0, "sync_wait", 1000, 1100),
    ev(16, 0, "block_decode", 1050, 1090, height=9),
    ev(17, 0, "sync_height", 1100, 1500, height=6, outcome="refused"),
    ev(18, 17, "kernel_execute", 1200, 1400, height=6, warm=False,
       bucket=128),
    ev(19, 18, "h2d", 1210, 1300, height=6),
]
TRACE = {
    "anchors": [[0, OFF], [2000, 2000 + OFF]],
    "devices": [{"name": "/device:TPU:0", "ops": [], "modules": [
        ["jit__pallas_verify_packed(1)", 292.0 + OFF, 60.0],
        # begins inside the cold dispatch, ends after it: held by none
        ["jit__pallas_verify_packed(1)", 1390.0 + OFF, 60.0],
        ["jit_something_else(2)", 600.0 + OFF, 10.0]]}],
}


def stripped(spans):
    """The same spans as an older program records them."""
    return [{k: v for k, v in e.items()
             if k not in ("id", "parent", "tid")} for e in spans]


def test_children_ancestors_and_ids():
    ids = spantree.by_id(SPANS)
    kids = spantree.children(SPANS)
    assert [e["name"] for e in kids[1]] == [
        "part_set", "commit_verify", "apply_block"]
    assert [e["name"] for e in spantree.ancestors(ids[7], ids)] == [
        "kernel_execute", "batch_verify", "commit_verify",
        "sync_height"]
    assert spantree.ancestors(ids[1], ids) == []
    assert 0 not in kids and 0 not in ids


def test_self_time_is_duration_minus_the_union_of_children():
    kids = spantree.children(SPANS)
    ids = spantree.by_id(SPANS)
    # apply_block 400 - union([510,700) + [690,800)) = 400 - 290
    assert spantree.self_ns(ids[11], kids) == 110
    # sync_height 1000 - (50 + 300 + 400)
    assert spantree.self_ns(ids[1], kids) == 250
    assert spantree.self_ns(ids[4], kids) == 90     # a leaf: all its own
    # a child reaching past its parent counts only inside it
    late = SPANS + [ev(30, 4, "late", 190, 260)]
    assert spantree.self_ns(ids[4], spantree.children(late)) == 80


def test_coverage_and_the_unattributed_share():
    assert spantree.coverage_ns(SPANS, 0, 1500) == 1500
    assert spantree.coverage_ns(SPANS, 900, 1200) == 300
    assert spantree.unattributed_share(SPANS) == 0.0
    holed = [e for e in SPANS if e["name"] not in (
        "sync_wait", "block_decode")]
    assert spantree.unattributed_share(holed) == \
        pytest.approx(100.0 * 100 / 1500)
    assert spantree.unattributed_share([]) is None
    # an instant covers nothing and stretches nothing
    mark = dict(ev(40, 0, "mark", 5000, 5000))
    assert spantree.unattributed_share(SPANS + [mark]) == 0.0
    # the loop's two frames stretch the window and name nothing: what
    # only they cover is unattributed.  Of [0, 1500): sync_height's
    # children leave 250 of the first height and all but the cold
    # dispatch (200) of the refused one, block_decode names 40 of the
    # wait
    frames = ("sync_height", "sync_wait")
    assert spantree.unattributed_share(SPANS, frames) == \
        pytest.approx(100.0 * (250 + 200 + 60) / 1500)
    assert spantree.unattributed_share(
        [e for e in SPANS if e["name"] in frames], frames) == 100.0


def test_per_height_sums_count_applied_heights_only():
    assert spantree.heights_applied(SPANS) == 1
    assert spantree.per_height_ms(SPANS, "apply_block") == \
        pytest.approx(400e-6)
    assert spantree.per_height_ms(
        SPANS, "block_decode", "part_set") == pytest.approx(90e-6)
    assert spantree.per_height_count(SPANS, "state_root",
                                     "hashes") == 40
    assert spantree.per_height_ms(SPANS, "no_such") is None
    assert spantree.per_height_count(SPANS, "state_root",
                                     "no_such") is None
    refused = [e for e in SPANS if e["id"] >= 15]
    assert spantree.per_height_ms(refused, "sync_wait") is None
    # self time a height: commit_verify 300 - (90 + 180)
    assert spantree.per_height_self_ms(SPANS, "commit_verify") == \
        pytest.approx(30e-6)
    assert spantree.per_height_self_ms(SPANS, "no_such") is None
    assert spantree.per_height_self_ms(
        stripped(SPANS), "commit_verify") is None


def test_legs_are_read_under_warm_dispatches_only():
    assert [e["id"] for e in spantree.under(
        SPANS, "h2d", "kernel_execute")] == [7, 19]
    assert [e["id"] for e in spantree.under(
        SPANS, "h2d", "kernel_execute", warm_only=True)] == [7]
    assert spantree.median_under_ms(
        SPANS, "h2d", "kernel_execute", warm_only=True) == \
        pytest.approx(20e-6)
    assert spantree.under(SPANS, "h2d", "batch_verify") == []
    assert spantree.median_under_ms(SPANS, "d2h", "no_such") is None


def test_device_events_are_matched_to_the_span_that_holds_them():
    found = spantree.dispatches(TRACE, SPANS)
    assert [d["span"] and d["span"]["id"] for d in found] == [6, None]
    assert found[0]["start"] == 292 + OFF
    assert spantree.containment_share(TRACE, SPANS) == 50.0
    # the innermost holder wins: a wider span around the dispatch
    wide = SPANS + [ev(50, 0, "kernel_execute", 0, 2000, warm=True)]
    assert spantree.dispatches(TRACE, wide)[0]["span"]["id"] == 6
    assert spantree.dispatches(TRACE, wide)[1]["span"]["id"] == 50


def test_dispatch_overhead_needs_the_shared_clock_only_to_pair():
    # launch start 250 -> device_wait end 360, less the kernel's 60
    # (ns -> us); the cold dispatch has no launch and no wait, so its
    # kernel pairs with nothing
    assert spantree.dispatch_overheads_us(TRACE, SPANS, slack_ns=50) \
        == [pytest.approx(0.050)]
    # two tiles in flight: each kernel goes to its own tile though
    # both spans hold the first kernel's midpoint
    ms = 1_000_000
    tiles = [ev(60, 0, "kernel_execute", 0, 16 * ms, warm=True),
             ev(61, 60, "launch", 1 * ms, 2 * ms),
             ev(62, 60, "device_wait", 5 * ms, 15 * ms),
             ev(63, 0, "kernel_execute", 3 * ms, 27 * ms, warm=True),
             ev(64, 63, "launch", 4 * ms, 5 * ms),
             ev(65, 63, "device_wait", 16 * ms, 26 * ms)]
    trace = {"anchors": [[0, OFF]], "devices": [{
        "name": "d", "ops": [], "modules": [
            ["jit__pallas_verify_packed(1)", 2.0 * ms + OFF, 11.0 * ms],
            ["jit__pallas_verify_packed(1)", 13.0 * ms + OFF,
             11.0 * ms]]}]}
    assert spantree.dispatch_overheads_us(trace, tiles) == [
        pytest.approx(3000.0), pytest.approx(11000.0)]
    # a clock that places the device's events a millisecond off in
    # either direction changes nothing: both ends are the host's
    for skew in (-ms, ms):
        skewed = dict(trace, anchors=[[0, OFF - skew]])
        assert spantree.dispatch_overheads_us(skewed, tiles) == [
            pytest.approx(3000.0), pytest.approx(11000.0)]


def test_no_trace_no_anchors_no_kernel_means_nothing_to_read():
    for trace in (None, {"devices": [], "anchors": []},
                  dict(TRACE, anchors=[])):
        assert spantree.dispatches(trace, SPANS) == []
        assert spantree.containment_share(trace, SPANS) is None
        assert spantree.dispatch_overheads_us(trace, SPANS) == []
    quiet = dict(TRACE, devices=[{"name": "d", "ops": [], "modules": [
        ["jit_something_else(2)", 600.0 + OFF, 10.0]]}])
    assert spantree.containment_share(quiet, SPANS) is None


def test_spans_without_ids_give_the_tree_nothing():
    old = stripped(SPANS)
    assert spantree.by_id(old) == {} and spantree.children(old) == {}
    assert spantree.under(old, "h2d", "kernel_execute") == []
    assert spantree.dispatch_overheads_us(TRACE, old) == []
    # sums by name and the containment check need no ids
    assert spantree.per_height_ms(old, "apply_block") == \
        pytest.approx(400e-6)
    assert spantree.containment_share(TRACE, old) == 50.0


# -- the readers, on what the chip recorded ----------------------------------

with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_small.json")) as f:
    REC = json.load(f)
BENCH = loader.Bench(ROOT)
SYNC = ["sync_apply_block_ms", "sync_state_root_ms",
        "sync_root_hashes_per_height", "sync_state_save_ms",
        "sync_store_save_ms", "sync_wire_ms", "sync_serve_ms",
        "sync_wait_ms", "sync_validate_block_ms",
        "sync_unattributed_share"]
LEGS = ["h2d_ms", "launch_ms", "device_wait_ms", "d2h_ms"]
VERIFY = ["commit_walk_ms"] + LEGS + [
    "dispatch_overhead_us", "span_containment_share"]


def obs(cell, spans, trace=None):
    return Obs(cell=BENCH.cell(cell), spans=spans, setup_spans=[],
               metrics={}, samples={}, compiles_in_window=0, laps={},
               device_kind="TPU v5 lite", trace=trace,
               trace_spans=spans)


def read(metric, o):
    return BENCH.reader(metric).read(o)


def test_the_new_metrics_are_registered_where_they_read():
    entries = {m["name"]: m for m in BENCH.manifest["per_layer"]}
    for name in SYNC:
        assert entries[name]["workloads"] == ["qa-175.catchup"]
        assert entries[name]["moves"] == "sync_heights_per_s"
    for name in VERIFY:
        assert "qa-175.verify" in entries[name]["workloads"]
        assert entries[name]["moves"] == "verify_p50_ms"
        assert entries[name]["source"] == (
            "device_trace" if name.endswith(("_us", "_share"))
            else "program_span")


@pytest.mark.parametrize("metric", SYNC)
def test_catchup_reader_on_the_recorded_heights(metric):
    o = obs("qa-175.catchup", REC["catchup"]["spans"])
    value = read(metric, o)
    assert value is not None and value >= 0
    # a program from before PR 24 records none of these spans
    old = [e for e in stripped(REC["catchup"]["spans"])
           if e["name"] in ("batch_verify", "host_prep",
                            "kernel_execute")]
    assert read(metric, obs("qa-175.catchup", old)) is None


def test_the_recorded_heights_add_up():
    o = obs("qa-175.catchup", REC["catchup"]["spans"])
    v = {m: read(m, o) for m in SYNC}
    heights = spantree.heights_applied(o.spans)
    assert heights == REC["catchup"]["heights"]
    # the root is most of apply_block; 16 txs a height hash 16 leaves
    # and every inner node of a tree of some 17,000 keys
    assert 0.5 * v["sync_apply_block_ms"] < v["sync_state_root_ms"] \
        < v["sync_apply_block_ms"]
    roots = [e for e in o.spans if e["name"] == "state_root"]
    assert all(set(e["attrs"]) == {"hashes"} for e in roots)
    assert 17_000 < v["sync_root_hashes_per_height"] < 19_000
    assert v["sync_root_hashes_per_height"] == pytest.approx(
        sum(e["attrs"]["hashes"] for e in roots) / heights)
    assert v["sync_state_save_ms"] < v["sync_apply_block_ms"]
    # what only the loop's two frames cover: more than nothing (the
    # p2p connections' work inside the wait has no span), and less
    # than the frames' whole share of the window
    kids = spantree.children(o.spans)
    lo = min(e["ts_ns"] for e in o.spans if e["dur_ns"] > 0)
    hi = max(e["ts_ns"] + e["dur_ns"] for e in o.spans)
    frames = [e for e in o.spans
              if e["name"] in ("sync_height", "sync_wait")]
    assert 1.0 < v["sync_unattributed_share"] < \
        100.0 * spantree.coverage_ns(frames, lo, hi) / (hi - lo)
    own = sum(spantree.self_ns(e, kids) for e in frames)
    assert v["sync_unattributed_share"] <= \
        100.0 * own / (hi - lo) + 1e-9
    # validate_block's own time is what its commit_verify leaves
    validates = [e for e in o.spans if e["name"] == "validate_block"]
    assert v["sync_validate_block_ms"] == pytest.approx(
        sum(spantree.self_ns(e, kids) for e in validates) / 1e6
        / heights)
    assert 0 < v["sync_validate_block_ms"] < \
        sum(e["dur_ns"] for e in validates) / 1e6 / heights
    # apply_block keeps almost no time for itself
    applies = [e for e in o.spans if e["name"] == "apply_block"]
    own = sum(spantree.self_ns(e, kids) for e in applies)
    assert own < 0.1 * sum(e["dur_ns"] for e in applies)


@pytest.mark.parametrize("metric", VERIFY)
def test_verify_reader_on_the_recorded_dispatches(metric):
    rec = REC["verify"]
    value = read(metric, obs("qa-175.verify", rec["spans"],
                             rec["trace"]))
    assert value is not None
    old = obs("qa-175.verify", stripped(
        [e for e in rec["spans"] if e["name"] in (
            "batch_verify", "host_prep", "kernel_execute")]),
        rec["trace"])
    if metric == "span_containment_share":
        # names alone suffice: an older program is read too
        assert read(metric, old) == pytest.approx(value)
    else:
        assert read(metric, old) is None


def test_the_recorded_legs_add_up_to_the_dispatch():
    rec = REC["verify"]
    o = obs("qa-175.verify", rec["spans"], rec["trace"])
    legs = sum(read(m, o) for m in LEGS)
    whole = read("kernel_execute_ms", o)
    assert legs == pytest.approx(whole, rel=0.05)
    assert read("span_containment_share", o) == 100.0
    # launch -> wake-up is longer than the kernel, and by less than
    # the dispatch
    assert 0 < read("dispatch_overhead_us", o) < 1e3 * whole
    # every span of a request carries its height, up to commit_verify
    ids = spantree.by_id(o.spans)
    for e in o.spans:
        if e["name"] in LEGS + ["kernel_execute", "host_prep"]:
            chain = spantree.ancestors(e, ids)
            assert chain[-1]["name"] == "commit_verify"
            assert {a["height"] for a in chain} == {e["height"]}
            assert e["height"] > 0


def test_a_tiled_commit_is_a_span_per_tile_from_dispatch():
    """The 10,000-validator one-off: verify_commit_light, 6,668
    signatures in two 4096-lane tiles."""
    rec = REC["tiled"]
    tiles = sorted((e for e in rec["spans"]
                    if e["name"] == "kernel_execute"),
                   key=lambda e: e["ts_ns"])
    preps = sorted((e for e in rec["spans"]
                    if e["name"] == "host_prep"),
                   key=lambda e: e["ts_ns"])
    assert len(tiles) == 2 * rec["commits"] == len(preps)
    for first, second, prep in zip(tiles[0::2], tiles[1::2],
                                   preps[1::2]):
        assert (first["attrs"]["tile"], second["attrs"]["tile"]) == \
            (0, 1)
        assert first["attrs"]["bucket"] == 4096
        assert first["parent"] == second["parent"] == prep["parent"]
        # tile 0 is dispatched before tile 1's host_prep and settled
        # after it
        assert first["ts_ns"] < prep["ts_ns"]
        assert first["ts_ns"] + first["dur_ns"] > \
            prep["ts_ns"] + prep["dur_ns"]
    o = obs("qa-175.verify", rec["spans"], rec["trace"])
    assert read("span_containment_share", o) == 100.0
    found = spantree.dispatches(rec["trace"], rec["spans"])
    assert len(found) == len(tiles)
    assert sorted(d["span"]["id"] for d in found) == \
        sorted(t["id"] for t in tiles)


def test_readers_do_not_raise_on_an_empty_window():
    for metric in SYNC:
        assert read(metric, obs("qa-175.catchup", [])) is None
    for metric in VERIFY:
        assert read(metric, obs("qa-175.verify", [])) is None
    deep = copy.deepcopy(REC["verify"]["spans"])
    for e in deep:
        e.pop("attrs", None)
    for metric in VERIFY:
        read(metric, obs("qa-175.verify", deep, REC["verify"]["trace"]))
