"""The readers of the tiled dispatch (PR 27: tiles_per_commit,
walk_share, seam_outside_tiles_ms, tile_device_gap_us) and
lib/tiled.batches under them: on a hand-made span list, and on three
commits of valset-10k.verify recorded on the chip
(benchmark/fixtures/spans_valset10k.json).  A program that records no
ids and parents gives the span readers nothing to read."""
import json
import os

import pytest

from _bench_util import ROOT
from benchmark.lib import loader, tiled
from benchmark.lib.session import Obs

BENCH = loader.Bench(ROOT)
CELL = "valset-10k.verify"
NEW = ["tiles_per_commit", "walk_share", "seam_outside_tiles_ms",
       "tile_device_gap_us"]
OFF = 1_000_000
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_valset10k.json")) as f:
    REC = json.load(f)


def ev(i, parent, name, start, end, **attrs):
    out = {"ts_ns": start, "dur_ns": end - start, "category": "x",
           "name": name, "height": 7, "id": i, "parent": parent,
           "tid": 1}
    if attrs:
        out["attrs"] = attrs
    return out


def tile(i, parent, start, end, n, **more):
    return ev(i, parent, "kernel_execute", start, end, warm=True,
              pipelined=True, tile=n, **more)


# one commit as the pipeline records it: the walk, then the seam with
# three tiles, each tile's host_prep inside the flight of the one
# before; and a second, small commit that fits one bucket
SPANS = [
    ev(1, 0, "commit_verify", 0, 1000),
    ev(2, 1, "commit_walk", 10, 410),
    ev(3, 1, "batch_verify", 420, 980, batch=90, backend="tpu"),
    ev(4, 3, "item_handover", 420, 450),
    ev(5, 3, "host_prep", 460, 500, pipelined=True),
    tile(6, 3, 505, 700, 0),
    ev(7, 3, "host_prep", 520, 560, pipelined=True),
    tile(8, 3, 565, 820, 1),
    ev(9, 3, "host_prep", 705, 745, pipelined=True),
    tile(10, 3, 750, 950, 2),
    ev(11, 3, "mask_handback", 955, 960),
    ev(12, 3, "item_release", 962, 978),
    ev(20, 0, "commit_verify", 2000, 2400),
    ev(21, 20, "commit_walk", 2010, 2110),
    ev(22, 20, "batch_verify", 2120, 2390, batch=6, backend="tpu"),
    ev(23, 22, "host_prep", 2130, 2150),
    ev(24, 22, "kernel_execute", 2160, 2380, warm=True),
]
KERNEL = "jit__pallas_verify_packed(1)"
TRACE = {
    "anchors": [[0, OFF], [600_000_000, 600_000_000 + OFF]],
    "devices": [{"name": "/device:TPU:0", "ops": [
        ["copy-start", 690.0 + OFF, 4.0]], "modules": [
        [KERNEL, 600.0 + OFF, 90.0],
        ["jit_other(2)", 692.0 + OFF, 6.0],    # overlaps the copy
        [KERNEL, 700.0 + OFF, 90.0],
        [KERNEL, 800.0 + OFF, 90.0],
        # the next commit's kernel, 500 ms on: no tile of this one
        [KERNEL, 800.0 + OFF + 500e6, 90.0]]}],
}


def obs(spans, trace=None):
    return Obs(cell=BENCH.cell(CELL), spans=spans, setup_spans=[],
               metrics={}, samples={}, compiles_in_window=0, laps={},
               device_kind="TPU v5 lite", trace=trace,
               trace_spans=spans)


def read(metric, o):
    return BENCH.reader(metric).read(o)


def stripped(spans):
    return [{k: v for k, v in e.items()
             if k not in ("id", "parent", "tid")} for e in spans]


def test_a_tiled_batch_is_one_with_a_warm_pipelined_tile():
    ((bv, preps, tiles),) = tiled.batches(SPANS)
    assert bv["id"] == 3
    assert [e["id"] for e in preps] == [5, 7, 9]
    assert [e["attrs"]["tile"] for e in tiles] == [0, 1, 2]
    # a cold tile (its shape compiles inside it) is no reading
    cold = [dict(e, attrs=dict(e["attrs"], warm=False))
            if e["name"] == "kernel_execute" else e for e in SPANS]
    assert tiled.batches(cold) == []
    assert tiled.batches(stripped(SPANS)) == []


def test_readers_on_the_hand_made_commit():
    o = obs(SPANS, TRACE)
    assert read("tiles_per_commit", o) == 3
    # median of 400/1000 and 100/400
    assert read("walk_share", o) == pytest.approx(32.5)
    # 560 - host_prep of tile 0 (40) - the tiles' union [505, 950)
    assert read("seam_outside_tiles_ms", o) == pytest.approx(75e-6)
    # gaps of 10 ns between the tiles' kernels; in the first of them
    # a copy and another module ran for [690, 698): 2 ns idle
    assert read("tile_device_gap_us", o) == pytest.approx(
        (2e-3 + 10e-3) / 2)


def test_the_device_gap_needs_no_shared_clock():
    o = obs(SPANS, TRACE)
    skewed = obs(SPANS, dict(TRACE, anchors=[
        [m + OFF, prof] for m, prof in TRACE["anchors"]]))
    assert read("tile_device_gap_us", skewed) == \
        read("tile_device_gap_us", o)
    # one kernel a commit: no pair lies closer than 100 ms
    lone = dict(TRACE, devices=[{"name": "d", "ops": [], "modules": [
        [KERNEL, 600.0 + OFF, 90.0],
        [KERNEL, 600.0 + OFF + 500e6, 90.0]]}])
    assert read("tile_device_gap_us", obs(SPANS, lone)) is None


def test_the_new_metrics_are_registered_for_the_tiled_cell_only():
    entries = {m["name"]: m for m in BENCH.manifest["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "verify_p50_ms"
        assert entries[name]["source"] == (
            "device_trace" if name == "tile_device_gap_us"
            else "program_span")
    verify = {m["name"] for m in BENCH.metrics("per_layer",
                                                "qa-175.verify")}
    mine = {m["name"] for m in BENCH.metrics("per_layer", CELL)}
    assert mine - verify == set(NEW) and verify <= mine
    assert [m["name"] for m in BENCH.metrics("end_to_end", CELL)] == \
        ["verify_p50_ms", "setup_s"]


@pytest.mark.parametrize("metric", NEW)
def test_reader_on_the_recorded_commits(metric):
    value = read(metric, obs(REC["spans"], REC["trace"]))
    assert value is not None and value >= 0
    old = obs(stripped(REC["spans"]), REC["trace"])
    if metric == "tile_device_gap_us":
        assert read(metric, old) == value       # the trace alone
        assert read(metric, obs(REC["spans"])) is None
    else:
        assert read(metric, old) is None
    assert read(metric, obs([])) is None


def test_the_recorded_commits_are_two_tiles_after_a_serial_walk():
    o = obs(REC["spans"], REC["trace"])
    found = tiled.batches(o.spans)
    assert len(found) == REC["commits"]
    assert read("tiles_per_commit", o) == 2
    for bv, preps, tiles in found:
        assert bv["attrs"]["batch"] == 6667
        assert [t["attrs"]["batch"] for t in tiles] == [3334, 3333]
        assert {t["attrs"]["bucket"] for t in tiles} == {4096}
        # the walk has ended before the seam begins: the device waits
        (walk,) = [e for e in o.spans if e["name"] == "commit_walk"
                   and e["parent"] == bv["parent"]]
        assert walk["ts_ns"] + walk["dur_ns"] <= bv["ts_ns"]
        # the seam's own pieces lie outside every tile
        names = [e["name"] for e in o.spans
                 if e["parent"] == bv["id"]]
        assert names[0] == "item_handover"
        assert names[-2:] == ["mask_handback", "item_release"]
    assert 40 < read("walk_share", o) < 60
    assert 1.0 < read("seam_outside_tiles_ms", o) < 8.0
    for bv, _, _ in found:
        # what a batch spends outside its tiles is all but named
        mine = [bv] + [e for e in o.spans if e["parent"] == bv["id"]]
        outside = read("seam_outside_tiles_ms", obs(mine))
        named = sum(e["dur_ns"] for e in mine if e["name"] in (
            "item_handover", "mask_handback", "item_release")) / 1e6
        assert 0.8 * outside < named <= outside
    # tile 1 is queued behind tile 0: the device does not wait
    assert read("tile_device_gap_us", o) < 100
