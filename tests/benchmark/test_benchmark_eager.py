"""The readers of the streamed seam (PR 28: after_walk_ms,
eager_tiles_per_commit): on hand-made span lists of a commit whose
verifier waits for verify() and of one whose first tile is fed from
BatchVerifier.add, and on three commits of valset-10k.verify recorded
on the chip with the streamed seam
(benchmark/fixtures/spans_valset10k_eager.json), beside PR 27's
recording of the serial one.  A program that records no ids and
parents gives both readers nothing to read."""
import json
import os

import pytest

from _bench_util import ROOT
from benchmark.lib import tiled
# the same hand-made events and Obs as PR 27's readers are tested on
from test_benchmark_tiled import (
    BENCH, REC as SERIAL, ev, obs, read, stripped, tile,
)

CELLS = ["qa-175.verify", "valset-10k.verify", "light-1k.skip"]
NEW = ["after_walk_ms", "eager_tiles_per_commit"]
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_valset10k_eager.json")) as f:
    EAGER = json.load(f)


# the walk, then the seam with two tiles: the program before PR 28
WAITS = [
    ev(1, 0, "commit_verify", 0, 1000),
    ev(2, 1, "commit_walk", 10, 410),
    ev(3, 1, "batch_verify", 420, 980, batch=90, backend="tpu"),
    ev(4, 3, "item_handover", 420, 450),
    ev(5, 3, "host_prep", 460, 500, pipelined=True),
    tile(6, 3, 505, 700, 0),
    ev(7, 3, "host_prep", 520, 560, pipelined=True),
    tile(8, 3, 565, 950, 1),
    ev(9, 3, "mask_handback", 955, 960),
    ev(10, 3, "item_release", 962, 978),
]
# tile 0 fed from add() inside the walk, settled when tile 1 is out
STREAMS = [
    ev(21, 0, "commit_verify", 2000, 2800),
    ev(22, 21, "commit_walk", 2010, 2480),
    ev(23, 21, "batch_verify", 2250, 2790, batch=90, backend="tpu"),
    ev(24, 23, "item_handover", 2250, 2270),
    ev(25, 23, "host_prep", 2272, 2310, pipelined=True),
    tile(26, 23, 2312, 2560, 0, eager=True),
    ev(27, 23, "item_release", 2320, 2330),
    ev(28, 23, "item_handover", 2490, 2500),
    ev(29, 23, "host_prep", 2502, 2530, pipelined=True),
    tile(30, 23, 2532, 2780, 1),
    ev(31, 23, "item_release", 2565, 2572),
    ev(32, 23, "mask_handback", 2782, 2788),
]
# a commit below one tile, and a batch the CPU verifier judged
SMALL = [
    ev(41, 0, "commit_verify", 4000, 4400),
    ev(42, 41, "commit_walk", 4010, 4110),
    ev(43, 41, "batch_verify", 4120, 4390, batch=6, backend="tpu"),
    ev(44, 43, "host_prep", 4130, 4150),
    ev(45, 43, "kernel_execute", 4160, 4380, warm=True),
]
ON_CPU = [ev(51, 0, "batch_verify", 5000, 5100, batch=6,
             backend="cpu", fallback=True)]


@pytest.mark.parametrize("spans,after,eager", [
    (WAITS, 590e-6, 0), (STREAMS, 320e-6, 1), (SMALL, 290e-6, 0),
    (WAITS + STREAMS + SMALL, 320e-6, 0), (STREAMS + ON_CPU, 320e-6, 1),
], ids=["waits", "streams", "small", "all", "cpu_batch_has_no_say"])
def test_readers_on_hand_made_commits(spans, after, eager):
    # commit_verify's end less its walk's end
    assert read("after_walk_ms", obs(spans)) == pytest.approx(after)
    assert read("eager_tiles_per_commit", obs(spans)) == eager


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read_is_none(metric):
    assert read(metric, obs([])) is None
    assert read(metric, obs(ON_CPU)) is None
    assert read(metric, obs(stripped(WAITS + STREAMS))) is None
    # a walk whose commit_verify the ring has dropped is no reading
    assert read("after_walk_ms", obs(STREAMS[1:])) is None


def test_the_new_metrics_are_registered_for_the_verify_p50_cells():
    """Every cell that reports verify_p50_ms reports both:
    eager_tiles_per_commit reads 0 below one tile, where the mechanism
    is bypassed (and test_benchmark_tiled pins the metrics only the
    tiled cell has).  Each is listed once, wherever in the list, and a
    later PR may append entries behind them and cells behind these."""
    names = [m["name"] for m in BENCH.manifest["per_layer"]]
    entries = {m["name"]: m for m in BENCH.manifest["per_layer"]}
    (e2e,) = [m for m in BENCH.manifest["end_to_end"]
              if m["name"] == "verify_p50_ms"]
    assert e2e["workloads"][:len(CELLS)] == CELLS
    for name, layer in zip(NEW, ("commit verification", "crypto seam")):
        assert names.count(name) == 1
        assert entries[name]["workloads"][:len(CELLS)] == CELLS
        assert entries[name]["moves"] == "verify_p50_ms"
        assert entries[name]["source"] == "program_span"
        assert entries[name]["layer"] == layer
    assert entries["after_walk_ms"]["better"] == "lower"
    assert entries["eager_tiles_per_commit"]["better"] == "higher"


def test_the_serial_recording_reads_as_the_parent_does():
    """PR 27's program: nothing is fed before the walk ends, and a
    request waits 34-35 ms after it."""
    o = obs(SERIAL["spans"], trace=SERIAL["trace"])
    assert read("eager_tiles_per_commit", o) == 0
    assert 30 < read("after_walk_ms", o) < 40


def test_the_streamed_recording_is_one_eager_tile_inside_the_walk():
    o = obs(EAGER["spans"], trace=EAGER["trace"])
    found = tiled.batches(o.spans)
    assert len(found) == EAGER["commits"] == 3
    assert read("eager_tiles_per_commit", o) == 1
    assert read("tiles_per_commit", o) == 2
    ids = {e["id"]: e for e in o.spans if e.get("id")}
    for bv, preps, tiles in found:
        assert bv["attrs"] == {"backend": "tpu", "batch": 6667}
        assert [t["attrs"]["batch"] for t in tiles] == [4096, 2571]
        assert {t["attrs"]["bucket"] for t in tiles} == {4096}
        assert [t["attrs"].get("eager") for t in tiles] == [True, None]
        # the seam is a child of the request, opened inside its walk
        request = ids[bv["parent"]]
        assert request["name"] == "commit_verify"
        (walk,) = [e for e in o.spans if e["name"] == "commit_walk"
                   and e.get("parent") == request["id"]]
        walk_end = walk["ts_ns"] + walk["dur_ns"]
        assert walk["ts_ns"] < bv["ts_ns"] < walk_end
        # tile 0 is launched inside the walk and settled after it,
        # when tile 1 is out; tile 1 begins after the walk
        assert tiles[0]["ts_ns"] < walk_end < \
            tiles[0]["ts_ns"] + tiles[0]["dur_ns"]
        assert tiles[1]["ts_ns"] > walk_end
        names = [e["name"] for e in sorted(
            (e for e in o.spans if e.get("parent") == bv["id"]),
            key=lambda e: e["ts_ns"])]
        assert names == ["item_handover", "host_prep",
                         "kernel_execute", "item_release"] * 2 + \
            ["mask_handback"]
    # what the request waits for after its walk fell by about what
    # the serial recording spends on tile 0
    after = read("after_walk_ms", o)
    before = read("after_walk_ms", obs(SERIAL["spans"]))
    assert 12 < after < 26 and 8 < before - after < 20
    # the seam's span now overlaps the walk: longer, not slower
    assert read("seam_ms", o) > read("seam_ms", obs(SERIAL["spans"]))
    assert 55 < read("walk_share", o) < 80
    # the device waits for the walk between the two kernels
    assert read("tile_device_gap_us", o) > 1000
