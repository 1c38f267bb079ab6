"""The cell wal-150.replay (PR 34): the seven readers of a WAL
playback's spans and lib/replayspans under them, on a hand-made span
list and on four heights recorded on the chip
(benchmark/fixtures/spans_wal150.json), which pin the cell's traffic
(300 signatures a pre-verification, no serial verification of an honest
vote) and hold the recorded height to one tree down to the device; the
accepted readers the cell was appended to; the driver end to end at a
tiny size in this process; the fabricator's forked writers.  A program
that records none of these spans gives every reader nothing to read."""
import asyncio
import json
import os

import pytest

from _bench_util import ROOT
from benchmark import run as bench_run
from benchmark.lib import loader, replayspans
from benchmark.lib.compiles import CompileLog
from benchmark.lib.session import Ctx, Obs
from benchmark.reference import wal as walfab

BENCH = loader.Bench(ROOT)
CELL = "wal-150.replay"
NEW = ["replay_wal_read_ms", "replay_preverify_ms", "replay_tally_ms",
       "replay_finalize_ms", "replay_unattributed_share",
       "replay_preverify_lanes", "replay_serial_verify_share"]
LAYER = dict.fromkeys(NEW, "consensus state machine") | {
    "replay_wal_read_ms": "wal"}
# the accepted metrics the cell is appended to; the first five read
# spans alone
ACCEPTED = ["sync_seam_ms", "sync_fallback_share", "sync_host_prep_ms",
            "sync_kernel_execute_ms", "sync_pad_share",
            "sync_commit_verify_ms", "sync_kernel_device_us_per_lane",
            "sync_device_idle_share"]
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_wal150.json")) as f:
    REC = json.load(f)
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_light1k.json")) as f:
    OTHER = json.load(f)     # a program's spans with no playback


def ev(i, parent, name, start, end, height=5, **attrs):
    out = {"ts_ns": start, "dur_ns": end - start, "category": "x",
           "name": name, "height": height, "id": i, "parent": parent,
           "tid": 1}
    if attrs:
        out["attrs"] = attrs
    return out


# two heights that commit (the second holds a forged vote) and one the
# WAL's end cuts short
SPANS = [
    ev(1, 0, "wal_replay", 0, 2500, **{"from": 5, "to": 6}),
    ev(2, 1, "replay_height", 0, 1000, outcome="committed"),
    ev(3, 2, "wal_read", 0, 100, records=30, bytes=9000),
    ev(4, 2, "vote_preverify", 110, 310, entries=24, late=4, fresh=24),
    ev(5, 4, "batch_verify", 150, 300, batch=24, backend="tpu"),
    ev(6, 2, "vote_tally", 320, 980, votes=24, memo_hits=24,
       serial_verifies=0),
    ev(7, 6, "validate_block", 330, 400),
    ev(8, 6, "finalize_commit", 700, 970),
    ev(9, 8, "validate_block", 700, 710),
    ev(10, 1, "replay_height", 1000, 2200, 6, outcome="committed"),
    ev(11, 10, "wal_read", 1000, 1120, 6, records=31, bytes=9300),
    ev(12, 10, "vote_preverify", 1130, 1400, 6, entries=25, late=4,
       fresh=25),
    ev(13, 10, "vote_tally", 1410, 2190, 6, votes=25, memo_hits=25,
       serial_verifies=1),
    ev(14, 13, "finalize_commit", 1800, 2180, 6),
    ev(20, 1, "replay_height", 2200, 2500, 7, outcome="stalled"),
    ev(21, 20, "wal_read", 2200, 2260, 7, records=12, bytes=3600),
    ev(22, 20, "vote_preverify", 2270, 2400, 7, entries=10, late=4,
       fresh=10),
    ev(23, 20, "vote_tally", 2410, 2490, 7, votes=10, memo_hits=10,
       serial_verifies=0),
]


def obs(spans, **kw):
    return Obs(cell=BENCH.cell(CELL), spans=spans, setup_spans=[],
               metrics={}, samples={}, compiles_in_window=0, laps={},
               device_kind="TPU v5 lite", trace=None, trace_spans=spans,
               **kw)


def read(metric, spans):
    return BENCH.reader(metric).read(obs(spans))


@pytest.mark.parametrize("metric,want", [
    # over the two heights that committed; the stalled one counts for
    # nothing but the traffic's guard and the share of serial verifies
    ("replay_wal_read_ms", (100 + 120) / 2 * 1e-6),
    ("replay_preverify_ms", (200 + 270) / 2 * 1e-6),
    ("replay_tally_ms", ((660 - 270) + (780 - 380)) / 2 * 1e-6),
    ("replay_finalize_ms", (270 + 380) / 2 * 1e-6),
    # [100,110) [310,320) [980,1000), [1120,1130) [1400,1410) [2190,2200)
    ("replay_unattributed_share", 100 * 70 / 2200),
    ("replay_preverify_lanes", 24),         # 24, 25, 10
    ("replay_serial_verify_share", 100 * 1 / 59),
])
def test_readers_on_the_hand_made_playback(metric, want):
    assert read(metric, SPANS) == pytest.approx(want)


def test_only_what_lies_below_a_committed_height_counts():
    assert sorted(replayspans.committed(SPANS)) == [2, 10]
    assert [e["id"] for e in replayspans.below(SPANS, "wal_read")] == \
        [3, 11]
    assert [e["id"] for e in
            replayspans.below(SPANS, "validate_block")] == [7, 9]
    # a burst of the live path: above no replayed height
    stray = SPANS + [ev(90, 0, "vote_tally", 5000, 5100, votes=3,
                        memo_hits=3, serial_verifies=3)]
    assert read("replay_tally_ms", stray) == \
        read("replay_tally_ms", SPANS)
    assert read("replay_serial_verify_share", stray) == \
        pytest.approx(100 * 4 / 62)
    assert replayspans.per_height_ms(SPANS, "no_such_span") is None


@pytest.mark.parametrize("metric,want", [
    ("replay_preverify_lanes", 300),
    ("replay_serial_verify_share", 0),
])
def test_the_recorded_heights_pin_the_cells_traffic(metric, want):
    assert REC["heights"] == 4
    assert read(metric, REC["spans"]) == want


@pytest.mark.parametrize("metric,lo,hi", [
    ("replay_wal_read_ms", 3, 20),
    ("replay_preverify_ms", 4, 25),
    ("replay_tally_ms", 8, 40),
    ("replay_finalize_ms", 1.5, 12),
    ("replay_unattributed_share", 0, 3),
])
def test_readers_on_the_recorded_heights(metric, lo, hi):
    assert lo <= read(metric, REC["spans"]) <= hi


def test_a_recorded_height_is_one_tree_down_to_the_device():
    spans = REC["spans"]
    ids = {e["id"]: e for e in spans if e.get("id")}

    def chain_of(e):
        out = []
        while e is not None:
            out.append(e["name"])
            e = ids.get(e["parent"])
        return tuple(out[::-1])

    chains = {chain_of(e) for e in spans}
    top = ("wal_replay", "replay_height") \
        if any(e["name"] == "wal_replay" for e in spans) \
        else ("replay_height",)
    seam = ("batch_verify", "kernel_execute")
    for leaf in ("h2d", "launch", "device_wait", "d2h"):
        # the votes' batch under the barrier, on the worker's thread
        assert top + ("vote_preverify",) + seam + (leaf,) in chains
        # the proposal's LastCommit, as the block completes
        assert top + ("vote_tally", "validate_block", "commit_verify") \
            + seam + (leaf,) in chains
    for inner in ("validate_block", "store_save_block", "apply_block"):
        assert top + ("vote_tally", "finalize_commit", inner) in chains
    assert top + ("wal_read",) in chains
    heights = replayspans.committed(spans)
    assert len(heights) == REC["heights"]
    batches = sorted(
        e["attrs"]["batch"] for e in spans if e["name"] == "batch_verify"
        and any(a["id"] in heights for a in _ancestors(e, ids)))
    assert batches == [150] * 4 + [300] * 4
    assert {e["attrs"]["bucket"] for e in spans
            if e["name"] == "kernel_execute"} == {256, 512}


def _ancestors(e, ids):
    out = []
    e = ids.get(e["parent"])
    while e is not None:
        out.append(e)
        e = ids.get(e["parent"])
    return out


@pytest.mark.parametrize("metric", ACCEPTED[:5])
def test_accepted_readers_read_the_recorded_heights(metric):
    """The accepted readers, unedited, find something to read in the
    cell's spans."""
    value = read(metric, REC["spans"])
    assert value is not None
    if metric == "sync_pad_share":     # 300 at 512 and 150 at 256
        assert value == 100 * (1 - (300 + 150) / (512 + 256))
    if metric == "sync_fallback_share":
        assert value == 0


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_gives_nothing_to_read(metric):
    assert read(metric, OTHER["spans"]) is None
    assert read(metric, []) is None


def test_what_the_cell_reports():
    """sync_heights_per_s and setup_s end to end; the accepted metrics
    it was appended to; the seven readers, each an entry of its own at
    the END of per_layer with these fields."""
    assert [m["name"] for m in BENCH.metrics("end_to_end", CELL)] == [
        "sync_heights_per_s", "setup_s"]
    listed = {m["name"]: m for m in BENCH.manifest["per_layer"]}
    reported = [m["name"] for m in BENCH.metrics("per_layer", CELL)]
    for metric in ACCEPTED:
        assert listed[metric]["workloads"][-1] == CELL
        assert metric in reported
    assert {"shape_setup_s", "compiles_in_window"} <= set(reported)
    assert [m["name"] for m in BENCH.manifest["per_layer"][-7:]] == NEW
    for metric in NEW:
        assert callable(BENCH.reader(metric).read)
        entry = dict(listed[metric])
        assert entry.pop("unit") == (
            "ms" if metric.endswith("_ms") else
            "%" if metric.endswith("_share") else "lanes")
        assert entry.pop("better") == (
            "higher" if metric == "replay_preverify_lanes" else "lower")
        assert entry == {
            "name": metric, "source": "program_span",
            "layer": LAYER[metric], "moves": "sync_heights_per_s",
            "workloads": [CELL]}
    cell = BENCH.cell(CELL)
    assert (cell.config_name, cell.traffic, cell.chips) == \
        ("wal-150", "replay", 1)
    assert cell.param("validators") == 150
    assert cell.param("forged_one_in") == 16


def test_a_traced_run_reads_every_replay_reader_through_the_manifest():
    line = bench_run.per_layer_metrics(BENCH, CELL, obs(REC["spans"]))
    assert [m for m in NEW + ACCEPTED[:5] if m not in line] == []
    units = {m["name"]: m["unit"] for m in BENCH.manifest["per_layer"]}
    for metric in NEW:
        assert line[metric] == {"value": read(metric, REC["spans"]),
                                "unit": units[metric]}
    other = bench_run.per_layer_metrics(BENCH, CELL, obs(OTHER["spans"]))
    assert [m for m in NEW if m in other] == []
    assert "sync_seam_ms" in other


# -- the driver, end to end, at a tiny size -----------------------------------

TINY = {"validators": 8, "wal_heights": 700, "wal_margin": 4,
        "prefix_heights": 24, "prewarm_ops": 4, "warmup_ops": 8,
        "quiet_ops": 4, "forged_one_in": 4, "tx_bytes": 64,
        "kv_check_keys": 4, "fabricator_workers": 2,
        "reference_workers": 0}


def tiny_run(trace: bool, tmp_path, **overrides):
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types import vote as vote_mod
    # a fresh process's memos: an earlier run of the same seed has
    # judged the same votes
    vote_mod._VERIFIED.clear()
    vote_mod._REJECTED.clear()
    cell = BENCH.cell(CELL)
    ctx = Ctx(BENCH, cell, seed=7, seconds=0.4, trace=trace,
              rehearsal=False, compiles=CompileLog(),
              t_start=bench_run.time.monotonic())
    ctx.work_dir = str(tmp_path)
    ctx.overrides.update(TINY | overrides)
    old = tracing.recorder()
    try:
        return asyncio.run(bench_run.run_cell(
            ctx, BENCH.traffic(cell.driver)))
    finally:
        tracing.set_recorder(old)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_plays_back_and_is_held_to_its_reference(trace,
                                                          tmp_path):
    result, problems = tiny_run(trace, tmp_path)
    assert set(result) - {"breakdown"} == {
        "correct", "attempted", "failed", "metrics", "device"}
    assert result["attempted"] > 20 and result["failed"] == 0
    # the CPU verifier did the work: never correct under any name,
    # and every complaint is about the device, none about the replay
    assert result["correct"] is False
    assert problems and all(
        "device" in p or "CPU verifier" in p for p in problems)
    if trace:
        assert set(NEW) <= set(result["metrics"])
        assert result["metrics"]["replay_preverify_lanes"]["value"] == \
            2 + 8 + 6
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert "sync_heights_per_s" not in result["metrics"]
    else:
        assert set(result["metrics"]) == {"sync_heights_per_s",
                                          "setup_s"}
        assert result["metrics"]["sync_heights_per_s"]["value"] > 50
    assert os.listdir(tmp_path) == []     # the WAL is gone again
    json.dumps(result)


def test_a_wal_the_playback_outruns_fails_the_run(tmp_path):
    with pytest.raises(RuntimeError, match=r"the WAL ran out: playback "
                       r"at height \d+ of 60 \(margin 4\); a 0.4 s "
                       r"window opened at height \d+ holds at most "
                       r"[\d.]+ heights/s; lengthen wal_heights"):
        tiny_run(False, tmp_path, wal_heights=60)


def test_the_forked_writers_write_the_one_writers_wal(tmp_path):
    from cometbft_tpu.consensus.wal import WAL
    made = {}
    for workers in (1, 3):
        d = tmp_path / str(workers)
        d.mkdir()
        made[workers] = asyncio.run(walfab.fabricate(
            str(d / "wal"), "w", 11, 8, 10, 40, 64, 4,
            workers=workers))
    one, three = made[1], made[3]
    assert list(WAL.iter_group(one.wal_path, strict=True)) == \
        list(WAL.iter_group(three.wal_path, strict=True))
    assert (one.forged, one.block_hash, one.app_hash) == \
        (three.forged, three.block_hash, three.app_hash)
    # the writers' own groups are gone, their files renamed into one
    assert sorted(os.listdir(tmp_path / "3")) == [
        "wal", "wal.000", "wal.001"]
