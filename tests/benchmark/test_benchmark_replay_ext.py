"""The cell vote-ext-60.replay: the four readers of a playback with vote
extensions and lib/replayext under them, on a hand-made span list and
counter delta; what the cell reports in BENCHMARK.json, appended behind
wal-150.replay; traffic/replay_ext.py end to end at the cell's
rehearsal size in this process, held to its plain reference (every
forged kind among the heights); a checkout without the oracle app
failing at once; the fabricator's forked writers.  A program that records none of what the
readers read gives each of them nothing to read."""
import asyncio
import json
import os
import sys

import pytest

from _bench_util import ROOT
from benchmark import run as bench_run
from benchmark.lib import loader, replayext
from benchmark.lib.compiles import CompileLog
from benchmark.lib.session import Ctx, Obs
from benchmark.reference import wal_ext as fab

BENCH = loader.Bench(ROOT)
CELL = "vote-ext-60.replay"
NEW = ["replay_ext_lanes", "replay_ext_verify_ms",
       "replay_process_proposal_ms", "replay_ext_serial_share"]
EXT = "cometbft_consensus_vote_extension_verify_total"
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_wal150.json")) as f:
    WAL150 = json.load(f)      # a playback with no extensions
with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "spans_light1k.json")) as f:
    OTHER = json.load(f)       # a program's spans with no playback


def ev(i, parent, name, start, end, height=5, **attrs):
    out = {"ts_ns": start, "dur_ns": end - start, "category": "x",
           "name": name, "height": height, "id": i, "parent": parent,
           "tid": 1}
    if attrs:
        out["attrs"] = attrs
    return out


# two heights that commit (three and two precommits' extension blocks,
# one refused) and one the WAL's end cuts short
SPANS = [
    ev(1, 0, "wal_replay", 0, 3000, **{"from": 5, "to": 6}),
    ev(2, 1, "replay_height", 0, 1000, outcome="committed"),
    ev(3, 2, "vote_preverify", 10, 200, entries=36, late=9, ext=24,
       fresh=30),
    ev(4, 2, "vote_tally", 210, 990, votes=24, memo_hits=24,
       serial_verifies=0),
    ev(5, 4, "consensus/process_proposal", 220, 300),
    ev(6, 5, "oracle_validate", 225, 295, votes=12),
    ev(7, 4, "vote_extension", 400, 410, bytes=1790, status="accepted"),
    ev(8, 4, "vote_extension", 420, 440, bytes=1790, status="accepted"),
    ev(9, 4, "vote_extension", 450, 453, bytes=1790, status="invalid"),
    ev(10, 1, "replay_height", 1000, 2000, 6, outcome="committed"),
    ev(11, 10, "vote_preverify", 1010, 1200, 6, entries=38, late=9,
       ext=26, fresh=32),
    ev(12, 10, "vote_tally", 1210, 1990, 6, votes=25, memo_hits=25,
       serial_verifies=1),
    ev(13, 12, "consensus/process_proposal", 1220, 1340, 6),
    ev(14, 12, "vote_extension", 1400, 1430, 6, bytes=1790,
       status="accepted"),
    ev(15, 12, "vote_extension", 1440, 1460, 6, bytes=1790,
       status="rejected"),
    ev(20, 1, "replay_height", 2000, 3000, 7, outcome="stalled"),
    ev(21, 20, "vote_preverify", 2010, 2100, 7, entries=12, late=9,
       ext=8, fresh=12),
    ev(22, 20, "vote_tally", 2110, 2990, 7, votes=10, memo_hits=10,
       serial_verifies=0),
    ev(23, 22, "consensus/process_proposal", 2120, 2900, 7),
    ev(24, 22, "vote_extension", 2950, 2990, 7, bytes=1790,
       status="accepted"),
]
DELTA = {(EXT, (("path", "memo"),)): 399.0,
         (EXT, (("path", "serial"),)): 1.0}


def obs(spans, metrics=None):
    return Obs(cell=BENCH.cell(CELL), spans=spans, setup_spans=[],
               metrics=metrics or {}, samples={}, compiles_in_window=0,
               laps={}, device_kind="TPU v5 lite", trace=None,
               trace_spans=spans)


def read(metric, spans, metrics=None):
    return BENCH.reader(metric).read(obs(spans, metrics))


@pytest.mark.parametrize("metric,want", [
    ("replay_ext_lanes", 24),                       # 24, 26, 8
    # per committed height: 10 + 20 + 3 and 30 + 20 ns
    ("replay_ext_verify_ms", (33 + 50) / 2 * 1e-6),
    ("replay_process_proposal_ms", (80 + 120) / 2 * 1e-6),
])
def test_readers_on_the_hand_made_playback(metric, want):
    assert read(metric, SPANS) == pytest.approx(want)


def test_the_serial_share_reads_the_counter_family():
    assert read("replay_ext_serial_share", SPANS, DELTA) == \
        pytest.approx(100 * 1 / 400)
    # and no other family: the vote signatures' counter is not it
    votes = {("cometbft_consensus_vote_verify_total",
              (("path", "serial"),)): 5.0}
    assert read("replay_ext_serial_share", SPANS, votes) is None
    assert replayext.serial_share({**DELTA, **votes}) == \
        pytest.approx(0.25)


def test_only_what_lies_below_a_committed_height_counts():
    stray = SPANS + [ev(90, 0, "vote_extension", 5000, 9000,
                        bytes=1790, status="accepted"),
                     ev(91, 0, "consensus/process_proposal", 5000, 9000)]
    for metric in ("replay_ext_verify_ms", "replay_process_proposal_ms"):
        assert read(metric, stray) == read(metric, SPANS)
    # a committed height holding none counts as 0 in the median
    one = [e for e in SPANS if e["id"] not in (14, 15)]
    assert read("replay_ext_verify_ms", one) == pytest.approx(
        (33 + 0) / 2 * 1e-6)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_them_gives_nothing_to_read(metric):
    """A playback without extensions (wal-150's recorded heights; its
    kvstore's ProcessProposal is still an ABCI span) and a program
    with no playback at all."""
    assert read(metric, OTHER["spans"]) is None
    assert read(metric, []) is None
    if metric != "replay_process_proposal_ms":
        assert read(metric, WAL150["spans"]) is None


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def pins(manifest: dict) -> None:
    """What the cell reports in ``manifest``, by name: appended behind
    wal-150.replay wherever that cell is listed, the four readers each
    an entry of its own with these fields, their cells BEGINNING with
    this one."""
    listed = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    assert [name for name in names if name in NEW] == NEW
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        cells = entry.get("workloads", [])
        if "wal-150.replay" in cells:
            assert CELL in cells, entry["name"]
            assert cells.index(CELL) > cells.index("wal-150.replay")
    assert [m["name"] for m in manifest["end_to_end"]
            if reports(m, CELL)][:2] == ["sync_heights_per_s", "setup_s"]
    for metric in NEW:
        entry = dict(listed[metric])
        assert entry.pop("unit") == {
            "replay_ext_lanes": "lanes",
            "replay_ext_serial_share": "%"}.get(metric, "ms")
        assert entry.pop("better") == (
            "higher" if metric == "replay_ext_lanes" else "lower")
        assert entry.pop("source") == (
            "program_counter" if metric == "replay_ext_serial_share"
            else "program_span")
        assert entry.pop("workloads")[:1] == [CELL]
        assert entry == {"name": metric,
                         "layer": "consensus state machine",
                         "moves": "sync_heights_per_s"}
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("vote-ext-60", "replay", 1)
    (config,) = [c for c in manifest["configs"]
                 if c["name"] == "vote-ext-60"]
    assert config["reduced"] == ["nodes", "wal_heights"]


def test_what_the_cell_reports():
    pins(BENCH.manifest)
    cell = BENCH.cell(CELL)
    assert cell.driver == "replay_ext"
    assert (cell.param("validators"), cell.param("forged_one_in"),
            cell.param("price_pairs"), cell.param("app")) == \
        (60, 16, 128, "oracle")
    for metric in NEW:
        assert callable(BENCH.reader(metric).read)


def test_a_traced_run_reads_the_new_readers_through_the_manifest():
    line = bench_run.per_layer_metrics(BENCH, CELL, obs(SPANS, DELTA))
    units = {m["name"]: m["unit"] for m in BENCH.manifest["per_layer"]}
    for metric in NEW:
        assert line[metric] == {"value": read(metric, SPANS, DELTA),
                                "unit": units[metric]}
    # the accepted replay readers find this cell's spans too
    assert line["replay_preverify_lanes"]["value"] == 36
    other = bench_run.per_layer_metrics(BENCH, CELL, obs(OTHER["spans"]))
    assert [m for m in NEW if m in other] == []


# -- traffic/replay_ext.py, end to end, at the rehearsal's size --------------------

def tiny_run(trace: bool, tmp_path, **overrides):
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.types import vote as vote_mod
    vote_mod._VERIFIED.clear()
    vote_mod._REJECTED.clear()
    cell = BENCH.cell(CELL)
    ctx = Ctx(BENCH, cell, seed=4, seconds=0.5, trace=trace,
              rehearsal=False, compiles=CompileLog(),
              t_start=bench_run.time.monotonic())
    ctx.work_dir = str(tmp_path)
    ctx.overrides.update(cell.params["rehearsal"] | {
        "wal_heights": 160, "tx_bytes": 64} | overrides)
    old = tracing.recorder()
    try:
        return asyncio.run(bench_run.run_cell(
            ctx, BENCH.traffic(cell.driver)))
    finally:
        tracing.set_recorder(old)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_plays_back_and_is_held_to_its_reference(trace,
                                                          tmp_path):
    # the traced run checks over worker processes, as the chip's does
    result, problems = tiny_run(trace, tmp_path,
                                reference_workers=2 if trace else 0)
    assert result["attempted"] > 5 and result["failed"] == 0
    # the CPU verifier did the work: never correct, and every
    # complaint is about the device, none about the replay
    assert result["correct"] is False
    assert problems and all(
        "device" in p or "CPU verifier" in p for p in problems)
    if trace:
        assert set(NEW) <= set(result["metrics"])
        # 12 validators: (3 late + 9 on time) x 2
        assert result["metrics"]["replay_ext_lanes"]["value"] == 24
        assert result["metrics"]["replay_preverify_lanes"]["value"] == \
            12 + 12 + 24
        assert result["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert set(result["metrics"]) == {"sync_heights_per_s",
                                          "setup_s"}
        assert result["metrics"]["sync_heights_per_s"]["value"] > 0
    assert os.listdir(tmp_path) == []


def test_the_rehearsal_forges_every_kind():
    cell = BENCH.cell(CELL)
    r = cell.params["rehearsal"]
    kinds = {fab.forged_kind(4, h, r["forged_one_in"])
             for h in range(1, r["wal_heights"] - r["wal_margin"])}
    assert kinds - {None} == set(fab.KINDS)


def test_a_checkout_without_the_oracle_app_fails_at_once(tmp_path,
                                                        monkeypatch):
    """What the parent commit does with the cell: exit 3 from set-up,
    before anything is fabricated."""
    monkeypatch.setitem(sys.modules, "cometbft_tpu.abci.oracle", None)
    with pytest.raises(SystemExit) as caught:
        tiny_run(False, tmp_path)
    assert caught.value.code == bench_run.EXIT_NO_PROGRAM
    assert os.listdir(tmp_path) == []


def test_the_forked_writers_write_the_one_writers_wal(tmp_path):
    from cometbft_tpu.consensus.wal import WAL
    made = {}
    for workers in (1, 3):
        d = tmp_path / str(workers)
        d.mkdir()
        made[workers] = asyncio.run(fab.fabricate(
            str(d / "wal"), "w", 11, 8, 10, 30, 64, 3, 16,
            workers=workers))
    one, three = made[1], made[3]
    assert list(WAL.iter_group(one.wal_path, strict=True)) == \
        list(WAL.iter_group(three.wal_path, strict=True))
    assert (one.forged, one.block_hash, one.app_hash) == \
        (three.forged, three.block_hash, three.app_hash)
