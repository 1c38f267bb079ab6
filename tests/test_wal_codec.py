"""The WAL's record payload (consensus/wal.py): a tag byte, then the
proto of a message or the integers of a ``timeout`` / ``end_height``
marker; canonical JSON for anything else.  Every kind the fabricators
and the live state machine write comes back equal through the dict form
(``WAL.iter_group``) and through the playback's read-ahead
(``replay._read_ahead`` over ``replay._frames``); a WAL written in JSON
throughout still plays back; the frame rules (torn tail, CRC, size
limit, repair) hold over both payloads; the counter and the benchmark's
reader of it."""
import json
import os
import random
from dataclasses import replace
from types import SimpleNamespace

import pytest

from benchmark.layers import replay_wal_binary_share
from benchmark.lib import probes
from cometbft_tpu.consensus import replay as replay_mod
from cometbft_tpu.consensus import wal as walmod
from cometbft_tpu.consensus.messages import (
    AggregateCommitMessage, BlockPartMessage, ProposalMessage,
    VoteMessage, dejsonify, jsonify, message_from_wal,
)
from cometbft_tpu.consensus.round_state import (
    STEP_PRECOMMIT_WAIT, TimeoutInfo,
)
from cometbft_tpu.consensus.wal import (
    MAX_MSG_SIZE_BYTES, WAL, CorruptWALError, WALError,
    _scan_valid_prefix, repair_wal_file,
)
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.bits import BitArray
from cometbft_tpu.libs.tracing import Recorder
from cometbft_tpu.types import canonical
from cometbft_tpu.types.block_id import BlockID
from cometbft_tpu.types.commit import AggregateCommit
from cometbft_tpu.types.part_set import Part, PartSet, PartSetHeader
from cometbft_tpu.types.proposal import Proposal
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.vote import Vote
from tests.test_wal_playback_reference import (
    fabricate, forget_votes, fresh_node, play,
)

_RNG = random.Random(44)


def _b(n: int) -> bytes:
    return _RNG.randbytes(n)


BID = BlockID(_b(32), PartSetHeader(2, _b(32)))
TS = Timestamp(1_700_000_000, 123_456_789)
PREVOTE = Vote(type=canonical.PREVOTE_TYPE, height=7, round=1,
               block_id=BID, timestamp=TS, validator_address=_b(20),
               validator_index=41, signature=_b(64))
BIG = PartSet.from_data(_b(2 * 65536 + 100))

# kind -> (the message written, or None for a plain record dict)
MESSAGES = {
    "prevote": VoteMessage(PREVOTE),
    "precommit_with_extensions": VoteMessage(replace(
        PREVOTE, type=canonical.PRECOMMIT_TYPE, validator_index=0,
        extension=_b(1800), extension_signature=_b(64),
        non_rp_extension=_b(24), non_rp_extension_signature=_b(64))),
    "nil_vote": VoteMessage(replace(
        PREVOTE, type=canonical.PRECOMMIT_TYPE, block_id=BlockID())),
    "proposal_pol_minus_one": ProposalMessage(Proposal(
        height=7, round=0, pol_round=-1, block_id=BID, timestamp=TS,
        signature=_b(64))),
    "part_1kb": BlockPartMessage(
        7, 0, PartSet.from_data(_b(1024)).get_part(0)),
    "part_65kb": BlockPartMessage(7, 2, BIG.get_part(1)),
    "aggregate_commit": AggregateCommitMessage(AggregateCommit(
        height=7, round=0, block_id=BID,
        signers=BitArray.from_indices(150, [0, 3, 149]),
        signature=_b(96))),
}
PLAIN = {
    "timeout": {"type": "timeout", "height": 7, "round": 1,
                "step": STEP_PRECOMMIT_WAIT},
    "end_height": {"type": "end_height", "height": 7},
    "round_state": {"type": "round_state", "height": 7, "round": 1,
                    "step": "RoundStepPrevote"},
    "arbitrary": {"type": "vote", "height": 3, "i": 1, "pad": "x" * 64},
}
JSON_KINDS = {"round_state", "arbitrary"}
BODY = {VoteMessage: ("vote", Vote.from_proto),
        ProposalMessage: ("proposal", Proposal.from_proto),
        BlockPartMessage: ("part", Part.from_proto),
        AggregateCommitMessage: ("commit", AggregateCommit.from_proto)}


def _record(kind: str) -> dict:
    msg = MESSAGES.get(kind)
    return msg.to_wal() if msg is not None else PLAIN[kind]


def _counted(before: dict) -> dict:
    now = walmod.record_counts()
    return {k: now[k] - before[k] for k in now}


def _write(path: str, records, **kw) -> None:
    w = WAL(path, **kw)
    for r in records:
        w.write(r)
    w.close()


def _payloads(path: str) -> list:
    return [p for f in WAL.group_files(path)
            for p, _ in WAL.iter_frames(f, decode=bytes)]


@pytest.mark.parametrize("kind", [*MESSAGES, *PLAIN])
def test_a_record_comes_back_through_both_readers(tmp_path, kind):
    path = str(tmp_path / "wal")
    record = _record(kind)
    _write(path, [record])
    (payload,) = _payloads(path)
    codec = "json" if kind in JSON_KINDS else "binary"
    assert (payload[0] == ord("{")) == (codec == "json")

    before = walmod.record_counts()
    (got,) = list(WAL.iter_group(path))
    assert _counted(before) == {"binary": int(codec == "binary"),
                                "json": int(codec == "json")}
    msg = MESSAGES.get(kind)
    if msg is None:
        assert got == record
    else:
        # the dict contract: the body a proto dict that dejsonify
        # leaves as it is, under the key the references read
        key, from_proto = BODY[type(msg)]
        assert set(got) == set(record)
        assert dejsonify(got[key]) == got[key]
        assert from_proto(dejsonify(got[key])) == getattr(msg, key)
        assert message_from_wal(got) == msg

    if kind == "arbitrary":
        return                      # not an input the playback takes
    burst, read, size = replay_mod._read_ahead(replay_mod._frames(path))
    assert (read, size) == (1, 8 + len(payload))
    assert burst == {
        "timeout": [("timeout", TimeoutInfo(0, 7, 1, STEP_PRECOMMIT_WAIT),
                     "")],
        "end_height": [], "round_state": []}.get(kind, [("peer", msg, "")])


def test_the_binary_payload_is_the_proto():
    """A vote's payload is its tag and ``wire.encode`` of its proto:
    a third of the bytes of the JSON it replaces."""
    from cometbft_tpu.wire import encode, pb
    vote = MESSAGES["prevote"].vote
    payload = walmod.encode_record(VoteMessage(vote).to_wal())
    assert payload == bytes((1,)) + encode(pb.VOTE, vote.to_proto())
    legacy = json.dumps(jsonify(VoteMessage(vote).to_wal()),
                        separators=(",", ":"), sort_keys=True).encode()
    assert 2 * len(payload) < len(legacy)
    assert all(k.tag[0] != ord("{") for k in walmod._KINDS.values())


@pytest.mark.parametrize("case", ["jsonified_body", "extra_key",
                                  "body_key_unknown", "header_not_int"])
def test_a_record_the_codec_cannot_hold_stays_json(tmp_path, case):
    """A record whose keys are not exactly its type's, or whose values
    the proto cannot hold, is written as JSON and read back as it was
    written."""
    vote = MESSAGES["prevote"]
    part = MESSAGES["part_1kb"]
    record = {
        "jsonified_body": {"type": "vote",
                           "vote": jsonify(vote.vote.to_proto())},
        "extra_key": {**vote.to_wal(), "peer": "abc"},
        "body_key_unknown": {"type": "vote",
                             "vote": {**vote.vote.to_proto(), "x": 1}},
        "header_not_int": {**part.to_wal(), "height": "7"},
    }[case]
    path = str(tmp_path / "wal")
    _write(path, [record])
    (payload,) = _payloads(path)
    assert payload[:1] == b"{"
    (got,) = list(WAL.iter_group(path))
    if case == "jsonified_body":
        # its bytes restored: the record the codec would have taken
        assert got == vote.to_wal()
        assert message_from_wal(got) == vote
    else:
        assert got == record


def test_an_unknown_tag_is_corruption(tmp_path):
    path = str(tmp_path / "wal")
    with open(path, "wb") as f:
        f.write(walmod._frame(b"\x7f\x00\x01"))
    with pytest.raises(CorruptWALError, match="unknown record tag"):
        list(WAL.iter_group(path))


# -- a WAL written before the binary payload -----------------------------------

def _legacy_copy(src: str, dst: str) -> int:
    """The WAL at ``src`` as the JSON writer wrote it: every record
    canonical JSON with bytes in hex."""
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    n = 0
    with open(dst, "wb") as f:
        for rec in WAL.iter_group(src):
            f.write(walmod._frame(json.dumps(
                jsonify(rec), separators=(",", ":"),
                sort_keys=True).encode()))
            n += 1
    return n


def test_a_wal_written_in_json_plays_back_to_the_same_heights(tmp_path):
    n, heights = 12, 6
    made = fabricate(str(tmp_path / "made"), n, heights)
    legacy = str(tmp_path / "legacy" / "wal")
    records = _legacy_copy(made.wal_path, legacy)
    assert all(p[:1] == b"{" for p in _payloads(legacy))

    def replayed(path):
        forget_votes()
        node = fresh_node(n)
        before = walmod.record_counts()
        committed = play(node, path)
        return node, committed, _counted(before)

    old, old_heights, old_counts = replayed(legacy)
    new, new_heights, new_counts = replayed(made.wal_path)
    assert old_heights == new_heights == list(range(1, heights + 1))
    assert old_counts == {"json": records, "binary": 0}
    assert new_counts == {"json": 0, "binary": records}
    assert old.state_store.load().app_hash == \
        new.state_store.load().app_hash
    for h in range(1, heights + 1):
        assert old.block_store.load_block(h).hash() == \
            new.block_store.load_block(h).hash()


def test_crash_recovery_reads_a_json_wal_as_a_binary_one(tmp_path):
    """``search_for_end_height`` finds the marker in either payload
    and gives the same records after it; in a binary WAL it decodes
    no message before the marker."""
    made = fabricate(str(tmp_path / "made"), 12, 4)
    legacy = str(tmp_path / "legacy" / "wal")
    _legacy_copy(made.wal_path, legacy)
    tails = {}
    for name, path in (("binary", made.wal_path), ("json", legacy)):
        before = walmod.record_counts()
        tails[name] = WAL.search_for_end_height(path, 2)
        counted = _counted(before)
        after = len(tails[name])
        if name == "binary":
            # the markers' integers (heights 1 and 2) and what follows
            # the second: no message before it is decoded
            assert counted == {"binary": after + 2, "json": 0}
        else:
            assert counted["binary"] == 0 and counted["json"] > after
    assert tails["binary"] == tails["json"]
    assert tails["binary"][-1] == {"type": "end_height", "height": 4}
    assert WAL.search_for_end_height(made.wal_path, 99) is None


# -- the frame rules over both payloads ----------------------------------------

def _frame_records(codec: str, n: int) -> list:
    if codec == "binary":
        return [VoteMessage(replace(PREVOTE, validator_index=i)).to_wal()
                for i in range(n)]
    return [{"type": "note", "i": i, "pad": "x" * 150} for i in range(n)]


def _same(got: list, written: list) -> bool:
    def norm(r):
        return message_from_wal(r) if r.get("type") == "vote" else r
    return [norm(r) for r in got] == [norm(r) for r in written]


def _offsets(path: str) -> list:
    out, pos = [], 0
    for _, size in WAL.iter_frames(path, decode=bytes):
        out.append(pos)
        pos += size
    return out


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_a_torn_tail_is_a_clean_stop_and_is_cut_on_open(tmp_path, codec):
    path = str(tmp_path / "wal")
    records = _frame_records(codec, 5)
    _write(path, records)
    extra = walmod.encode_record(_frame_records(codec, 6)[5])
    with open(path, "ab") as f:
        f.write(walmod._frame(extra)[:8 + len(extra) // 2])
    assert _same(list(WAL.iter_group(path)), records)
    with pytest.raises(CorruptWALError, match="truncated"):
        list(WAL.iter_group(path, strict=True))
    _write(path, _frame_records(codec, 6)[5:])      # reopen cuts it
    assert os.path.exists(path + ".corrupted")
    assert _same(list(WAL.iter_group(path, strict=True)),
                 _frame_records(codec, 6))


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_a_crc_mismatch_is_corruption_and_repair_cuts_there(tmp_path,
                                                            codec):
    path = str(tmp_path / "wal")
    records = _frame_records(codec, 5)
    _write(path, records)
    third = _offsets(path)[2]
    with open(path, "r+b") as f:
        f.seek(third + 8 + 3)
        byte = f.read(1)
        f.seek(third + 8 + 3)
        f.write(bytes((byte[0] ^ 0xFF,)))
    with pytest.raises(CorruptWALError, match="crc mismatch"):
        list(WAL.iter_group(path))
    with open(path, "rb") as f:
        assert _scan_valid_prefix(f.read()) == third
    assert repair_wal_file(path) > 0
    assert _same(list(WAL.iter_group(path)), records[:2])


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_an_oversize_frame_is_corruption(tmp_path, codec):
    path = str(tmp_path / "wal")
    records = _frame_records(codec, 3)
    _write(path, records)
    end = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write((0).to_bytes(4, "big")
                + (MAX_MSG_SIZE_BYTES + 1).to_bytes(4, "big") + b"\x01")
    with pytest.raises(CorruptWALError, match="frame too large"):
        list(WAL.iter_group(path))
    with open(path, "rb") as f:
        assert _scan_valid_prefix(f.read()) == end
    repair_wal_file(path)
    assert _same(list(WAL.iter_group(path, strict=True)), records)


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_repair_of_a_rotated_file_and_a_write_after_it(tmp_path, codec):
    path = str(tmp_path / "wal")
    w = WAL(path, head_size_limit=1024)
    for r in _frame_records(codec, 40):
        w.write(r)
    w.flush_and_sync()
    rotated = WAL.group_files(path)[:-1]
    assert rotated, "needs at least one rotated file"
    with open(rotated[0], "r+b") as f:
        f.seek(os.path.getsize(rotated[0]) // 2)
        f.write(b"\xff" * 16)
    assert repair_wal_file(path) > 0
    w.reopen()
    last = _frame_records(codec, 41)[40]
    w.write_sync(last)
    w.close()
    got = list(WAL.iter_group(path, strict=True))
    assert _same(got[-1:], [last])
    assert len(got) < 41


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_a_record_over_the_size_limit_is_refused(tmp_path, codec):
    w = WAL(str(tmp_path / "wal"))
    if codec == "binary":
        part = replace(BIG.get_part(0),
                       bytes_=b"\x00" * (MAX_MSG_SIZE_BYTES + 1))
        record = BlockPartMessage(7, 0, part).to_wal()
    else:
        record = {"type": "note", "pad": "x" * MAX_MSG_SIZE_BYTES}
    assert walmod.encode_record(record)[:1] == \
        (b"\x03" if codec == "binary" else b"{")
    with pytest.raises(WALError, match="too big"):
        w.write(record)
    w.close()
    assert list(WAL.iter_group(str(tmp_path / "wal"))) == []


# -- the counter ---------------------------------------------------------------

def test_a_fabricated_wal_plays_back_all_binary(tmp_path):
    """Every record of the fabricator's WAL is counted ``binary`` where
    the playback decodes it, none ``json``; each ``wal_read`` span
    notes 0 JSON records; the benchmark's reader reads 100."""
    n, heights = 12, 5
    made = fabricate(str(tmp_path), n, heights)
    records = len(_payloads(made.wal_path))
    forget_votes()
    node = fresh_node(n)
    old = tracing.set_recorder(Recorder(buffer_size=1 << 14))
    before = probes.metrics_snapshot(libmetrics.DEFAULT)
    try:
        assert play(node, made.wal_path) == list(range(1, heights + 1))
        events = tracing.snapshot()
    finally:
        tracing.set_recorder(old)
    delta = probes.metrics_delta(
        before, probes.metrics_snapshot(libmetrics.DEFAULT))
    family = "cometbft_consensus_wal_records_total"
    assert probes.total(delta, family, codec="binary") == records
    assert probes.total(delta, family, codec="json") == 0
    reads = [e for e in events if e["name"] == "wal_read"]
    assert reads and all(e["attrs"]["json"] == 0 for e in reads)
    assert sum(e["attrs"]["records"] for e in reads) == records
    assert replay_wal_binary_share.read(
        SimpleNamespace(metrics=delta)) == 100.0


@pytest.mark.parametrize("binary,json_,want", [
    (300, 0, 100.0), (3, 1, 75.0), (0, 8, 0.0), (0, 0, None)])
def test_the_binary_share_reader(binary, json_, want):
    family = "cometbft_consensus_wal_records_total"
    metrics = {(family, (("codec", "binary"),)): float(binary),
               (family, (("codec", "json"),)): float(json_)}
    assert replay_wal_binary_share.read(
        SimpleNamespace(metrics=metrics)) == want
    assert replay_wal_binary_share.read(SimpleNamespace(metrics={})) \
        is None


def test_the_family_is_on_the_metrics_page():
    page = libmetrics.DEFAULT.render()
    for codec in ("binary", "json"):
        assert f'cometbft_consensus_wal_records_total{{codec="{codec}"}}' \
            in page
