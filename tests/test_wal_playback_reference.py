"""WAL playback (consensus/replay.playback, the ``replay`` command)
against its plain reference (benchmark/reference/wal_replay.py) on
seeded WALs from the fabricator (benchmark/reference/wal.py), at a
small width and at configuration ``wal-150``'s published one: every
vote's verdict, every height's commit, the three kinds of forged vote,
a WAL cut mid-height; the one pre-verification function the receive
routine, the playback and crash recovery share (late precommits of the
last commit included); the span tree of a replayed height and the
three counters; the fabricator against a live state machine."""
import asyncio
import functools
import hashlib
import os
from types import SimpleNamespace

import pytest

from benchmark.reference import wal as walfab
from benchmark.reference import wal_replay as reference
from cometbft_tpu.abci import types as abci
from cometbft_tpu.abci.client import AppConns
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.config import ConsensusConfig
from cometbft_tpu.consensus import replay as replay_mod
from cometbft_tpu.consensus.state import ConsensusState
from cometbft_tpu.consensus.ticker import NilTicker
from cometbft_tpu.consensus.wal import WAL
from cometbft_tpu.db import MemDB
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.tracing import Recorder
from cometbft_tpu.state import make_genesis_state
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.store import Store
from cometbft_tpu.store import BlockStore
from cometbft_tpu.types import vote as vote_mod

CHAIN_ID = "wal-test"
SEED = 5
ONE_IN = 4
# (validators, heights): small, and the published width
SIZES = {"small": (12, 26), "wal-150": (150, 12)}
KINDS = (walfab.PREVOTE, walfab.PRECOMMIT, walfab.LATE)



def forget_votes():
    vote_mod._VERIFIED.clear()
    vote_mod._REJECTED.clear()


def fresh_node(n: int, doc=None):
    """Fresh stores, app and genesis state for an n-validator chain."""
    if doc is None:
        doc, _ = walfab.genesis(CHAIN_ID, SEED, n, 10)
    app = KVStoreApplication()
    conns = AppConns(app)
    state_store, block_store = Store(MemDB()), BlockStore(MemDB())
    state = make_genesis_state(doc)
    state_store.save(state)
    asyncio.run(conns.consensus.init_chain(
        abci.InitChainRequest(chain_id=CHAIN_ID)))
    return SimpleNamespace(app=app, conns=conns, state=state,
                           state_store=state_store,
                           block_store=block_store,
                           witness=reference.Witness())


def play(node, wal_path: str, to_height: int = 0) -> list:
    return asyncio.run(replay_mod.playback(
        ConsensusConfig(), node.state, node.state_store,
        node.block_store, node.conns, wal_path, to_height=to_height,
        event_bus=node.witness, logger=node.witness))


def fabricate(tmp, n: int, heights: int, one_in: int = ONE_IN):
    os.makedirs(tmp, exist_ok=True)
    return asyncio.run(walfab.fabricate(
        os.path.join(tmp, "wal"), CHAIN_ID, SEED, n, 10, heights, 1024,
        one_in))


def digest(wal_path: str) -> str:
    h = hashlib.sha256()
    for path in WAL.group_files(wal_path):
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


@functools.cache
def played(size: str):
    """One fabricated WAL of ``size`` played back once, under a
    recorder of its own, beside the reference's reading of it."""
    import tempfile
    n, heights = SIZES[size]
    made = fabricate(tempfile.mkdtemp(prefix="wal-playback-"), n,
                     heights)
    before = digest(made.wal_path)
    forget_votes()
    old = tracing.set_recorder(Recorder(buffer_size=1 << 16))
    counts0 = vote_mod.verify_counts()
    try:
        node = fresh_node(n)
        committed = play(node, made.wal_path)
        events = tracing.snapshot()
    finally:
        tracing.set_recorder(old)
    counts1 = vote_mod.verify_counts()
    _, vset = walfab.genesis(CHAIN_ID, SEED, n, 10)
    want = reference.run(CHAIN_ID, reference.validators_of(vset),
                         made.wal_path)
    return SimpleNamespace(
        made=made, node=node, committed=committed, events=events,
        want=want, vset=vset, wal_unchanged=digest(made.wal_path) == before,
        memo=counts1[0] - counts0[0], serial=counts1[1] - counts0[1])


def named(events, name):
    return [e for e in events if e["name"] == name]


# -- the playback against the reference ---------------------------------------

@pytest.mark.parametrize("size", SIZES)
def test_playback_equals_the_reference(size):
    run = played(size)
    n, heights = SIZES[size]
    assert run.committed == list(range(1, heights + 1))
    assert reference.differences(run.want, run.node.witness,
                                 run.node.block_store,
                                 range(1, heights)) == []
    assert run.node.witness.errors == []
    for h in run.committed:
        meta = run.node.block_store.load_block_meta(h)
        assert meta.block_id.hash == run.made.block_hash[h]
    assert run.node.state_store.load().app_hash == \
        run.made.app_hash[heights]
    # what a height holds, as the configuration names it
    need = walfab.quorum(n)

    def kind(h):
        return run.made.forged.get(h, (None,))[0]

    def before(h):      # precommits up to the one that completes +2/3
        return need + (kind(h) == walfab.PRECOMMIT)
    for h in range(2, heights):
        ref = run.want[h]
        assert ref.votes == (n - before(h - 1)) + n + before(h)
        assert ref.late == n - before(h - 1)
        assert len(ref.at_quorum) == need
        assert len(ref.after_late) == n - (
            kind(h) in (walfab.PRECOMMIT, walfab.LATE))
        assert ref.round == 0 and ref.block == run.made.block_hash[h]


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("kind", KINDS)
def test_a_forged_vote_is_refused_and_its_height_commits(size, kind):
    run = played(size)
    hits = [(h, at) for h, (k, at) in run.made.forged.items()
            if k == kind and h < SIZES[size][1]]
    assert hits, f"the WAL forged no {kind}"
    for h, at in hits:
        assert run.node.witness.refused[h] == {
            (kind, at): reference.INVALID_SIGNATURE}
        assert run.want[h].refused == {
            (kind, at): reference.INVALID_SIGNATURE}
        assert (kind, at) not in run.node.witness.accepted[h]
        assert h in run.committed
        seen = run.node.block_store.load_seen_commit(h)
        assert seen.block_id.hash == run.made.block_hash[h]
        if kind != walfab.PREVOTE:
            # never counted: absent from the seen commit
            assert seen.signatures[at].absent_flag()


@pytest.mark.parametrize("size", SIZES)
def test_every_vote_reaches_the_tally_pre_verified(size):
    """One batch a height holds every vote between two markers, the
    late precommits of the last commit among them; the serial path
    verifies nothing but the forged votes' confirmations."""
    run = played(size)
    n, heights = SIZES[size]
    pre = {e["height"]: e["attrs"] for e in named(run.events,
                                                  "vote_preverify")}
    tally = {e["height"]: e["attrs"] for e in named(run.events,
                                                    "vote_tally")}
    assert sorted(pre) == sorted(tally) == run.committed
    for h in run.committed:
        ref = run.want[h]
        assert pre[h] == {"entries": ref.votes, "late": ref.late,
                          "fresh": ref.votes}
        assert tally[h]["votes"] == ref.votes
        forged_here = sum(
            1 for fh, (kind, _) in run.made.forged.items()
            if fh + (kind == walfab.LATE) == h)
        assert tally[h]["serial_verifies"] == forged_here
        assert tally[h]["memo_hits"] == ref.votes
    assert pre[2]["late"] == n - walfab.quorum(n)
    assert run.serial == len([h for h in run.made.forged if h < heights])
    assert run.memo == sum(r.votes for r in run.want.values())


def test_the_playback_writes_nothing_to_the_wal_it_reads():
    assert played("small").wal_unchanged


def test_to_height_stops_there(tmp_path):
    run = played("small")
    node = fresh_node(SIZES["small"][0])
    assert play(node, run.made.wal_path, to_height=5) == [1, 2, 3, 4, 5]
    assert node.block_store.height == 5
    # and goes on from the marker below the state's height
    node.state = node.state_store.load()
    assert play(node, run.made.wal_path, to_height=8) == [6, 7, 8]


@pytest.mark.parametrize("size", SIZES)
def test_a_wal_cut_mid_height_ends_stalled(tmp_path, size):
    n, _ = SIZES[size]
    made = fabricate(str(tmp_path / "whole"), n, 4, one_in=0)
    records = list(WAL.iter_group(made.wal_path))
    marks = [i for i, r in enumerate(records)
             if r.get("type") == "end_height"]
    # up to half-way through the last height's votes
    cut = records[:(marks[-2] + marks[-1]) // 2]
    wal = WAL(str(tmp_path / "cut" / "wal"))
    for r in cut:
        wal.write(r)
    wal.close()
    old = tracing.set_recorder(Recorder(buffer_size=1 << 14))
    try:
        node = fresh_node(n)
        assert play(node, wal.path) == [1, 2, 3]
        outcomes = [(e["height"], e["attrs"]["outcome"])
                    for e in named(tracing.snapshot(), "replay_height")]
    finally:
        tracing.set_recorder(old)
    assert outcomes == [(1, "committed"), (2, "committed"),
                        (3, "committed"), (4, "stalled")]
    assert node.block_store.height == 3
    assert node.block_store.load_block_meta(4) is None


def test_the_reference_over_worker_processes_is_the_reference(tmp_path):
    run = played("small")
    heights = SIZES["small"][1]
    got = reference.run_parallel(
        CHAIN_ID, reference.validators_of(run.vset), run.made.wal_path,
        heights - 1, 2, str(tmp_path))
    assert sorted(got) == list(range(1, heights))
    for h, r in got.items():
        assert r == run.want[h]


# -- one tree a height, three counters ----------------------------------------

def test_one_replayed_height_is_one_span_tree():
    run = played("small")
    ids = {e["id"]: e for e in run.events if e.get("id")}

    def chain_of(e):
        out = []
        while e is not None:
            out.append(e["name"])
            e = ids.get(e["parent"])
        return out[::-1]

    (root,) = named(run.events, "wal_replay")
    assert root["parent"] == 0 and root["category"] == tracing.CONSENSUS
    assert root["attrs"] == {"from": 1, "to": SIZES["small"][1]}
    heights = named(run.events, "replay_height")
    assert [e["parent"] for e in heights] == [root["id"]] * len(heights)
    # gc_us: the collections that struck the height (ISSUE 37)
    assert all(e["attrs"].pop("gc_us") >= 0 for e in heights)
    assert [e["attrs"] for e in heights] == \
        [{"outcome": "committed"}] * len(heights)
    for h in heights[1:]:
        below = [e for e in run.events if e.get("id")
                 and h["id"] in [a["id"] for a in
                                 _ancestors(e, ids)]]
        chains = {tuple(chain_of(e)) for e in below}
        top = ("wal_replay", "replay_height")
        # the read, the barrier around the seam's batch, the records
        # one by one: the proposal's block validated as it completes
        # (its LastCommit through the seam), the commit finalised by
        # the precommit that completes +2/3
        assert top + ("wal_read",) in chains
        assert top + ("vote_preverify", "batch_verify") in chains
        assert top + ("vote_tally", "validate_block", "commit_verify",
                      "batch_verify") in chains
        for inner in ("validate_block", "store_save_block",
                      "apply_block"):
            assert top + ("vote_tally", "finalize_commit",
                          inner) in chains
        # (a collection may strike anywhere: its gc_pause is not the
        # replay's own span)
        one = [e for e in below if e["parent"] == h["id"]
               and e["category"] != tracing.RUNTIME]
        assert sorted(e["name"] for e in one if e["dur_ns"]) == [
            "vote_preverify", "vote_tally", "wal_read"]
        assert h["height"] == one[0]["height"]
    (read,) = [e for e in run.events if e["name"] == "wal_read"
               and e["height"] == 2]
    assert read["attrs"]["records"] == run.want[2].votes + 4
    assert read["attrs"]["bytes"] > 100 * read["attrs"]["records"]


def _ancestors(e, ids):
    out = []
    e = ids.get(e["parent"])
    while e is not None:
        out.append(e)
        e = ids.get(e["parent"])
    return out


def _sample(text: str, name: str, **labels) -> float:
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in
                                  labels.items()) + "}" if labels else "")
    for line in text.splitlines():
        if line.startswith(want + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{want} is not on the page")


def test_the_three_counters_are_on_the_metrics_page(tmp_path):
    """The process-global registry a node's /metrics page merges in."""
    n = 12
    made = fabricate(str(tmp_path), n, 6, one_in=3)
    forget_votes()
    before = libmetrics.DEFAULT.render()
    play(fresh_node(n), made.wal_path)
    page = libmetrics.render_merged(libmetrics.Registry(),
                                    libmetrics.DEFAULT)

    def moved(name, **labels):
        return _sample(page, name, **labels) - \
            _sample(before, name, **labels)
    forged = [h for h in made.forged if h < 6]
    votes = sum(r.votes for r in reference.run(
        CHAIN_ID, reference.validators_of(
            walfab.genesis(CHAIN_ID, SEED, n, 10)[1]),
        made.wal_path).values())
    assert moved("cometbft_consensus_replay_heights_total") == 6
    assert moved("cometbft_consensus_vote_verify_total",
                 path="serial") == len(forged) == 2
    assert moved("cometbft_consensus_vote_verify_total",
                 path="memo") == votes
    assert moved("cometbft_consensus_vote_preverified_total",
                 verdict="invalid") == 2
    assert moved("cometbft_consensus_vote_preverified_total",
                 verdict="valid") == votes - 2
    assert moved("cometbft_consensus_vote_preverified_total",
                 verdict="unjudged") == 0


# -- the one pre-verification function ----------------------------------------

def live_state(node, wal=None) -> ConsensusState:
    config = ConsensusConfig()
    config.pipeline_commit = False      # no supervisor to apply under
    # upstream's default: a node waits out NewHeight (and writes the
    # timeout that ends it) even when every precommit has arrived
    config.timeout_commit_ns = 1_000_000_000
    cs = ConsensusState(
        config, node.state,
        BlockExecutor(node.state_store, node.conns.consensus,
                      block_store=node.block_store),
        node.block_store, wal=wal, event_bus=node.witness,
        logger=node.witness)
    cs.ticker = NilTicker()
    return cs


def inputs(records) -> list:
    return replay_mod._read_ahead(
        (r, 0) for r in records if r.get("type") != "end_height")[0]


def test_the_fabricated_wal_is_what_a_live_node_writes(tmp_path):
    """A live state machine (not replaying: it writes its WAL) fed the
    fabricated WAL's inputs a height's burst at a time writes the same
    records in the same order, ``round_state`` aside."""
    n, heights = 12, 9
    made = fabricate(str(tmp_path / "made"), n, heights)
    node = fresh_node(n)
    live_wal = WAL(str(tmp_path / "live" / "wal"))
    cs = live_state(node, wal=live_wal)
    records = list(WAL.iter_group(made.wal_path))

    async def feed():
        burst = []
        for r in records:
            if r.get("type") == "end_height":
                await cs._handle_burst(inputs(burst))
                burst = []
            else:
                burst.append(r)
    asyncio.run(feed())
    live_wal.close()
    wrote = [r for r in WAL.iter_group(live_wal.path)
             if r.get("type") != "round_state"]
    assert wrote == records
    assert node.block_store.height == heights


def test_late_precommits_are_pre_verified_on_the_live_path(tmp_path):
    n, need = 12, walfab.quorum(12)
    made = fabricate(str(tmp_path), n, 3, one_in=0)
    forget_votes()
    node = fresh_node(n)
    cs = live_state(node)
    records = list(WAL.iter_group(made.wal_path))
    marks = [i for i, r in enumerate(records)
             if r.get("type") == "end_height"]
    late = records[marks[0] + 1:marks[0] + 1 + n - need]
    assert all(r["type"] == "vote" for r in late)
    old = tracing.set_recorder(Recorder(buffer_size=1 << 12))
    try:
        async def feed():
            await cs._handle_burst(inputs(records[:marks[0]]))
            assert cs.rs.height == 2
            serial = vote_mod.verify_counts()[1]
            tracing.clear()
            await cs._handle_burst(inputs(late))
            return vote_mod.verify_counts()[1] - serial
        assert asyncio.run(feed()) == 0
        events = tracing.snapshot()
    finally:
        tracing.set_recorder(old)
    (pre,) = named(events, "vote_preverify")
    assert pre["attrs"] == {"entries": n - need, "late": n - need,
                            "fresh": n - need}
    (batch,) = named(events, "batch_verify")
    assert batch["parent"] == pre["id"]
    assert batch["attrs"]["batch"] == n - need
    (tally,) = named(events, "vote_tally")
    assert tally["attrs"] == {"votes": n - need, "memo_hits": n - need,
                              "serial_verifies": 0}
    assert cs.rs.last_commit.has_all()


def test_crash_recovery_pre_verifies_the_height_in_flight(tmp_path):
    """catchup_replay over a 150-validator height in flight (every
    prevote and the precommits short of +2/3 in the WAL, no marker):
    one batch on the seam for its votes, no serial verification, and
    the state a record at a time leaves."""
    n, need = 150, walfab.quorum(150)
    made = fabricate(str(tmp_path / "made"), n, 3, one_in=0)
    records = list(WAL.iter_group(made.wal_path))
    marks = [i for i, r in enumerate(records)
             if r.get("type") == "end_height"]
    wal = WAL(str(tmp_path / "crashed" / "wal"))
    for r in records[:marks[2] - 1]:    # the last precommit unwritten
        wal.write(r)
    wal.close()
    in_flight = records[marks[1] + 1:marks[2] - 1]
    votes = sum(1 for r in in_flight if r["type"] == "vote")
    assert votes == (n - need) + n + need - 1

    def recovered(feed):
        forget_votes()
        node = fresh_node(n)
        assert play(node, wal.path, to_height=2) == [1, 2]
        node.state = node.state_store.load()
        cs = live_state(node)
        old = tracing.set_recorder(Recorder(buffer_size=1 << 14))
        serial = vote_mod.verify_counts()[1]
        try:
            asyncio.run(feed(cs))
            events = tracing.snapshot()
        finally:
            tracing.set_recorder(old)
        return cs, events, vote_mod.verify_counts()[1] - serial

    async def one_at_a_time(cs):
        cs.replay_mode = True
        for kind, msg, peer in inputs(in_flight):
            if kind == "timeout":
                await cs._handle_timeout(msg)
            else:
                await cs._handle_msg(msg, peer, internal=False)
        cs.replay_mode = False

    cs, events, serial = recovered(
        lambda cs: replay_mod.catchup_replay(cs, wal.path))
    assert serial == 0
    (pre,) = named(events, "vote_preverify")
    assert pre["attrs"] == {"entries": votes, "late": n - need,
                            "fresh": votes}
    seams = named(events, "batch_verify")
    assert sorted(b["attrs"]["batch"] for b in seams) == [n, votes]
    plain, _, serial = recovered(one_at_a_time)
    assert serial == votes
    for a, b in ((cs, plain),):
        assert (a.rs.height, a.rs.round, a.rs.step) == \
            (b.rs.height, b.rs.round, b.rs.step) == (3, 0, 6)
        assert a.rs.proposal_block.hash() == b.rs.proposal_block.hash()
        for which in ("prevotes", "precommits"):
            va, vb = getattr(a.rs.votes, which)(0), \
                getattr(b.rs.votes, which)(0)
            assert va.sum == vb.sum
            assert [va.bit_array().get_index(i) for i in range(n)] == \
                [vb.bit_array().get_index(i) for i in range(n)]
        assert a.rs.last_commit.sum == b.rs.last_commit.sum == n * 10
        assert a.rs.locked_round == b.rs.locked_round == 0


# -- the command ----------------------------------------------------------------

def test_the_replay_command_on_a_temp_home(tmp_path, capsys):
    """``cometbft_tpu replay`` over the home of a stopped one-validator
    node whose stores are gone and whose WAL is left: handshake, then
    the WAL's heights committed again, up to ``--to-height`` and on
    from there."""
    import shutil
    from cometbft_tpu.cmd.__main__ import main
    from cometbft_tpu.confix import effective_config
    from cometbft_tpu.node import Node
    home = str(tmp_path / "home")
    assert main(["--home", home, "init", "--chain-id", CHAIN_ID]) == 0
    cfg = effective_config(home)
    cfg.p2p.laddr = "127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.timeout_commit_ns = 0

    async def run_node() -> int:
        node = Node(cfg)
        await node.start()
        try:
            while node.height < 5:
                await asyncio.sleep(0.02)
        finally:
            await node.stop()
        return node.block_store.height
    top = asyncio.run(asyncio.wait_for(run_node(), 60))
    data = cfg.base.path(cfg.base.db_dir)
    wal_dir = os.path.dirname(cfg.base.path(cfg.consensus.wal_file))
    shutil.move(wal_dir, str(tmp_path / "cs.wal"))
    shutil.rmtree(data)
    os.makedirs(data)
    shutil.move(str(tmp_path / "cs.wal"), wal_dir)
    before = digest(cfg.base.path(cfg.consensus.wal_file))
    capsys.readouterr()
    assert main(["--home", home, "replay", "--to-height", "3"]) == 0
    assert "Replayed heights 1..3 (3 committed)" in \
        capsys.readouterr().out
    assert main(["--home", home, "replay"]) == 0
    out = capsys.readouterr().out
    assert f"Replayed heights 4..{top} ({top - 3} committed)" in out
    assert main(["--home", home, "replay"]) == 0
    assert "no height committed" in capsys.readouterr().out
    assert digest(cfg.base.path(cfg.consensus.wal_file)) == before
