"""WAL playback with vote extensions on (configuration ``vote-ext-60``)
against its plain reference (benchmark/reference/wal_ext_replay.py) on
seeded WALs from its fabricator (benchmark/reference/wal_ext.py), over
the ``oracle`` app: every vote's verdict and both extension verdicts of
every non-nil precommit, each forged kind refused with its own words
and its height committed, the seen extended commit, the oracle's
medians; a restart mid-WAL that rebuilds the last commit from the
stored extended commit (playback and crash recovery); every signature
on the batch path; the spans and the counter family extensions add."""
import asyncio
import functools
import os
import tempfile
from types import SimpleNamespace

import pytest

from benchmark.reference import wal_ext as fab
from benchmark.reference import wal_ext_replay as reference
from benchmark.reference import wal_replay
from cometbft_tpu.abci import types as abci
from cometbft_tpu.abci.oracle import OracleApplication, oracle_key
from cometbft_tpu.config import ConsensusConfig
from cometbft_tpu.consensus import replay as replay_mod
from cometbft_tpu.consensus.state import ConsensusState
from cometbft_tpu.consensus.ticker import NilTicker
from cometbft_tpu.consensus.wal import WAL
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.tracing import Recorder
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.types import vote as vote_mod

CHAIN_ID = "wal-ext-test"
SEED = 5
ONE_IN = 4          # every kind by height 21 (seed 5: 1, 5, 9, ..., 25)
N, HEIGHTS, PAIRS = 12, 26, 128
ERRORS = {
    fab.PREVOTE: (wal_replay.PREVOTE, reference.INVALID_SIGNATURE),
    fab.PRECOMMIT: (wal_replay.PRECOMMIT, reference.INVALID_SIGNATURE),
    fab.EXT: (wal_replay.PRECOMMIT, "invalid vote extension signature"),
    fab.NON_RP: (wal_replay.PRECOMMIT,
                 "invalid non-RP vote extension signature"),
    fab.LATE_EXT: (wal_replay.LATE, "failed to verify vote: invalid vote "
                                    "extension signature"),
    fab.PAYLOAD: (wal_replay.PRECOMMIT, "invalid vote extension"),
}


def forget_votes():
    vote_mod._VERIFIED.clear()
    vote_mod._REJECTED.clear()


def fresh_node(doc=None, app=None):
    if doc is None:
        doc, _, _ = fab.genesis(CHAIN_ID, SEED, N, 10)
    app = app or OracleApplication()
    conns, state_store, block_store, state = asyncio.run(
        fab.fresh_node(doc, app))
    return SimpleNamespace(app=app, conns=conns, state=state,
                           state_store=state_store,
                           block_store=block_store,
                           witness=wal_replay.Witness())


def play(node, wal_path: str, to_height: int = 0) -> list:
    return asyncio.run(replay_mod.playback(
        ConsensusConfig(), node.state, node.state_store,
        node.block_store, node.conns, wal_path, to_height=to_height,
        event_bus=node.witness, logger=node.witness))


def fabricate(tmp, n=N, heights=HEIGHTS, one_in=ONE_IN):
    os.makedirs(tmp, exist_ok=True)
    return asyncio.run(fab.fabricate(
        os.path.join(tmp, "wal"), CHAIN_ID, SEED, n, 10, heights, 1024,
        one_in, PAIRS))


def want_of(made, n=N):
    _, vset, _ = fab.genesis(CHAIN_ID, SEED, n, 10)
    return reference.run(CHAIN_ID, wal_replay.validators_of(vset),
                         made.wal_path, PAIRS)


@functools.cache
def played():
    """One fabricated WAL played back once, under a recorder of its
    own, beside the reference's reading of it."""
    made = fabricate(tempfile.mkdtemp(prefix="wal-ext-playback-"))
    forget_votes()
    old = tracing.set_recorder(Recorder(buffer_size=1 << 17))
    votes0 = vote_mod.verify_counts()
    ext0 = vote_mod.extension_verify_counts()
    try:
        node = fresh_node()
        committed = play(node, made.wal_path)
        events = tracing.snapshot()
    finally:
        tracing.set_recorder(old)
    votes1 = vote_mod.verify_counts()
    ext1 = vote_mod.extension_verify_counts()
    return SimpleNamespace(
        made=made, node=node, committed=committed, events=events,
        want=want_of(made),
        votes=(votes1[0] - votes0[0], votes1[1] - votes0[1]),
        ext=(ext1[0] - ext0[0], ext1[1] - ext0[1]))


def named(events, name):
    return [e for e in events if e["name"] == name]


# -- the playback against the reference ---------------------------------------

def test_playback_equals_the_reference():
    run = played()
    assert run.committed == list(range(1, HEIGHTS + 1))
    assert reference.differences(run.want, run.node.witness,
                                 run.node.block_store,
                                 range(1, HEIGHTS)) == []
    assert run.node.witness.errors == []
    for h in run.committed:
        meta = run.node.block_store.load_block_meta(h)
        assert meta.block_id.hash == run.made.block_hash[h]
    assert run.node.state_store.load().app_hash == \
        run.made.app_hash[HEIGHTS]
    need = 2 * N // 3 + 1
    for h in range(2, HEIGHTS):
        ref = run.want[h]
        assert len(ref.at_quorum) == len(ref.seen_ext) == need
        # two extension signatures a non-nil precommit of the
        # read-ahead: the late ones of h - 1 and those of h
        assert ref.ext_lanes == 2 * (ref.votes - N)


def test_the_reference_over_worker_processes_is_the_reference(tmp_path):
    """What the cell's check runs at the timed size: the heights split
    over worker processes, each read back whole."""
    run = played()
    _, vset, _ = fab.genesis(CHAIN_ID, SEED, N, 10)
    got = reference.run_parallel(
        CHAIN_ID, wal_replay.validators_of(vset), run.made.wal_path, PAIRS,
        HEIGHTS - 1, 2, str(tmp_path))
    assert sorted(got) == list(range(1, HEIGHTS))
    for h, r in got.items():
        assert r == run.want[h], h
    assert os.listdir(tmp_path) == []


def test_every_forged_kind_appears():
    assert sorted({k for h, (k, _) in played().made.forged.items()
                   if h < HEIGHTS}) == sorted(fab.KINDS)


@pytest.mark.parametrize("kind", fab.KINDS)
def test_a_forged_kind_is_refused_with_its_error_and_its_height_commits(
        kind):
    run = played()
    hits = [(h, at) for h, (k, at) in run.made.forged.items()
            if k == kind and h < HEIGHTS]
    assert hits, f"the WAL forged no {kind}"
    key, error = ERRORS[kind]
    for h, at in hits:
        assert run.node.witness.refused[h] == {(key, at): error}
        assert run.want[h].refused == {(key, at): error}
        assert (key, at) not in run.node.witness.accepted[h]
        assert h in run.committed
        if kind != fab.PREVOTE:
            # never counted, and not in the stored extended commit
            assert run.node.block_store.load_seen_commit(h) \
                .signatures[at].absent_flag()
            ec = run.node.block_store.load_block_ext_commit(h)
            assert ec.extended_signatures[at].absent_flag()


def test_the_stored_extended_commit_holds_the_extensions():
    run = played()
    _, vset, _ = fab.genesis(CHAIN_ID, SEED, N, 10)
    for h in range(1, HEIGHTS):
        ec = run.node.block_store.load_block_ext_commit(h)
        for i in run.want[h].at_quorum:
            sig = ec.extended_signatures[i]
            assert sig.extension == fab.encode_prices(fab.prices(
                SEED, h, vset.validators[i].address, PAIRS))
            assert sig.extension_signature and \
                sig.non_rp_extension_signature
            assert sig.non_rp_extension == b""


def test_the_oracle_state_is_the_reference_medians():
    run = played()
    for h in range(2, HEIGHTS + 1):
        txs = run.node.block_store.load_block(h).data.txs
        assert len(txs) == 2 and len(txs[0]) > 1_800 * N
        want = reference.medians(txs[0], PAIRS)
        assert len(want) == PAIRS
        for pair in (0, 1, 64, PAIRS - 1):
            got = asyncio.run(run.node.app.query(abci.QueryRequest(
                data=oracle_key(pair), height=h)))
            assert got.value == b"%d" % want[pair]


def test_every_signature_reaches_the_batch():
    """One batch a read-ahead holds every signature, the extension
    signatures of the late precommits among them; the serial path
    verifies nothing but the forged signatures' confirmations, vote
    and extension signatures counted apart."""
    run = played()
    pre = {e["height"]: e["attrs"] for e in named(run.events,
                                                  "vote_preverify")}
    assert sorted(pre) == run.committed
    for h in run.committed:
        ref = run.want[h]
        assert pre[h]["entries"] == ref.votes + ref.ext_lanes
        assert pre[h]["ext"] == ref.ext_lanes
        assert pre[h]["late"] == 3 * ref.late
    forged = [k for h, (k, _) in run.made.forged.items()]
    assert run.votes[1] == sum(k in (fab.PREVOTE, fab.PRECOMMIT)
                               for k in forged)
    assert run.ext[1] == sum(k in (fab.EXT, fab.NON_RP, fab.LATE_EXT)
                             for k in forged)
    assert run.ext[1] == sum(r.ext_serial for r in run.want.values())
    # every extension check the rule makes, the last height's late
    # precommits (past the WAL's end) aside
    assert sum(run.ext) == sum(r.ext_checks for r in run.want.values())


def test_the_spans_extensions_add():
    run = played()
    ids = {e["id"]: e for e in run.events if e.get("id")}
    exts = named(run.events, "vote_extension")
    assert exts and all(ids[e["parent"]]["name"] == "vote_tally"
                        for e in exts)
    assert {e["attrs"]["status"] for e in exts} == {
        "accepted", "rejected", "invalid"}
    assert all(1_700 < e["attrs"]["bytes"] < 2_000 for e in exts
               if e["attrs"]["status"] == "accepted")
    # a height in hand: one a precommit of that height
    by_height = {}
    for e in exts:
        by_height[e["height"]] = by_height.get(e["height"], 0) + 1
    assert by_height[3] == 2 * N // 3 + 1
    checks = named(run.events, "oracle_validate")
    assert len(checks) == HEIGHTS - 1
    for c in checks:
        assert ids[c["parent"]]["name"] == "consensus/process_proposal"
        assert c["attrs"] == {"votes": N}
        (batch,) = [e for e in run.events if e["parent"] == c["id"]]
        assert batch["name"] == "batch_verify" and \
            batch["attrs"]["batch"] == N


def _sample(text: str, name: str, **labels) -> float:
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in
                                  labels.items()) + "}" if labels else "")
    for line in text.splitlines():
        if line.startswith(want + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{want} is not on the page")


def test_the_extension_counter_is_on_the_metrics_page(tmp_path):
    made = fabricate(str(tmp_path), heights=14)
    forget_votes()
    before = libmetrics.DEFAULT.render()
    play(fresh_node(), made.wal_path)
    page = libmetrics.render_merged(libmetrics.Registry(),
                                    libmetrics.DEFAULT)
    want = want_of(made)

    def moved(path):
        return _sample(page, "cometbft_consensus_vote_extension_verify_"
                       "total", path=path) - _sample(
            before, "cometbft_consensus_vote_extension_verify_total",
            path=path)
    assert moved("serial") == sum(r.ext_serial for r in want.values()) \
        == 2
    assert moved("memo") + moved("serial") == \
        sum(r.ext_checks for r in want.values())


# -- a restart mid-WAL -----------------------------------------------------------

def test_a_restarted_playback_rebuilds_the_last_commit_from_the_extended_commit():
    """Played to height 17, stopped, and played on from the stores: the
    last commit is rebuilt from the stored extended commit (a plain
    one would refuse the late precommits' extensions as unexpected),
    the forged late extension of 17 is refused in it as before, and
    every verdict is the reference's."""
    run = played()
    assert run.made.forged[17][0] == fab.LATE_EXT
    forget_votes()
    node = fresh_node()
    assert play(node, run.made.wal_path, to_height=17) == list(range(1, 18))
    node.state = node.state_store.load()
    rebuilt = []
    real = ConsensusState._vote_set_from_extended_commit

    def spy(self, state, ec):
        rebuilt.append(ec.height)
        return real(self, state, ec)
    ConsensusState._vote_set_from_extended_commit = spy
    try:
        assert play(node, run.made.wal_path) == list(range(18, HEIGHTS + 1))
    finally:
        ConsensusState._vote_set_from_extended_commit = real
    assert rebuilt == [17]
    assert node.witness.refused[17] == {
        (wal_replay.LATE, run.made.forged[17][1]):
            ERRORS[fab.LATE_EXT][1]}
    assert reference.differences(run.want, node.witness, node.block_store,
                                 range(1, HEIGHTS)) == []
    assert node.state_store.load().app_hash == run.made.app_hash[HEIGHTS]


def test_crash_recovery_rebuilds_the_last_commit_and_goes_on(tmp_path):
    """catchup_replay over a height in flight (18: the late precommits
    of 17, one with a forged extension, every prevote, the precommits
    short of +2/3; no marker) on a state machine over the stores of
    height 17: the last commit comes back from the extended commit, the
    forged extension is refused with the serial path's own words, one
    batch holds every signature and nothing is verified serially but
    that forgery's confirmation."""
    run = played()
    records = list(WAL.iter_group(run.made.wal_path))
    marks = [i for i, r in enumerate(records)
             if r.get("type") == "end_height"]
    wal = WAL(str(tmp_path / "crashed" / "wal"))
    for r in records[:marks[17] - 1]:       # 18's last precommit unwritten
        wal.write(r)
    wal.close()
    in_flight = records[marks[16] + 1:marks[17] - 1]
    forget_votes()
    node = fresh_node()
    assert play(node, wal.path, to_height=17) == list(range(1, 18))
    node.state = node.state_store.load()
    config = ConsensusConfig()
    config.pipeline_commit = False
    config.timeout_commit_ns = 1_000_000_000
    cs = ConsensusState(
        config, node.state,
        BlockExecutor(node.state_store, node.conns.consensus,
                      block_store=node.block_store),
        node.block_store, event_bus=node.witness, logger=node.witness)
    cs.ticker = NilTicker()
    assert cs.rs.last_commit.extensions_enabled
    old = tracing.set_recorder(Recorder(buffer_size=1 << 14))
    serial = vote_mod.verify_counts()[1], \
        vote_mod.extension_verify_counts()[1]
    try:
        asyncio.run(replay_mod.catchup_replay(cs, wal.path))
        events = tracing.snapshot()
    finally:
        tracing.set_recorder(old)
    assert (vote_mod.verify_counts()[1] - serial[0],
            vote_mod.extension_verify_counts()[1] - serial[1]) == (0, 1)
    late_at = run.made.forged[17][1]
    assert node.witness.refused[17][(wal_replay.LATE, late_at)] == \
        ERRORS[fab.LATE_EXT][1]
    want = run.want[17]
    assert cs.rs.last_commit.sum == 10 * len(want.after_late)
    assert (cs.rs.height, cs.rs.round) == (18, 0)
    votes = sum(1 for r in in_flight if r["type"] == "vote")
    (pre,) = named(events, "vote_preverify")
    assert pre["attrs"]["entries"] == votes + pre["attrs"]["ext"]
    assert pre["attrs"]["ext"] == 2 * (
        sum(1 for r in in_flight if r["type"] == "vote"
            and r["vote"].get("type") == 2))


# -- the node and the command ----------------------------------------------------

def test_a_node_runs_the_oracle_app_and_the_replay_command_plays_it(
        tmp_path, capsys):
    """A one-validator node with ``proxy_app = "oracle"`` and vote
    extensions on from height 1 extends its precommits with prices,
    injects its extended commit and commits; ``cometbft_tpu replay``
    plays its WAL back over fresh stores by the same name."""
    import json
    import shutil
    from cometbft_tpu.cmd.__main__ import main
    from cometbft_tpu.confix import effective_config, set_value
    from cometbft_tpu.node import Node
    home = str(tmp_path / "home")
    assert main(["--home", home, "init", "--chain-id", CHAIN_ID]) == 0
    set_value(home, "base.proxy_app", "oracle")
    cfg = effective_config(home)
    cfg.p2p.laddr = "127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.timeout_commit_ns = 0
    genesis = cfg.base.path(cfg.base.genesis_file)
    with open(genesis) as f:
        doc = json.load(f)
    params = doc.setdefault("consensus_params", {})
    params.setdefault("feature", {})["vote_extensions_enable_height"] = "1"
    with open(genesis, "w") as f:
        json.dump(doc, f)

    async def run_node():
        node = Node(cfg)
        await node.start()
        try:
            while node.height < 5:
                await asyncio.sleep(0.02)
        finally:
            await node.stop()
        ec = node.block_store.load_block_ext_commit(3)
        first = node.block_store.load_block(4).data.txs[0]
        return node.block_store.height, ec, first
    top, ec, first = asyncio.run(asyncio.wait_for(run_node(), 90))
    (sig,) = [s for s in ec.extended_signatures if not s.absent_flag()]
    assert len(sig.extension) > 1_700 and sig.extension_signature
    assert [v[3] for v in reference.commit_info(first)] == [sig.extension]
    data = cfg.base.path(cfg.base.db_dir)
    wal_dir = os.path.dirname(cfg.base.path(cfg.consensus.wal_file))
    shutil.move(wal_dir, str(tmp_path / "cs.wal"))
    shutil.rmtree(data)
    os.makedirs(data)
    shutil.move(str(tmp_path / "cs.wal"), wal_dir)
    capsys.readouterr()
    assert main(["--home", home, "replay"]) == 0
    assert f"Replayed heights 1..{top} ({top} committed)" in \
        capsys.readouterr().out


def test_the_cells_readers_on_these_recorded_heights():
    """The four readers vote-ext-60.replay adds, over the spans this
    playback recorded (a read-ahead is ``(3 late + 9 on time) x 2``
    extension signatures at 12 validators)."""
    from benchmark.lib import loader, replayspans
    from benchmark.lib.session import Obs
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = loader.Bench(root)
    run = played()
    obs = Obs(cell=bench.cell("vote-ext-60.replay"), spans=run.events,
              setup_spans=[], metrics={}, samples={},
              compiles_in_window=0, laps={}, device_kind="cpu",
              trace=None, trace_spans=run.events)

    def read(metric):
        return bench.reader(metric).read(obs)
    assert read("replay_ext_lanes") == 24
    assert len(replayspans.committed(run.events)) == HEIGHTS
    assert 0 < read("replay_ext_verify_ms") < \
        replayspans.per_height_ms(run.events, "vote_tally")
    assert 0 < read("replay_process_proposal_ms")
    assert read("replay_ext_serial_share") is None     # no counter delta
