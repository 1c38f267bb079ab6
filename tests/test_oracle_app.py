"""The ``oracle`` app (abci/oracle.py): its five calls against the plain
reference's own decoder and medians (benchmark/reference/wal_ext_replay)
and the fabricator's own encoder (benchmark/reference/wal_ext), which
share no code with it: ExtendVote, VerifyVoteExtension, PrepareProposal,
ProcessProposal (one batch of the seam, under an ``oracle_validate``
span) and FinalizeBlock; and its selection by name."""
import asyncio
import random

import pytest

from benchmark.reference import wal_ext as fab
from benchmark.reference import wal_ext_replay as reference
from cometbft_tpu.abci import oracle
from cometbft_tpu.abci import types as abci
from cometbft_tpu.abci.oracle import OracleApplication
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.tracing import Recorder
from cometbft_tpu.types import canonical

CHAIN_ID = "oracle-test"
PAIRS = 128
N = 7


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(scope="module")
def chain():
    """A genesis, its privs, and an app after InitChain."""
    doc, vset, privs = fab.genesis(CHAIN_ID, 3, N, 10)
    return doc, vset, privs


def fresh_app(chain, **kw) -> OracleApplication:
    doc, vset, _ = chain
    app = OracleApplication(**kw)
    run(app.init_chain(abci.InitChainRequest(
        chain_id=CHAIN_ID, consensus_params=doc.consensus_params,
        validators=[abci.ValidatorUpdate(
            power=v.voting_power, pub_key_type=v.pub_key.type(),
            pub_key_bytes=v.pub_key.bytes()) for v in vset.validators])))
    return app


def extensions(chain, height: int, seed: int = 3) -> list:
    """Every validator's (extension, extension signature) at
    ``height``, by the fabricator's encoder."""
    _, vset, privs = chain
    out = []
    for val, priv in zip(vset.validators, privs):
        ext = fab.encode_prices(fab.prices(seed, height, val.address,
                                           PAIRS))
        out.append((ext, priv.sign(canonical.vote_extension_sign_bytes(
            CHAIN_ID, height, 0, ext))))
    return out


def info_tx(chain, height: int, exts=None, flags=None) -> bytes:
    _, vset, privs = chain
    exts = exts or extensions(chain, height)
    flags = flags or [2] * N
    return fab.encode_commit_info(0, [
        (val.address, val.voting_power, flag,
         ext if flag == 2 else b"", sig if flag == 2 else b"", b"",
         priv.sign(b"") if flag == 2 else b"")
        for val, priv, (ext, sig), flag in zip(vset.validators, privs,
                                               exts, flags)])


def last_commit(chain, flags=None) -> abci.CommitInfo:
    _, vset, _ = chain
    flags = flags or [2] * N
    return abci.CommitInfo(round=0, votes=[
        abci.VoteInfo(validator=abci.ABCIValidator(
            address=v.address, power=v.voting_power), block_id_flag=f)
        for v, f in zip(vset.validators, flags)])


# -- ExtendVote, VerifyVoteExtension ---------------------------------------------

def test_extend_vote_is_the_fabricators_payload(chain):
    _, vset, _ = chain
    addr = vset.validators[2].address
    app = fresh_app(chain, seed=3, validator_address=addr)
    got = run(app.extend_vote(abci.ExtendVoteRequest(height=9)))
    want = fab.prices(3, 9, addr, PAIRS)
    assert got.vote_extension == fab.encode_prices(want)
    assert reference.decode_prices(got.vote_extension, PAIRS) == want
    assert got.non_rp_extension == b""
    assert 1_700 < len(got.vote_extension) < 2_000


def _payloads() -> dict:
    rng = random.Random(40)
    good = fab.prices(1, 2, b"v" * 20, PAIRS)
    some = dict(list(good.items())[:5])
    big = {p: v for p, v in enumerate(range(1, 300))}
    return {
        "all pairs": fab.encode_prices(good),
        "a few pairs": fab.encode_prices(some),
        "none": b"",
        "an unknown two-byte pair id": fab.encode_prices({3: 7, 130: 9}),
        "unknown pair": fab.encode_prices({**some, PAIRS + 4: 1}),
        "zero price": fab.encode_prices({**some, 9: 0}),
        "over the cap": fab.encode_prices(big),
        "short price": fab._field(1, b"\x08\x05" + fab._field(2, b"\x01" * 7)),
        "repeated pair": fab.encode_prices(some) + fab.encode_prices(
            {next(iter(some)): 5}),
        "another field": fab._field(2, b"\x08\x01"),
        "truncated": fab.encode_prices(good)[:-3],
        "garbage": rng.randbytes(1_000),
    }


@pytest.mark.parametrize("name,payload", list(_payloads().items()))
def test_verify_vote_extension_is_the_reference_decoders_verdict(
        chain, name, payload):
    app = OracleApplication()
    try:
        want = reference.decode_prices(payload, PAIRS)
    except ValueError:
        want = None
    got = run(app.verify_vote_extension(abci.VerifyVoteExtensionRequest(
        vote_extension=payload)))
    assert got.is_accepted() == (want is not None), name
    if want is not None:
        assert oracle.decode_prices(payload) == want
    assert got.is_accepted() == (name in ("all pairs", "a few pairs",
                                          "none"))


def test_the_fast_and_the_general_decoder_agree():
    """A payload of one-byte ids takes one unpack; the same entries
    with one id written in two bytes (a varint may be) take the loop."""
    rng = random.Random(41)
    for _ in range(50):
        prices = {p: rng.randrange(1, 1 << 63)
                  for p in rng.sample(range(PAIRS - 1), rng.randrange(1, 40))}
        raw = fab.encode_prices(prices)
        assert oracle.decode_prices(raw) == prices
        wide = fab._field(1, b"\x08\xff\x00" + fab._field(
            2, (7).to_bytes(8, "big")))             # pair 127, two bytes
        assert oracle.decode_prices(raw + wide) == {**prices, 127: 7}
        assert reference.decode_prices(raw + wide, PAIRS) == \
            {**prices, 127: 7}


# -- PrepareProposal, ProcessProposal, FinalizeBlock -----------------------------

def test_prepare_proposal_injects_the_local_last_commit(chain):
    _, vset, privs = chain
    app = fresh_app(chain)
    exts = extensions(chain, 4)
    ci = abci.ExtendedCommitInfo(round=0, votes=[
        abci.ExtendedVoteInfo(
            validator=abci.ABCIValidator(address=v.address,
                                         power=v.voting_power),
            vote_extension=ext, extension_signature=sig, block_id_flag=2,
            non_rp_extension_signature=priv.sign(b""))
        for v, priv, (ext, sig) in zip(vset.validators, privs, exts)])
    tx = b"k=v"
    got = run(app.prepare_proposal(abci.PrepareProposalRequest(
        txs=[tx], local_last_commit=ci, height=5, max_tx_bytes=-1)))
    # the app's encoder and the fabricator's write the same bytes
    assert got.txs == [info_tx(chain, 4, exts), tx]
    assert [v[:4] for v in reference.commit_info(got.txs[0])] == [
        (v.address, v.voting_power, 2, ext)
        for v, (ext, _) in zip(vset.validators, exts)]
    # the first height has no last commit to inject
    first = run(app.prepare_proposal(abci.PrepareProposalRequest(
        txs=[tx], height=1, max_tx_bytes=-1)))
    assert first.txs == [tx]


def _process(app, txs, height=5, commit=None, chain=None):
    return run(app.process_proposal(abci.ProcessProposalRequest(
        txs=txs, height=height, hash=b"h%d" % height,
        proposed_last_commit=commit or last_commit(chain))))


def test_process_proposal_accepts_the_injected_commit_in_one_batch(chain):
    app = fresh_app(chain)
    old = tracing.set_recorder(Recorder(buffer_size=1 << 12))
    try:
        async def call():
            with tracing.span(tracing.ABCI, "consensus/process_proposal"):
                return await app.process_proposal(
                    abci.ProcessProposalRequest(
                        txs=[info_tx(chain, 4), b"k=v"], height=5,
                        hash=b"h5", proposed_last_commit=last_commit(
                            chain)))
        assert run(call()).is_accepted()
        events = tracing.snapshot()
    finally:
        tracing.set_recorder(old)
    (outer,) = [e for e in events if e["name"] ==
                "consensus/process_proposal"]
    (check,) = [e for e in events if e["name"] == "oracle_validate"]
    (batch,) = [e for e in events if e["name"] == "batch_verify"]
    assert check["parent"] == outer["id"] and \
        check["category"] == tracing.ABCI
    assert check["attrs"] == {"votes": N}
    assert batch["parent"] == check["id"] and batch["attrs"]["batch"] == N


def test_process_proposal_refuses_what_validate_vote_extensions_refuses(
        chain):
    app = fresh_app(chain)
    exts = extensions(chain, 4)
    forged = list(exts)
    ext, sig = forged[3]
    forged[3] = (ext, sig[:10] + bytes([sig[10] ^ 4]) + sig[11:])
    unknown = list(exts)
    unknown[1] = (fab.encode_prices({PAIRS + 1: 5}), unknown[1][1])
    few = [2, 2, 2, 2, 1, 1, 1]      # 4 of 7: not more than 2/3
    cases = {
        "a forged extension signature": ([info_tx(chain, 4, forged)],
                                         None),
        "a payload the app rejects": ([info_tx(chain, 4, unknown)], None),
        "signers that are not the last commit's": (
            [info_tx(chain, 4, exts, [2] * (N - 1) + [1])], None),
        "not more than 2/3": ([info_tx(chain, 4, exts, few)],
                              last_commit(chain, few)),
        "another height's extensions": ([info_tx(chain, 3)], None),
        "no tx": ([], None),
        "not a commit info": ([b"k=v"], None),
    }
    for name, (txs, commit) in cases.items():
        got = _process(app, txs, commit=commit, chain=chain)
        assert not got.is_accepted(), name
    # a block of the first height carries none, and is the kvstore's
    assert _process(app, [b"k=v"], height=1, chain=chain).is_accepted()
    assert not _process(app, [b"k:v"], height=1, chain=chain).is_accepted()


@pytest.mark.parametrize("proposed", [True, False])
def test_finalize_block_writes_the_reference_medians(chain, proposed):
    """Whether the app validated the block in ProcessProposal or not
    (a block it did not see proposed: blocksync, the handshake)."""
    app = fresh_app(chain)
    exts = extensions(chain, 4)
    exts[5] = (fab.encode_prices({1: 3, 2: 4}), exts[5][1])  # fewer pairs
    flags = [2, 2, 2, 2, 2, 2, 1]
    tx = info_tx(chain, 4, exts, flags)
    commit = last_commit(chain, flags)
    if proposed:
        # the signature of the seventh vote is over the payload above:
        # re-sign it so that ProcessProposal accepts
        _, vset, privs = chain
        exts[5] = (exts[5][0], privs[5].sign(
            canonical.vote_extension_sign_bytes(CHAIN_ID, 4, 0,
                                                exts[5][0])))
        tx = info_tx(chain, 4, exts, flags)
        assert _process(app, [tx, b"k=v"], commit=commit).is_accepted()
    resp = run(app.finalize_block(abci.FinalizeBlockRequest(
        txs=[tx, b"k=v"], height=5, hash=b"h5",
        decided_last_commit=commit)))
    assert len(resp.tx_results) == 2
    run(app.commit(abci.CommitRequest()))
    want = reference.medians(tx, PAIRS)
    assert len(want) == PAIRS
    for pair, price in want.items():
        got = run(app.query(abci.QueryRequest(
            data=oracle.oracle_key(pair))))
        assert got.value == b"%d" % price
    assert run(app.query(abci.QueryRequest(data=b"k"))).value == b"v"
    # the injected tx wrote no kv pair of its own
    assert resp.app_hash == app.tree.reported_hash()


def test_the_oracle_app_is_selectable_by_name():
    from cometbft_tpu.abci.server import _build_app
    assert isinstance(_build_app("oracle"), OracleApplication)


def test_a_restarted_app_checks_as_before(chain):
    """The chain id and the height extensions start at come with
    InitChain; an app made again over the same database (a node's
    restart: no InitChain) keeps them."""
    from cometbft_tpu.db import MemDB
    db = MemDB()
    doc, vset, _ = chain
    first = OracleApplication(db=db)
    run(first.init_chain(abci.InitChainRequest(
        chain_id=CHAIN_ID, consensus_params=doc.consensus_params,
        validators=[abci.ValidatorUpdate(
            power=v.voting_power, pub_key_type=v.pub_key.type(),
            pub_key_bytes=v.pub_key.bytes()) for v in vset.validators])))
    run(first.commit(abci.CommitRequest()))
    again = OracleApplication(db=db)
    assert (again.chain_id, again.vote_extensions_enable_height) == \
        (CHAIN_ID, 1)
    assert _process(again, [info_tx(chain, 4), b"k=v"],
                    chain=chain).is_accepted()
