"""light.Client.verify_to_height against the plain reference of the
``light-1k`` deployment (benchmark/reference/skipping.py), at small
sizes on the CPU: hop sequence, outcomes, the index a refusal names,
which signatures reached a verifier, what the store holds.  Also: the
by-address commit walk (ValidatorSet.index_by_address) against the
linear lookup it replaced, and the span tree of one request.
"""
import asyncio
import random

import pytest

from benchmark.reference import skipping
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.db import MemDB
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.tracing import Recorder
from cometbft_tpu.light.client import SKIPPING, Client, TrustOptions
from cometbft_tpu.light.store import TrustedStore
from cometbft_tpu.light.verifier import (
    InvalidHeaderError, LightClientError,
)
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import LightBlock
from cometbft_tpu.types.validation import (
    Fraction, NotEnoughVotingPowerError, VerificationError,
    verify_commit_light_trusting,
)
from cometbft_tpu.types.vote import BLOCK_ID_FLAG_COMMIT

CHAIN_ID = "ref-chain"
HEIGHTS = 33
DAY_NS = 24 * 3600 * 10 ** 9
_CHAINS: dict = {}


def chain_of(validators: int, churn: int):
    key = (validators, churn)
    if key not in _CHAINS:
        chain = skipping.build_chain(CHAIN_ID, 11, validators, 10,
                                     churn, HEIGHTS)
        _CHAINS[key] = (chain, {h: lb.to_proto()
                                for h, lb in chain.blocks.items()})
    return _CHAINS[key]


class Provider:
    """Every fetch a freshly decoded block; ``served`` replaces
    heights."""

    def __init__(self, wire: dict, served: dict):
        self.wire, self.served = wire, served

    async def light_block(self, height: int) -> LightBlock:
        return LightBlock.from_proto(
            self.served.get(height) or self.wire[height])

    async def report_evidence(self, ev) -> None:
        raise AssertionError("no divergence on one chain")

    def id(self) -> str:
        return "test-provider"


class RecordingVerifier:
    """What reached a batch verifier, batch by batch."""

    def __init__(self, inner, log: list):
        self.inner, self.log, self.items = inner, log, []

    def add(self, pub_key, msg: bytes, sig: bytes) -> None:
        self.items.append((pub_key.bytes(), msg, sig))
        self.inner.add(pub_key, msg, sig)

    def verify(self):
        self.log.append(self.items)
        return self.inner.verify()


@pytest.fixture
def recorder(tmp_path):
    old = tracing.set_recorder(
        Recorder(buffer_size=65536, dump_dir=str(tmp_path)))
    yield tracing.recorder()
    tracing.set_recorder(old)


@pytest.fixture
def batches(monkeypatch):
    log: list = []
    create = crypto_batch.create_batch_verifier
    monkeypatch.setattr(
        crypto_batch, "create_batch_verifier",
        lambda pub_key: RecordingVerifier(create(pub_key), log))
    return log


def sync(chain, wire: dict, served: dict, level: tuple):
    """(the client, what verify_to_height returned or raised)."""
    provider = Provider(wire, served)
    client = Client(
        CHAIN_ID, TrustOptions(DAY_NS, 1, chain.header_hash(1)),
        provider, [provider], TrustedStore(MemDB()),
        verification_mode=SKIPPING, trust_level=Fraction(*level))

    async def run():
        await client.initialize(now=chain.now)
        try:
            return await client.verify_to_height(HEIGHTS, now=chain.now)
        except LightClientError as e:
            return e
    return client, asyncio.run(run())


def held_to_reference(chain, wire, level, batches, mutate=None,
                      at: int = HEIGHTS):
    """Run the client and the reference over the chain with
    ``mutate(light block)`` applied to height ``at``, and compare
    everything the reference knows.  Returns the reference's hops."""
    served, plain = {}, {}
    if mutate is not None:
        lb = LightBlock.from_proto(wire[at])
        mutate(lb)
        served[at] = lb.to_proto()
        plain[at] = skipping.plain(CHAIN_ID, lb)

    def fetch(height: int):
        if height not in plain:
            plain[height] = skipping.plain(CHAIN_ID,
                                           chain.blocks[height])
        return plain[height]

    want = skipping.verify_skipping(fetch, 1, HEIGHTS, level)
    client, got = sync(chain, wire, served, level)

    hops = [(e["attrs"]["trusted"], e["attrs"]["candidate"],
             e["attrs"]["outcome"])
            for e in tracing.snapshot(category=tracing.LIGHT)
            if e["name"] == "light_hop"]
    assert hops == [(h.trusted, h.candidate, h.outcome) for h in want]
    last = want[-1]
    if last.outcome == skipping.VERIFIED:
        assert got.height == HEIGHTS
        assert got.hash() == plain[HEIGHTS].header_hash
    else:
        assert isinstance(got, InvalidHeaderError), got
        if last.index is not None:
            assert f"wrong signature (#{last.index})" in str(got)
        else:
            assert "wrong signature" not in str(got)
    # which signatures reached a verifier, batch by batch, in order
    assert batches == [
        b for h in want for b in skipping.dispatched(
            plain[h.candidate], plain[h.trusted], h)]
    # the store: the root and every verified hop, nothing else
    verified = {h.candidate: plain[h.candidate].header_hash
                for h in want if h.outcome == skipping.VERIFIED}
    assert client.store.heights() == sorted({1} | set(verified))
    for height, header_hash in verified.items():
        assert client.store.light_block(height).hash() == header_hash
    return want


@pytest.mark.parametrize("level", [(1, 3), (2, 3)], ids=["1of3", "2of3"])
@pytest.mark.parametrize("churn", [0, 1, 2])
@pytest.mark.parametrize("validators", [16, 40, 64])
def test_honest_chain(recorder, batches, validators, churn, level):
    chain, wire = chain_of(validators, churn)
    want = held_to_reference(chain, wire, level, batches)
    assert want[-1].outcome == skipping.VERIFIED
    refused = sum(1 for h in want if h.outcome == skipping.CANT_TRUST)
    if churn == 0:
        assert len(want) == 1       # one set all along: one jump
    if (validators, churn) in ((16, 1), (40, 2)):
        assert refused >= 2         # two levels of bisection


def _forge(index: int):
    def mutate(lb):
        cs = lb.signed_header.commit.signatures[index]
        cs.signature = bytes([cs.signature[0] ^ 1]) + cs.signature[1:]
    return mutate


@pytest.mark.parametrize("level", [(1, 3), (2, 3)], ids=["1of3", "2of3"])
@pytest.mark.parametrize("where", ["trusting", "light_only", "past"])
@pytest.mark.parametrize("what", ["pivot", "target"])
def test_forged_signature(recorder, batches, what, where, level):
    chain, wire = chain_of(40, 1)
    honest = held_to_reference(chain, wire, level, batches)
    del batches[:]
    tracing.clear()
    # the verified hop to forge in: the first (a pivot) or the last
    hop = [h for h in honest if h.outcome == skipping.VERIFIED][
        0 if what == "pivot" else -1]
    assert (hop.candidate == HEIGHTS) == (what == "target")
    # "past": the commit's last lane, beyond the 2/3 mark of the new
    # set; the walk by address goes that far only if it has to
    index = {"trusting": hop.trusting.verified[-1],
             "light_only": hop.light.verified[0],
             "past": len(chain.blocks[1].validator_set) - 1}[where]
    assert index >= hop.light.walked or where != "past"
    want = held_to_reference(chain, wire, level, batches,
                             _forge(index), at=hop.candidate)
    if where == "past" and index not in hop.trusting.taken:
        # never looked at: the sync goes through
        assert [h.outcome for h in want] == [h.outcome for h in honest]
    else:                   # named, and never bisected
        assert want[-1].outcome == skipping.INVALID
        assert want[-1].index == index
        assert want[:-1] == honest[:len(want) - 1]


@pytest.mark.parametrize("level", [(1, 3), (2, 3)], ids=["1of3", "2of3"])
@pytest.mark.parametrize("what", ["unknown_signer", "double_vote"])
def test_commit_lanes_by_address(recorder, batches, what, level):
    """The trusting check goes by the address a commit signature
    states: one that the trusted set does not know is skipped (the
    walk takes a later signer instead), one stated twice is a double
    vote, refused without bisection."""
    chain, wire = chain_of(40, 1)
    honest = held_to_reference(chain, wire, level, batches)
    del batches[:]
    tracing.clear()
    taken = honest[-1].trusting.taken

    def mutate(lb):
        sigs = lb.signed_header.commit.signatures
        sigs[taken[1]].validator_address = \
            random.Random(5).randbytes(20) if what == "unknown_signer" \
            else sigs[taken[0]].validator_address

    want = held_to_reference(chain, wire, level, batches, mutate)
    if what == "double_vote":
        assert want[-1].outcome == skipping.INVALID
        assert want[-1].index is None
    else:
        assert taken[1] not in want[-1].trusting.taken
        assert want[-1].outcome in (skipping.VERIFIED,
                                    skipping.CANT_TRUST)


# -- the by-address walk against the linear lookup it replaced ------------

def _linear_verdict(vals, commit, level: Fraction):
    """The message verify_commit_light_trusting refuses with (None:
    accepted), through ValidatorSet.get_by_address: the linear scan,
    copy and all, that the walk used before index_by_address."""
    needed = vals.total_voting_power() * level.numerator \
        // level.denominator
    seen, entries, tallied = {}, [], 0
    for idx, cs in enumerate(commit.signatures):
        if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
            continue
        val_idx, val = vals.get_by_address(cs.validator_address)
        if val is None:
            continue
        if val_idx in seen:
            return f"double vote from {val} ({seen[val_idx]} and {idx})"
        seen[val_idx] = idx
        entries.append((idx, val))
        tallied += val.voting_power
        if tallied > needed:
            break
    if tallied <= needed:
        return str(NotEnoughVotingPowerError(tallied, needed))
    for idx, val in entries:
        sig = commit.signatures[idx].signature
        if not val.pub_key.verify_signature(
                commit.vote_sign_bytes(CHAIN_ID, idx), sig):
            return f"wrong signature (#{idx}): {sig.hex().upper()}"
    return None


@pytest.mark.parametrize("seed", range(24))
def test_by_address_walk_equals_the_linear_lookup(seed):
    rng = random.Random(seed)
    chain, wire = chain_of(rng.choice((16, 40)), rng.choice((1, 2)))
    lo = rng.randrange(1, HEIGHTS - 1)
    hi = rng.randrange(lo + 1, HEIGHTS + 1)
    trusted = LightBlock.from_proto(wire[lo]).validator_set
    commit = LightBlock.from_proto(wire[hi]).signed_header.commit
    sigs = commit.signatures
    for _ in range(rng.randrange(3)):
        mutation = rng.choice(("forge", "double", "unknown"))
        i, j = rng.sample(range(len(sigs)), 2)
        if mutation == "forge":
            sigs[i].signature = bytes([sigs[i].signature[0] ^ 1]) \
                + sigs[i].signature[1:]
        elif mutation == "double":
            sigs[i].validator_address = sigs[j].validator_address
        else:
            sigs[i].validator_address = rng.randbytes(20)
    level = Fraction(*rng.choice(((1, 3), (1, 2), (2, 3))))
    want = _linear_verdict(trusted, commit, level)
    try:
        verify_commit_light_trusting(CHAIN_ID, trusted, commit, level)
        got = None
    except VerificationError as e:
        got = str(e)
    assert got == want


# -- the span tree of one request -------------------------------------------

def test_one_request_is_one_span_tree(recorder, batches):
    chain, wire = chain_of(40, 1)
    counted = {o: _hops_total(o)
               for o in ("verified", "cant_trust", "invalid")}
    want = held_to_reference(chain, wire, (1, 3), batches)
    events = tracing.snapshot()
    kids: dict = {}
    for e in events:
        kids.setdefault(e["parent"], []).append(e)

    def below(parent, name):
        return [e for e in kids.get(parent["id"], ())
                if e["name"] == name]

    (root,) = [e for e in events if e["name"] == "light_sync"]
    assert root["parent"] == 0 and root["category"] == tracing.LIGHT
    # gc_us: the collections that struck the request (ISSUE 37)
    assert root["attrs"].pop("gc_us") >= 0
    assert root["attrs"] == {"from": 1, "to": HEIGHTS}
    hops = below(root, "light_hop")
    assert len(hops) == len(want) == 3      # 33 refused, 17, 33
    # a fetch for the target and one a bisection, a save a verified
    # hop, the witness's block fetched inside the detector
    assert len(below(root, "light_fetch")) == 2
    # the store read that finds the block to start from opens the
    # request: nothing of a request lies before its root
    (read,) = below(root, "light_store_read")
    assert root["ts_ns"] <= read["ts_ns"] and all(
        read["ts_ns"] + read["dur_ns"] <= e["ts_ns"]
        for e in kids[root["id"]] if e is not read)
    assert len(below(root, "light_store_save")) == 2
    (detect,) = below(root, "light_detect")
    assert len(below(detect, "light_fetch")) == 1
    for span, hop in zip(hops, want):
        # only what a reader reads rides on the span (and, as on
        # every span an exception leaves, its name)
        error = span["attrs"].pop("error", None)
        assert span["attrs"] == {
            "trusted": hop.trusted, "candidate": hop.candidate,
            "outcome": hop.outcome}, span
        assert span["height"] == hop.candidate
        assert len(below(span, "header_checks")) == 1
        checks = below(span, "commit_verify")
        walks = [w for c in checks for w in below(c, "commit_walk")]
        assert [w["attrs"]["lookup"] for w in walks] == (
            ["address"] if hop.outcome == skipping.CANT_TRUST
            else ["address", "index"])
        assert walks[0]["attrs"]["walked"] == hop.trusting.walked
        seams = [b for c in checks for b in below(c, "batch_verify")]
        if hop.outcome == skipping.CANT_TRUST:
            assert error == "NewValSetCantBeTrustedError"
            assert not seams        # refused on the tally alone
            continue
        assert error is None
        light = walks[1]["attrs"]
        assert light["walked"] == hop.light.walked
        assert light["cache_hits"] == \
            len(hop.light.taken) - len(hop.light.verified) > 0
        assert [b["attrs"]["batch"] for b in seams] == [
            len(hop.trusting.verified), len(hop.light.verified)]
    # one counter, by outcome
    assert _hops_total("verified") - counted["verified"] == 2
    assert _hops_total("cant_trust") - counted["cant_trust"] == 1
    assert _hops_total("invalid") == counted["invalid"]


class Tip(Provider):
    """Height 0 is the chain's latest."""

    async def light_block(self, height: int) -> LightBlock:
        return await super().light_block(height or HEIGHTS)


@pytest.mark.parametrize("way_in", ["stored", "update"])
def test_every_way_in_opens_one_root(recorder, way_in):
    """A height the store already has is a ``light_sync`` of one store
    read and no hop; update() verifies forward under a root of its
    own."""
    chain, wire = chain_of(16, 0)
    client, _ = sync(chain, wire, {}, (1, 3))
    if way_in == "update":
        for height in client.store.heights()[1:]:
            client.store.delete(height)
        client.primary = Tip(wire, {})
    tracing.clear()
    if way_in == "stored":
        lb = asyncio.run(client.verify_to_height(HEIGHTS, now=chain.now))
    else:
        lb = asyncio.run(client.update(now=chain.now))
    assert lb.hash() == chain.header_hash(HEIGHTS)
    events = tracing.snapshot(category=tracing.LIGHT)
    (root,) = [e for e in events if e["name"] == "light_sync"]
    below = {e["name"] for e in events if e["parent"] == root["id"]}
    assert root["attrs"].pop("gc_us") >= 0
    if way_in == "stored":
        assert root["attrs"] == {"to": HEIGHTS}
        assert below == {"light_store_read"}
    else:
        assert root["attrs"] == {"from": 1, "to": HEIGHTS}
        assert below == {"light_hop", "light_store_save",
                         "light_detect"}


def _hops_total(outcome: str) -> float:
    for line in libmetrics.DEFAULT.render().splitlines():
        if line.startswith("cometbft_light_hops_total{") and \
                f'outcome="{outcome}"' in line:
            return float(line.split()[-1])
    return 0.0


def test_the_walks_report_nothing_without_a_span():
    """The single-signature path opens no commit_walk span: the walk
    takes no span and notes nothing."""
    chain, wire = chain_of(16, 0)
    lb = LightBlock.from_proto(wire[2])
    tallied = validation._walk_commit(
        CHAIN_ID, lb.validator_set, lb.signed_header.commit, 10 ** 9,
        lambda cs: False, lambda cs: True, True, True, None,
        strict=True, handle=lambda *a: None)
    assert tallied == lb.validator_set.total_voting_power()
