"""Skipping-verification light-client sync (verify_to_height): batch
verifier routing, shared-signature-cache reuse across hops, and
bisection under validator-set rotation (docs/light_proofs.md;
"Practical Light Clients for Committee-Based Blockchains" in
PAPERS.md).
"""
import asyncio

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.db.db import MemDB
from cometbft_tpu.light.client import Client, TrustOptions
from cometbft_tpu.light.provider import (
    LightBlockNotFoundError, Provider,
)
from cometbft_tpu.light.store import TrustedStore
from cometbft_tpu.types import canonical
from cometbft_tpu.types.block import Header, LightBlock, SignedHeader
from cometbft_tpu.types.block_id import BlockID
from cometbft_tpu.types.commit import Commit, CommitSig
from cometbft_tpu.types.part_set import PartSetHeader
from cometbft_tpu.types.priv_validator import new_mock_pv
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, Vote
from cometbft_tpu.version import BLOCK_PROTOCOL

CHAIN_ID = "skip-chain"
T0 = 1_700_000_000
HOUR_NS = 3600 * 10**9


@pytest.fixture(autouse=True)
def _cpu_backend():
    crypto_batch.set_backend("cpu")
    yield
    crypto_batch.set_backend("auto")


def _valset(pvs) -> ValidatorSet:
    return ValidatorSet([
        Validator(address=pv.get_pub_key().address(),
                  pub_key=pv.get_pub_key(), voting_power=10)
        for pv in pvs])


def make_chain(n_heights: int, pvs_by_height) -> dict[int, LightBlock]:
    """Synthetic header chain 1..n signed by per-height validator
    sets; pvs_by_height(h) returns the priv validators of height h
    (and h+1's set is committed as next_validators_hash)."""
    blocks: dict[int, LightBlock] = {}
    prev_id = BlockID()
    for h in range(1, n_heights + 1):
        pvs = pvs_by_height(h)
        vals = _valset(pvs)
        next_vals = _valset(pvs_by_height(h + 1))
        header = Header(
            chain_id=CHAIN_ID, height=h,
            time=Timestamp(T0 + h, 0),
            last_block_id=prev_id,
            validators_hash=vals.hash(),
            next_validators_hash=next_vals.hash(),
            proposer_address=vals.validators[0].address)
        assert header.version.block == BLOCK_PROTOCOL
        bid = BlockID(hash=header.hash(),
                      part_set_header=PartSetHeader(1, b"\xAA" * 32))
        sigs = []
        by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
        for i, val in enumerate(vals.validators):
            ts = Timestamp(T0 + h, i + 1)
            v = Vote(type=canonical.PRECOMMIT_TYPE, height=h, round=0,
                     block_id=bid, timestamp=ts,
                     validator_address=val.address, validator_index=i)
            v.signature = by_addr[val.address].priv_key.sign(
                v.sign_bytes(CHAIN_ID))
            sigs.append(CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=val.address, timestamp=ts,
                signature=v.signature))
        commit = Commit(height=h, round=0, block_id=bid,
                        signatures=sigs)
        blocks[h] = LightBlock(
            signed_header=SignedHeader(header=header, commit=commit),
            validator_set=vals)
        blocks[h].validate_basic(CHAIN_ID)
        prev_id = bid
    return blocks


class DictProvider(Provider):
    def __init__(self, blocks: dict[int, LightBlock]):
        self.blocks = blocks
        self.requests: list[int] = []

    async def light_block(self, height: int) -> LightBlock:
        self.requests.append(height)
        if height == 0:
            height = max(self.blocks)
        lb = self.blocks.get(height)
        if lb is None:
            raise LightBlockNotFoundError(f"no block {height}")
        return lb

    async def report_evidence(self, ev) -> None:
        pass


def _client(blocks, witnesses=()) -> tuple[Client, DictProvider]:
    primary = DictProvider(blocks)
    c = Client(CHAIN_ID,
               TrustOptions(period_ns=24 * HOUR_NS, height=1,
                            header_hash=blocks[1].hash()),
               primary, list(witnesses), TrustedStore(MemDB()))
    return c, primary


def _now() -> Timestamp:
    return Timestamp(T0 + 1000, 0)


class _CountingVerifier:
    """Wraps a BatchVerifier, mirroring adds/verifies into counters."""

    def __init__(self, inner, counts):
        self._inner = inner
        self._counts = counts

    def add(self, pub_key, msg, sig):
        self._counts["added"] += 1
        self._inner.add(pub_key, msg, sig)

    def extend_raw(self, triples):
        self._counts["added"] += len(triples)
        self._inner.extend_raw(triples)

    def verify(self):
        self._counts["batches"] += 1
        return self._inner.verify()

    def __len__(self):
        return len(self._inner)


@pytest.fixture
def batch_counts(monkeypatch):
    counts = {"created": 0, "added": 0, "batches": 0}
    orig = crypto_batch.create_batch_verifier

    def counting(pub_key):
        counts["created"] += 1
        return _CountingVerifier(orig(pub_key), counts)

    monkeypatch.setattr(crypto_batch, "create_batch_verifier",
                        counting)
    return counts


class TestVerifyToHeight:
    def test_single_hop_uses_batch_verifier(self, batch_counts):
        """Stable valset: the target is one non-adjacent hop, and its
        commit checks dispatch through the crypto.batch seam, not the
        per-signature loop."""
        pvs = [new_mock_pv() for _ in range(4)]
        blocks = make_chain(20, lambda h: pvs)

        async def run():
            c, primary = _client(blocks)
            await c.initialize(now=_now())
            lb = await c.verify_to_height(20, now=_now())
            assert lb.height == 20
            # skipping: straight jump, no intermediate fetches
            assert set(primary.requests) <= {1, 20}
            return c
        c = asyncio.run(run())
        assert batch_counts["created"] >= 1
        assert batch_counts["batches"] >= 1
        assert batch_counts["added"] >= 2
        assert c.store.light_block(20) is not None

    def test_shared_cache_skips_overlap(self, batch_counts):
        """The 1/3-trust check and the 2/3 check of one hop examine
        the same commit; with the sync-wide cache the 2/3 check only
        adds what the trusting pass has not already proved.  4 equal
        validators: trusting stops after 2 sigs (early 1/3 exit), the
        2/3 check cache-hits those and adds exactly 1 more — 3 batch
        entries, not 5."""
        pvs = [new_mock_pv() for _ in range(4)]
        blocks = make_chain(10, lambda h: pvs)

        async def run():
            c, _ = _client(blocks)
            await c.initialize(now=_now())
            await c.verify_to_height(10, now=_now())
        asyncio.run(run())
        assert batch_counts["added"] == 3, batch_counts

    def test_bisection_under_valset_rotation(self, batch_counts):
        """Rotate 1 of 4 validators per height: a straight jump from
        the trust root to the tip has < 1/3 overlap, so the client
        must bisect — and every hop's checks stay on the batch
        seam."""
        pool = [new_mock_pv() for _ in range(16)]

        def pvs_at(h):
            # window of 4 shifting one validator per height
            return [pool[(h + i) % len(pool)] for i in range(4)]

        blocks = make_chain(12, pvs_at)

        async def run():
            c, primary = _client(blocks)
            await c.initialize(now=_now())
            lb = await c.verify_to_height(12, now=_now())
            assert lb.height == 12
            # bisection fetched intermediate pivots
            assert len([r for r in primary.requests
                        if r not in (1, 12)]) > 0
            return c
        c = asyncio.run(run())
        assert batch_counts["batches"] >= 2
        # the trace of verified hops landed in the trusted store
        assert len(c.store.heights()) >= 3

    def test_verify_to_height_equals_verify_light_block(self):
        """Same verdict + stored trace as the unshared-cache path."""
        pvs = [new_mock_pv() for _ in range(4)]
        blocks = make_chain(8, lambda h: pvs)

        async def run():
            c1, _ = _client(blocks)
            await c1.initialize(now=_now())
            a = await c1.verify_to_height(8, now=_now())
            c2, _ = _client(blocks)
            await c2.initialize(now=_now())
            b = await c2.verify_light_block_at_height(8, now=_now())
            assert a.hash() == b.hash()
        asyncio.run(run())

    def test_tampered_target_rejected(self):
        """A structurally consistent forgery (header re-hashed into
        the commit's block id, signatures NOT re-made) must die in
        signature verification — the batch path's verdict."""
        pvs = [new_mock_pv() for _ in range(4)]
        blocks = make_chain(6, lambda h: pvs)
        import dataclasses
        lb = blocks[6]
        hdr = dataclasses.replace(lb.signed_header.header,
                                  app_hash=b"\xEE" * 32)
        old_commit = lb.signed_header.commit
        forged_commit = Commit(
            height=6, round=0,
            block_id=BlockID(hash=hdr.hash(),
                             part_set_header=old_commit
                             .block_id.part_set_header),
            signatures=list(old_commit.signatures))
        blocks[6] = LightBlock(
            signed_header=SignedHeader(header=hdr,
                                       commit=forged_commit),
            validator_set=lb.validator_set)

        from cometbft_tpu.light.verifier import LightClientError

        async def run():
            c, _ = _client(blocks)
            await c.initialize(now=_now())
            with pytest.raises(LightClientError):
                await c.verify_to_height(6, now=_now())
        asyncio.run(run())
