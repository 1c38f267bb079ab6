"""Types layer tests: validator set rotation (reference golden sequence),
commit construction + verification (single and batch CPU paths), header
hashing, part sets, evidence round-trips.
"""
import random

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.types import canonical
from cometbft_tpu.types.block import (
    Block, ConsensusVersion, Data, Header, make_block,
)
from cometbft_tpu.types.block_id import BlockID
from cometbft_tpu.types.commit import Commit, CommitError, CommitSig
from cometbft_tpu.types.evidence import DuplicateVoteEvidence
from cometbft_tpu.types.part_set import PartSet, PartSetHeader
from cometbft_tpu.types.signature_cache import SignatureCache
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validation import (
    Fraction, NotEnoughVotingPowerError, VerificationError, verify_commit,
    verify_commit_light, verify_commit_light_trusting,
)
from cometbft_tpu.types.validator import Validator
from cometbft_tpu.types.validator_set import ValidatorSet
from cometbft_tpu.types.vote import (
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, Vote,
)


def _val(addr: bytes, power: int) -> Validator:
    return Validator(address=addr, pub_key=None, voting_power=power)


class TestProposerSelection:
    def test_golden_sequence(self):
        """Reference: validator_set_test.go TestProposerSelection1."""
        vset = ValidatorSet([
            _val(b"foo", 1000), _val(b"bar", 300), _val(b"baz", 330)])
        proposers = []
        for _ in range(99):
            proposers.append(vset.get_proposer().address.decode())
            vset.increment_proposer_priority(1)
        expected = (
            "foo baz foo bar foo foo baz foo bar foo foo baz foo foo bar "
            "foo baz foo foo bar foo foo baz foo bar foo foo baz foo bar "
            "foo foo baz foo foo bar foo baz foo foo bar foo baz foo foo "
            "bar foo baz foo foo bar foo baz foo foo foo baz bar foo foo "
            "foo baz foo bar foo foo baz foo bar foo foo baz foo bar foo "
            "foo baz foo bar foo foo baz foo foo bar foo baz foo foo bar "
            "foo baz foo foo bar foo baz foo foo").split()
        assert proposers == expected

    def test_equal_power_order_by_address(self):
        """Reference: TestProposerSelection2 — equal power goes in
        address order."""
        addrs = [bytes(19) + bytes([i]) for i in range(3)]
        vset = ValidatorSet([_val(a, 100) for a in addrs])
        for i in range(15):
            prop = vset.get_proposer()
            assert prop.address == addrs[i % 3], f"round {i}"
            vset.increment_proposer_priority(1)

    def test_priorities_centered(self):
        vset = ValidatorSet([_val(b"a" * 20, 10), _val(b"b" * 20, 20)])
        total = sum(v.proposer_priority for v in vset.validators)
        # centered: |avg| < n
        assert abs(total) < len(vset)

    def test_update_with_change_set(self):
        vset = ValidatorSet([_val(b"a" * 20, 10), _val(b"b" * 20, 20)])
        vset.update_with_change_set([_val(b"c" * 20, 30)])
        assert vset.size() == 3
        assert vset.total_voting_power() == 60
        # removal via zero power
        vset.update_with_change_set(
            [Validator(address=b"a" * 20, pub_key=None, voting_power=0)])
        assert vset.size() == 2
        assert vset.total_voting_power() == 50

    def test_sorted_by_power_desc_then_address(self):
        vset = ValidatorSet([
            _val(b"x" * 20, 10), _val(b"a" * 20, 30), _val(b"m" * 20, 30)])
        powers = [v.voting_power for v in vset.validators]
        assert powers == [30, 30, 10]
        assert vset.validators[0].address == b"a" * 20


def _make_keys(n):
    return [ed25519.gen_priv_key() for _ in range(n)]


def _make_commit_fixture(n=4, power=10, chain_id="test-chain", height=5,
                         absent=(), nil=()):
    privs = _make_keys(n)
    vals = [Validator.new(pk.pub_key(), power) for pk in privs]
    pairs = sorted(zip(vals, privs),
                   key=lambda vp: (-vp[0].voting_power, vp[0].address))
    vals = [p[0] for p in pairs]
    privs = [p[1] for p in pairs]
    vset = ValidatorSet(vals)
    block_id = BlockID(hash=b"\x12" * 32,
                       part_set_header=PartSetHeader(1, b"\x34" * 32))
    sigs = []
    for i, (val, priv) in enumerate(zip(vset.validators, privs)):
        if i in absent:
            sigs.append(CommitSig.absent())
            continue
        bid = BlockID() if i in nil else block_id
        flag = BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT
        ts = Timestamp(1700000000 + i, 0)
        v = Vote(type=canonical.PRECOMMIT_TYPE, height=height, round=0,
                 block_id=bid, timestamp=ts,
                 validator_address=val.address, validator_index=i)
        sig = priv.sign(v.sign_bytes(chain_id))
        sigs.append(CommitSig(block_id_flag=flag,
                              validator_address=val.address,
                              timestamp=ts, signature=sig))
    commit = Commit(height=height, round=0, block_id=block_id,
                    signatures=sigs)
    return chain_id, vset, block_id, height, commit


@pytest.fixture(params=["cpu"])
def backend(request):
    crypto_batch.set_backend(request.param)
    yield request.param
    crypto_batch.set_backend("auto")


class TestVerifyCommit:
    def test_all_signed_ok(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture()
        verify_commit(chain_id, vset, bid, h, commit)

    def test_with_absent_ok(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture(absent=(3,))
        verify_commit(chain_id, vset, bid, h, commit)

    def test_insufficient_power(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture(
            absent=(1, 2, 3))
        with pytest.raises(NotEnoughVotingPowerError):
            verify_commit(chain_id, vset, bid, h, commit)

    def test_nil_votes_do_not_count(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture(nil=(1, 2))
        with pytest.raises(NotEnoughVotingPowerError):
            verify_commit(chain_id, vset, bid, h, commit)

    def test_bad_signature_detected(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture()
        commit.signatures[2].signature = bytes(64)
        with pytest.raises(VerificationError, match="wrong signature"):
            verify_commit(chain_id, vset, bid, h, commit)

    def test_wrong_height(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture()
        with pytest.raises(VerificationError, match="wrong height"):
            verify_commit(chain_id, vset, bid, h + 1, commit)

    def test_light_trusting(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture()
        verify_commit_light_trusting(chain_id, vset, commit,
                                     Fraction(1, 3))

    def test_light_with_cache(self, backend):
        chain_id, vset, bid, h, commit = _make_commit_fixture()
        cache = SignatureCache()
        verify_commit_light(chain_id, vset, bid, h, commit,
                            count_all_signatures=True, cache=cache)
        assert len(cache) == 4
        # second run is fully cached
        verify_commit_light(chain_id, vset, bid, h, commit,
                            count_all_signatures=True, cache=cache)


class TestCommit:
    def test_hash_deterministic(self):
        _, _, bid, h, commit = _make_commit_fixture()
        assert commit.hash() == Commit(
            height=h, round=0, block_id=bid,
            signatures=list(commit.signatures)).hash()

    def test_get_vote_roundtrip_sign_bytes(self):
        chain_id, vset, bid, h, commit = _make_commit_fixture()
        v = commit.get_vote(0)
        assert v.sign_bytes(chain_id) == commit.vote_sign_bytes(chain_id, 0)

    def test_median_time(self):
        chain_id, vset, bid, h, commit = _make_commit_fixture()
        mt = commit.median_time(vset)
        assert mt.seconds in range(1700000000, 1700000004)


class TestHeaderAndBlock:
    def _header(self):
        return Header(
            chain_id="test", height=3, time=Timestamp(1700000000, 0),
            last_block_id=BlockID(hash=b"\x01" * 32,
                                  part_set_header=PartSetHeader(
                                      1, b"\x02" * 32)),
            last_commit_hash=b"\x03" * 32, data_hash=b"\x04" * 32,
            validators_hash=b"\x05" * 32, next_validators_hash=b"\x06" * 32,
            consensus_hash=b"\x07" * 32, app_hash=b"\x08" * 32,
            last_results_hash=b"\x09" * 32, evidence_hash=b"\x0a" * 32,
            proposer_address=b"\x0b" * 20)

    def test_header_hash_deterministic(self):
        h1, h2 = self._header(), self._header()
        assert h1.hash() == h2.hash()
        assert len(h1.hash()) == 32
        h2.height = 4
        assert h1.hash() != h2.hash()

    def test_header_hash_empty_without_validators_hash(self):
        h = self._header()
        h.validators_hash = b""
        assert h.hash() == b""

    def test_block_roundtrip_via_parts(self):
        commit = Commit(
            height=2, round=0,
            block_id=BlockID(hash=b"\x01" * 32,
                             part_set_header=PartSetHeader(1, b"\x02" * 32)),
            signatures=[CommitSig.absent()])
        b = make_block(3, [b"tx1", b"tx2" * 1000], commit, [])
        b.header.chain_id = "test"
        b.header.validators_hash = b"\x05" * 32
        ps = b.make_part_set(1024)
        assert ps.is_complete()
        b2 = Block.from_parts(ps)
        assert b2.header.chain_id == "test"
        assert b2.data.txs == b.data.txs
        assert b2.hash() == b.hash()

    def test_part_set_add_and_verify(self):
        data = bytes(range(256)) * 40
        ps = PartSet.from_data(data, 1024)
        ps2 = PartSet(ps.header())
        for i in range(ps.total):
            assert ps2.add_part(ps.get_part(i))
            assert not ps2.add_part(ps.get_part(i))  # duplicate
        assert ps2.is_complete()
        assert ps2.assemble() == data

    def test_part_set_rejects_corrupt(self):
        from cometbft_tpu.types.part_set import Part, PartSetError
        data = b"\xaa" * 4096
        ps = PartSet.from_data(data, 1024)
        ps2 = PartSet(ps.header())
        good = ps.get_part(0)
        bad = Part(index=0, bytes_=b"\xbb" * 1024, proof=good.proof)
        with pytest.raises(PartSetError):
            ps2.add_part(bad)


class TestEvidence:
    def test_duplicate_vote_evidence(self):
        priv = ed25519.gen_priv_key()
        val = Validator.new(priv.pub_key(), 10)
        vset = ValidatorSet([val])
        bid1 = BlockID(hash=b"\x01" * 32,
                       part_set_header=PartSetHeader(1, b"\x02" * 32))
        bid2 = BlockID(hash=b"\x03" * 32,
                       part_set_header=PartSetHeader(1, b"\x04" * 32))
        votes = []
        for bid in (bid1, bid2):
            v = Vote(type=canonical.PREVOTE_TYPE, height=7, round=0,
                     block_id=bid, timestamp=Timestamp(1700000000, 0),
                     validator_address=val.address, validator_index=0)
            v.signature = priv.sign(v.sign_bytes("test"))
            votes.append(v)
        ev = DuplicateVoteEvidence.new(
            votes[0], votes[1], Timestamp(1700000001, 0), vset)
        ev.validate_basic()
        ev.validate_abci()
        assert ev.height == 7
        assert len(ev.hash()) == 32
        # round-trip
        from cometbft_tpu.types.evidence import evidence_from_proto_wrapped
        ev2 = evidence_from_proto_wrapped(ev.to_proto_wrapped())
        assert ev2.hash() == ev.hash()


class TestValidatorSetHash:
    def test_hash_changes_with_power(self):
        privs = _make_keys(3)
        vset1 = ValidatorSet(
            [Validator.new(p.pub_key(), 10) for p in privs])
        vset2 = ValidatorSet(
            [Validator.new(p.pub_key(), 11) for p in privs])
        assert vset1.hash() != vset2.hash()
        assert len(vset1.hash()) == 32

    def test_proto_roundtrip(self):
        privs = _make_keys(3)
        vset = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
        vset2 = ValidatorSet.from_proto(vset.to_proto())
        assert vset2.hash() == vset.hash()
        assert vset2.proposer.address == vset.proposer.address


class TestVoteSignBytesTemplate:
    def test_template_matches_full_marshal_across_flags_and_times(self):
        """commit.vote_sign_bytes's template-splice fast path must be
        byte-for-byte the canonical Vote.sign_bytes marshal for every
        flag variant and timestamp shape (incl. zero nanos / zero
        seconds edge encodings)."""
        bid = BlockID(hash=b"\x9a" * 32,
                      part_set_header=PartSetHeader(3, b"\xbc" * 32))
        times = [Timestamp(1700000000, 0), Timestamp(1700000000, 1),
                 Timestamp(0, 0), Timestamp(1, 999_999_999),
                 Timestamp(2**31, 5)]
        sigs = []
        for i, ts in enumerate(times):
            flag = (BLOCK_ID_FLAG_COMMIT if i % 3 != 1
                    else BLOCK_ID_FLAG_NIL)
            sigs.append(CommitSig(block_id_flag=flag,
                                  validator_address=bytes([i]) * 20,
                                  timestamp=ts, signature=b"\x01" * 64))
        commit = Commit(height=42, round=3, block_id=bid,
                        signatures=sigs)
        for chain in ("tmpl-chain", ""):
            for i in range(len(sigs)):
                want = commit.get_vote(i).sign_bytes(chain)
                got = commit.vote_sign_bytes(chain, i)
                assert got == want, (chain, i)


    _SECONDS = (0, 1, 127, 128, 2**31, 2**35, 2**62, -1, -2**40)
    _NANOS = (0, 1, 127, 128, 16_383, 16_384, 999_999_999, -1)
    _CHAINS = ("", "chain-009", "c" * 30, "c" * 50)
    _BID = BlockID(hash=b"\x9a" * 32,
                   part_set_header=PartSetHeader(3, b"\xbc" * 32))

    @staticmethod
    def _commit(flag, round_, ts):
        return Commit(
            height=42, round=round_, block_id=TestVoteSignBytesTemplate._BID,
            signatures=[CommitSig(block_id_flag=flag,
                                  validator_address=b"\x07" * 20,
                                  timestamp=ts, signature=b"\x01" * 64)])

    @pytest.mark.parametrize("round_", (0, 3))
    @pytest.mark.parametrize("chain", _CHAINS,
                             ids=[str(len(c)) for c in _CHAINS])
    @pytest.mark.parametrize("flag",
                             (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL))
    @pytest.mark.parametrize("nanos", _NANOS)
    @pytest.mark.parametrize("seconds", _SECONDS)
    def test_splice_parity(self, seconds, nanos, flag, chain, round_):
        """The dedicated timestamp encoder against the generic one
        (Vote.sign_bytes -> canonical.vote_sign_bytes ->
        wire.proto.encode), where each varint changes length, the
        field is empty, a value is negative (ten-byte varint) and the
        body's own length prefix goes from one byte to two."""
        commit = self._commit(flag, round_, Timestamp(seconds, nanos))
        want = commit.get_vote(0).sign_bytes(chain)
        got = commit.vote_sign_bytes(chain, 0)
        assert type(got) is bytes and got == want
        make = commit.vote_sign_bytes_maker(chain, commit.signatures[0])
        assert make(Timestamp(seconds, nanos)) == want

    def test_splice_parity_grid_crosses_the_two_byte_prefix(self):
        """The grid above holds bodies of exactly 127 and 128 bytes
        (the outer uvarint goes to two bytes there) and every
        timestamp-field length from empty to 2 x (tag + ten bytes)."""
        from cometbft_tpu.wire.proto import decode_uvarint

        def body_len(ts, chain):
            sb = self._commit(BLOCK_ID_FLAG_COMMIT, 0,
                              ts).vote_sign_bytes(chain, 0)
            n, pos = decode_uvarint(sb, 0)
            assert pos + n == len(sb) and pos == (1 if n < 128 else 2)
            return n

        bodies, fields = set(), set()
        for chain in self._CHAINS:
            empty = body_len(Timestamp(0, 0), chain)
            for seconds in self._SECONDS:
                for nanos in self._NANOS:
                    n = body_len(Timestamp(seconds, nanos), chain)
                    bodies.add(n)
                    fields.add(n - empty)
        assert {127, 128} <= bodies
        assert min(fields) == 0 and max(fields) == 22

    def test_splice_parity_seeded_sweep(self):
        """2,000 seeded timestamps over the whole int64 x int32 range
        and the live range (seconds ~1.7e9, nanos < 1e9)."""
        rng = random.Random(30)
        commit = self._commit(BLOCK_ID_FLAG_COMMIT, 1, Timestamp(0, 0))
        make = commit.vote_sign_bytes_maker("sweep", commit.signatures[0])
        for i in range(2000):
            if i % 2:
                ts = Timestamp(rng.randrange(-2**63, 2**63),
                               rng.randrange(-2**31, 2**31))
            else:
                ts = Timestamp(1_700_000_000 + rng.randrange(10**6),
                               rng.randrange(10**9))
            assert make(ts) == canonical.vote_sign_bytes(
                "sweep", canonical.PRECOMMIT_TYPE, 42, 1, self._BID,
                ts), ts

    def test_unknown_flag_still_raises_every_call(self):
        """An unknown block-id flag is refused by CommitSig.block_id
        before a template is made, and is never memoised."""
        commit = self._commit(9, 0, Timestamp(1, 1))
        for _ in range(2):
            with pytest.raises(CommitError, match="unknown BlockIDFlag"):
                commit.vote_sign_bytes("c", 0)
        assert not commit.__dict__.get("_vsb_tmpls")


class _RecordingVerifier(crypto_batch.BatchVerifier):
    """The CPU verifier, keeping what the walk hands it."""

    def __init__(self):
        self.inner = ed25519.CpuBatchVerifier()
        self.triples = []

    def add(self, pub_key, msg, sig):
        self.triples.append((pub_key.bytes(), msg, sig))
        self.inner.add(pub_key, msg, sig)

    def extend_raw(self, triples):
        self.triples.extend(triples)
        self.inner.extend_raw(triples)

    def verify(self):
        return self.inner.verify()


class TestWalkDecidesTheSame:
    """What the walk (the native walk where it engages, PR 38) hands
    the batch verifier, against the plain reference: Vote.sign_bytes
    of get_vote(idx), index by index."""

    CHAIN = "walk-chain-1k"
    N = 1000

    @pytest.fixture(scope="class")
    def signed(self):
        rng = random.Random(3030)
        privs = [ed25519.gen_priv_key_from_secret(b"walk-%d" % i)
                 for i in range(self.N)]
        vset = ValidatorSet([Validator.new(p.pub_key(), rng.randrange(1, 50))
                             for p in privs])
        by_addr = {p.pub_key().address(): p for p in privs}
        bid = BlockID(hash=b"\x12" * 32,
                      part_set_header=PartSetHeader(1, b"\x34" * 32))
        commit = Commit(height=77, round=2, block_id=bid, signatures=[])
        for i, val in enumerate(vset.validators):
            roll = rng.random()
            if roll < 0.03:
                commit.signatures.append(CommitSig.absent())
                continue
            flag = BLOCK_ID_FLAG_NIL if roll < 0.08 else BLOCK_ID_FLAG_COMMIT
            commit.signatures.append(CommitSig(
                block_id_flag=flag, validator_address=val.address,
                timestamp=Timestamp(1_700_000_000 + rng.randrange(3),
                                    rng.randrange(10**9))))
            commit.signatures[i].signature = by_addr[val.address].sign(
                commit.get_vote(i).sign_bytes(self.CHAIN))
        return vset, bid, commit

    def _reference(self, vset, commit, light):
        """(pub, msg, sig) in walk order; light stops past 2/3."""
        needed = vset.total_voting_power() * 2 // 3
        out, tallied = [], 0
        for i, cs in enumerate(commit.signatures):
            if cs.block_id_flag == BLOCK_ID_FLAG_ABSENT or (
                    light and cs.block_id_flag != BLOCK_ID_FLAG_COMMIT):
                continue
            out.append((vset.validators[i].pub_key.bytes(),
                        commit.get_vote(i).sign_bytes(self.CHAIN),
                        cs.signature))
            tallied += vset.validators[i].voting_power
            if light and tallied > needed:
                break
        return out

    def _verify(self, monkeypatch, light, vset, bid, commit):
        made = []

        def create(_pub_key):
            made.append(_RecordingVerifier())
            return made[-1]

        monkeypatch.setattr(crypto_batch, "create_batch_verifier", create)
        verify = verify_commit_light if light else verify_commit
        try:
            verify(self.CHAIN, vset, bid, commit.height, commit)
        finally:
            assert len(made) == 1
        return made[0].triples

    @pytest.mark.parametrize("light", (True, False),
                             ids=("verify_commit_light", "verify_commit"))
    def test_triples_equal_the_reference(self, monkeypatch, signed, light):
        vset, bid, commit = signed
        want = self._reference(vset, commit, light)
        got = self._verify(monkeypatch, light, vset, bid, commit)
        assert len(got) == len(want)
        assert got == want
        assert all(type(msg) is bytes for _, msg, _ in got)
        if light:
            assert len(got) < sum(
                cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
                for cs in commit.signatures)     # the early exit held

    @pytest.mark.parametrize("light", (True, False),
                             ids=("verify_commit_light", "verify_commit"))
    def test_lowest_forged_index_is_named(self, monkeypatch, signed, light):
        vset, bid, commit = signed
        commits = [i for i, cs in enumerate(commit.signatures)
                   if cs.block_id_flag == BLOCK_ID_FLAG_COMMIT]
        low, high = commits[40], commits[300]
        forged = Commit(height=commit.height, round=commit.round,
                        block_id=bid, signatures=list(commit.signatures))
        for i in (high, low):
            cs = commit.signatures[i]
            forged.signatures[i] = CommitSig(
                block_id_flag=cs.block_id_flag,
                validator_address=cs.validator_address,
                timestamp=cs.timestamp,
                signature=cs.signature[:-1] + bytes([cs.signature[-1] ^ 1]))
        with pytest.raises(VerificationError,
                           match=rf"wrong signature \(#{low}\)"):
            self._verify(monkeypatch, light, vset, bid, forged)
