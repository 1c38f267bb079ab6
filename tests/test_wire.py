"""Wire codec tests, pinned to the reference's own sign-bytes test vectors.

Golden vectors from types/vote_test.go TestVoteSignBytesTestVectors and
the CanonicalVoteExtension schema.
"""
import pytest

from cometbft_tpu.types.block_id import BlockID
from cometbft_tpu.types.commit import Commit, CommitSig
from cometbft_tpu.types.part_set import PartSetHeader
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.vote import BLOCK_ID_FLAG_NIL, Vote
from cometbft_tpu.types import canonical
from cometbft_tpu.wire import pb, encode, decode, marshal_delimited


ZERO_TS = Timestamp.zero()


def _vote_sign_bytes(chain_id, **kw):
    v = Vote(**kw)
    return v.sign_bytes(chain_id)


class TestVoteSignBytesGoldenVectors:
    """Byte-exact vectors from reference types/vote_test.go:67-165."""

    def test_empty_vote(self):
        want = bytes([0xd, 0x2a, 0xb, 0x8, 0x80, 0x92, 0xb8, 0xc3, 0x98,
                      0xfe, 0xff, 0xff, 0xff, 0x1])
        assert _vote_sign_bytes("") == want

    def test_precommit(self):
        want = bytes([
            0x21,
            0x8, 0x2,
            0x11, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x19, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x2a, 0xb, 0x8, 0x80, 0x92, 0xb8, 0xc3, 0x98, 0xfe, 0xff,
            0xff, 0xff, 0x1,
        ])
        assert _vote_sign_bytes(
            "", height=1, round=1,
            type=canonical.PRECOMMIT_TYPE) == want

    def test_prevote(self):
        want = bytes([
            0x21,
            0x8, 0x1,
            0x11, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x19, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x2a, 0xb, 0x8, 0x80, 0x92, 0xb8, 0xc3, 0x98, 0xfe, 0xff,
            0xff, 0xff, 0x1,
        ])
        assert _vote_sign_bytes("", height=1, round=1,
                                type=canonical.PREVOTE_TYPE) == want

    def test_no_type(self):
        want = bytes([
            0x1f,
            0x11, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x19, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x2a, 0xb, 0x8, 0x80, 0x92, 0xb8, 0xc3, 0x98, 0xfe, 0xff,
            0xff, 0xff, 0x1,
        ])
        assert _vote_sign_bytes("", height=1, round=1) == want

    def test_with_chain_id(self):
        want = bytes([
            0x2e,
            0x11, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x19, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0,
            0x2a, 0xb, 0x8, 0x80, 0x92, 0xb8, 0xc3, 0x98, 0xfe, 0xff,
            0xff, 0xff, 0x1,
            0x32, 0xd, 0x74, 0x65, 0x73, 0x74, 0x5f, 0x63, 0x68, 0x61,
            0x69, 0x6e, 0x5f, 0x69, 0x64,
        ])
        assert _vote_sign_bytes("test_chain_id", height=1, round=1) == want

    def test_extension_not_in_vote_sign_bytes(self):
        # vector 5: extension does not change vote sign-bytes
        a = _vote_sign_bytes("test_chain_id", height=1, round=1)
        b = _vote_sign_bytes("test_chain_id", height=1, round=1,
                             extension=b"extension")
        assert a == b


# the inputs of the vectors above: (chain id, Vote fields)
_VECTOR_INPUTS = [
    ("", {}),
    ("", {"height": 1, "round": 1, "type": canonical.PRECOMMIT_TYPE}),
    ("", {"height": 1, "round": 1, "type": canonical.PREVOTE_TYPE}),
    ("", {"height": 1, "round": 1}),
    ("test_chain_id", {"height": 1, "round": 1}),
]


class TestVoteSignBytesVectorsSpliced:
    """The same inputs through the commit's spliced template
    (canonical.vote_sign_bytes_template, a dedicated timestamp
    encoder) must give the bytes the vectors pin for Vote.sign_bytes."""

    @pytest.mark.parametrize("chain_id,kw", _VECTOR_INPUTS)
    def test_template(self, chain_id, kw):
        v = Vote(**kw)
        make = canonical.vote_sign_bytes_template(
            chain_id, v.type, v.height, v.round, v.block_id)
        got = make(v.timestamp)
        assert type(got) is bytes and got == v.sign_bytes(chain_id)

    @pytest.mark.parametrize("chain_id,kw", _VECTOR_INPUTS)
    def test_one_signature_commit(self, chain_id, kw):
        """A Commit's votes are precommits: the vector's height, round,
        nil block id and Go-zero timestamp under that type (the
        precommit vector is then the pinned bytes themselves)."""
        v = Vote(**{**kw, "type": canonical.PRECOMMIT_TYPE})
        commit = Commit(
            height=v.height, round=v.round,
            signatures=[CommitSig(block_id_flag=BLOCK_ID_FLAG_NIL,
                                  validator_address=b"\x01" * 20,
                                  timestamp=v.timestamp,
                                  signature=b"\x02" * 64)])
        got = commit.vote_sign_bytes(chain_id, 0)
        assert type(got) is bytes and got == v.sign_bytes(chain_id)
        assert got == commit.get_vote(0).sign_bytes(chain_id)


class TestRoundTrip:
    def test_vote_roundtrip(self):
        v = Vote(
            type=canonical.PRECOMMIT_TYPE, height=12345, round=2,
            block_id=BlockID(hash=b"\xab" * 32,
                             part_set_header=PartSetHeader(3, b"\xcd" * 32)),
            timestamp=Timestamp(1700000000, 123456789),
            validator_address=b"\x11" * 20, validator_index=7,
            signature=b"\x22" * 64, extension=b"ext",
            extension_signature=b"\x33" * 64,
        )
        raw = encode(pb.VOTE, v.to_proto())
        v2 = Vote.from_proto(decode(pb.VOTE, raw))
        assert v == v2

    def test_negative_int_roundtrip(self):
        d = {"pol_round": -1, "type": 32,
             "timestamp": ZERO_TS.to_proto()}
        raw = encode(pb.CANONICAL_PROPOSAL, d)
        back = decode(pb.CANONICAL_PROPOSAL, raw)
        assert back["pol_round"] == -1

    def test_unknown_field_skipped(self):
        # encode a Vote, decode as CommitSig-shaped desc missing most fields
        v = Vote(type=1, height=5, round=0, timestamp=ZERO_TS,
                 validator_address=b"\x01" * 20, signature=b"\x02" * 64)
        raw = encode(pb.VOTE, v.to_proto())
        got = decode(pb.COMMIT_SIG, raw)  # overlapping field numbers differ
        assert isinstance(got, dict)

    def test_timestamp_zero_value(self):
        assert Timestamp.zero().to_proto() == {"seconds": -62135596800}
        assert encode(pb.TIMESTAMP, Timestamp.zero().to_proto()) == bytes(
            [0x8, 0x80, 0x92, 0xb8, 0xc3, 0x98, 0xfe, 0xff, 0xff, 0xff,
             0x1])


class TestTimestamp:
    def test_rfc3339(self):
        ts = Timestamp(1700000000, 500000000)
        assert ts.rfc3339() == "2023-11-14T22:13:20.5Z"
        assert Timestamp.from_rfc3339(ts.rfc3339()) == ts

    def test_rfc3339_no_frac(self):
        ts = Timestamp(1700000000, 0)
        assert ts.rfc3339() == "2023-11-14T22:13:20Z"
        assert Timestamp.from_rfc3339(ts.rfc3339()) == ts


class TestVoteExtensionSignBytes:
    def test_shape(self):
        b = canonical.vote_extension_sign_bytes("chain", 3, 1, b"ext")
        # length-prefixed; decodable
        from cometbft_tpu.wire import unmarshal_delimited
        d, n = unmarshal_delimited(pb.CANONICAL_VOTE_EXTENSION, b)
        assert n == len(b)
        assert d == {"extension": b"ext", "height": 3, "round": 1,
                     "chain_id": "chain"}
