"""Canonical sign-bytes: the exact bytes validators sign.

Reference: types/canonical.go + proto/cometbft/types/v2/canonical.proto.
Height/round are sfixed64 (fixed-size for canonicalization); the BlockID is
dropped entirely for nil votes; sign-bytes are uvarint-length-delimited
(libs/protoio MarshalDelimited).  Byte-identical output is pinned by the
reference's own test vectors (types/vote_test.go TestVoteSignBytesTestVectors)
in tests/test_wire.py.
"""
from __future__ import annotations

from ..wire import pb, marshal_delimited
from .block_id import BlockID
from .timestamp import Timestamp

# SignedMsgType (proto/cometbft/types/v2/types.proto)
UNKNOWN_TYPE = 0
PREVOTE_TYPE = 1
PRECOMMIT_TYPE = 2
PROPOSAL_TYPE = 32


def is_vote_type_valid(t: int) -> bool:
    return t in (PREVOTE_TYPE, PRECOMMIT_TYPE)


def canonicalize_block_id(bid: BlockID) -> dict | None:
    """nil → None (field omitted from sign-bytes); else CanonicalBlockID."""
    if bid.is_nil():
        return None
    d: dict = {"part_set_header": bid.part_set_header.to_proto()}
    if bid.hash:
        d["hash"] = bid.hash
    return d


def _canonical_vote(chain_id: str, type_: int, height: int, round_: int,
                    bid: BlockID, ts: Timestamp) -> dict:
    d: dict = {"timestamp": ts.to_proto()}
    if type_:
        d["type"] = type_
    if height:
        d["height"] = height
    if round_:
        d["round"] = round_
    cbid = canonicalize_block_id(bid)
    if cbid is not None:
        d["block_id"] = cbid
    if chain_id:
        d["chain_id"] = chain_id
    return d


def vote_sign_bytes(chain_id: str, type_: int, height: int, round_: int,
                    bid: BlockID, ts: Timestamp) -> bytes:
    """Reference: types/vote.go VoteSignBytes."""
    return marshal_delimited(
        pb.CANONICAL_VOTE,
        _canonical_vote(chain_id, type_, height, round_, bid, ts))


_MASK64 = (1 << 64) - 1


def _vote_splice_parts():
    """What vote_sign_bytes_template needs beside a vote's own fields:
    CANONICAL_VOTE's descriptor cut before and after the timestamp
    field (split descriptors, not dict filtering: timestamp is
    always=True, so the full descriptor with the field unset would
    still emit an empty submessage into the wrong half), and the two
    varint tables of make(ts).  A varint is cut 14 bits at a time:
    more[c] is chunk c as two bytes with both continuation bits set,
    last[c] the final chunk (one byte below 128, else two)."""
    from ..wire.proto import Msg
    fields = pb.CANONICAL_VOTE.fields
    vote_ok = [f.name for f in fields] == [
        "type", "height", "round", "block_id", "timestamp", "chain_id"]
    ts_ok = fields[4].tag == b"\x2a" and [
        (f.name, f.tag, f.kind) for f in pb.TIMESTAMP.fields] == [
        ("seconds", b"\x08", "int64"), ("nanos", b"\x10", "int32")]
    if not (vote_ok and ts_ok):
        # explicit (not assert): must fail fast even under python -O —
        # a drifted descriptor would otherwise emit wrong sign bytes
        raise ValueError("CANONICAL_VOTE / Timestamp field layout "
                         "drifted; fix the template splice")
    pre = Msg(pb.CANONICAL_VOTE.name + ".pre", *fields[:4])
    suf = Msg(pb.CANONICAL_VOTE.name + ".suf", fields[5])
    more = tuple(bytes((c & 0x7F | 0x80, c >> 7 | 0x80))
                 for c in range(1 << 14))
    last = tuple(bytes((c,)) if c < 0x80 else bytes((c & 0x7F | 0x80, c >> 7))
                 for c in range(1 << 14))
    return pre, suf, more, last


_VOTE_SPLICE = None


def vote_sign_bytes_template(chain_id: str, type_: int, height: int,
                             round_: int, bid: BlockID):
    """Returns make(ts) -> the same bytes as vote_sign_bytes for that
    timestamp.  Canonical proto fields marshal in field-number order
    (type=1, height=2, round=3, block_id=4, timestamp=5, chain_id=6),
    so everything except the timestamp field marshals ONCE, through
    the generic encoder, and each vote splices its own timestamp
    between the two halves — a commit's votes share every signed field
    but the timestamp.

    make(ts) writes the timestamp field itself, with no dict and no
    descriptor: tag 0x2a, length, 0x08 + varint(seconds) when seconds
    is not 0, 0x10 + varint(nanos) when nanos is not 0 (both 0: an
    empty submessage, the field is `always`), negatives as proto's
    ten-byte two's-complement varint.  The field's body is at most 22
    bytes, so its own length is one byte, and the outer length prefix
    (two bytes from a 128-byte body on) is one of 23 heads made here.
    One path for every int64 seconds and int32 nanos; parity with
    vote_sign_bytes is pinned by tests/test_types.py.  Measured (CPU
    sandbox, PR 30): 2.9 us a call through the generic encoder, 0.7 us
    spliced."""
    global _VOTE_SPLICE
    if _VOTE_SPLICE is None:
        _VOTE_SPLICE = _vote_splice_parts()
    pre_desc, suf_desc, more, last = _VOTE_SPLICE
    from ..wire.proto import encode, encode_uvarint
    d = _canonical_vote(chain_id, type_, height, round_, bid,
                        Timestamp(0, 0))
    d.pop("timestamp")
    pre = encode(pre_desc, d)
    suf = encode(suf_desc, d)
    fixed = len(pre) + 2 + len(suf)     # + the field's tag and length
    heads = tuple(encode_uvarint(fixed + n) + pre + b"\x2a" + bytes((n,))
                  for n in range(23))   # 2 x (tag + ten-byte varint) at most

    def make(ts: Timestamp) -> bytes:
        # the two fields are written out one after the other, not in
        # a loop over (tag, value): +0.1 us a call (CPU sandbox)
        seconds, nanos = ts
        parts = []
        if seconds:
            seconds &= _MASK64
            parts.append(b"\x08")
            while seconds > 0x3FFF:
                parts.append(more[seconds & 0x3FFF])
                seconds >>= 14
            parts.append(last[seconds])
        if nanos:
            nanos &= _MASK64
            parts.append(b"\x10")
            while nanos > 0x3FFF:
                parts.append(more[nanos & 0x3FFF])
                nanos >>= 14
            parts.append(last[nanos])
        field = b"".join(parts)
        return heads[len(field)] + field + suf

    # what the native commit walk splices from (native/commit_walk.hpp):
    # heads[len(field)] + field + suf, the field written as above
    make.heads = heads
    make.suf = suf
    return make


def vote_extension_sign_bytes(chain_id: str, height: int, round_: int,
                              extension: bytes) -> bytes:
    """Reference: types/vote.go VoteExtensionSignBytes."""
    d: dict = {}
    if extension:
        d["extension"] = extension
    if height:
        d["height"] = height
    if round_:
        d["round"] = round_
    if chain_id:
        d["chain_id"] = chain_id
    return marshal_delimited(pb.CANONICAL_VOTE_EXTENSION, d)


def proposal_sign_bytes(chain_id: str, height: int, round_: int,
                        pol_round: int, bid: BlockID,
                        ts: Timestamp) -> bytes:
    """Reference: types/proposal.go ProposalSignBytes."""
    d: dict = {"type": PROPOSAL_TYPE, "timestamp": ts.to_proto()}
    if height:
        d["height"] = height
    if round_:
        d["round"] = round_
    if pol_round:
        d["pol_round"] = pol_round
    cbid = canonicalize_block_id(bid)
    if cbid is not None:
        d["block_id"] = cbid
    if chain_id:
        d["chain_id"] = chain_id
    return marshal_delimited(pb.CANONICAL_PROPOSAL, d)
