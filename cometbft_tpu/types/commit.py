"""Commit and ExtendedCommit: the evidence a block was committed.

Reference: types/block.go:634-1300 — CommitSig (one slot per validator,
flag Absent/Commit/Nil), Commit.Hash (merkle over CommitSig proto bytes),
GetVote/VoteSignBytes reconstruction, BFT-time MedianTime.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..crypto import merkle
from ..libs.bits import BitArray
from ..wire import pb, encode
from .block_id import BlockID
from .timestamp import Timestamp
from .vote import (
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL,
    MAX_SIGNATURE_SIZE, Vote,
)
from . import canonical


_VALID_FLAGS = (BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT,
                BLOCK_ID_FLAG_NIL)


class CommitError(Exception):
    pass


@dataclass
class CommitSig:
    block_id_flag: int = BLOCK_ID_FLAG_ABSENT
    validator_address: bytes = b""
    timestamp: Timestamp = field(default_factory=Timestamp.zero)
    signature: bytes = b""

    @classmethod
    def absent(cls) -> "CommitSig":
        """Reference: NewCommitSigAbsent — validator did not sign.

        Timestamp is the Go zero time so CommitSig proto bytes (and hence
        Commit.Hash) match the reference byte-for-byte."""
        return cls(block_id_flag=BLOCK_ID_FLAG_ABSENT,
                   timestamp=Timestamp.zero())

    def for_block(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_COMMIT

    def absent_flag(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """The BlockID this sig signed over (reference: CommitSig.BlockID)."""
        if self.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            return commit_block_id
        if self.block_id_flag in (BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_NIL):
            return BlockID()
        raise CommitError(f"unknown BlockIDFlag {self.block_id_flag}")

    def validate_basic(self) -> None:
        if self.block_id_flag not in _VALID_FLAGS:
            raise CommitError(f"unknown BlockIDFlag {self.block_id_flag}")
        if self.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            if self.validator_address:
                raise CommitError("validator address is present")
            if not (self.timestamp == Timestamp(0, 0) or
                    self.timestamp.is_zero()):
                raise CommitError("time is present")
            if self.signature:
                raise CommitError("signature is present")
        else:
            if len(self.validator_address) != 20:
                raise CommitError("wrong validator address size")
            if not self.signature:
                raise CommitError("signature is missing")
            if len(self.signature) > MAX_SIGNATURE_SIZE:
                raise CommitError("signature is too big")

    def to_proto(self) -> dict:
        d: dict = {"timestamp": self.timestamp.to_proto()}
        if self.block_id_flag:
            d["block_id_flag"] = self.block_id_flag
        if self.validator_address:
            d["validator_address"] = self.validator_address
        if self.signature:
            d["signature"] = self.signature
        return d

    @classmethod
    def from_proto(cls, d: dict) -> "CommitSig":
        return cls(
            block_id_flag=d.get("block_id_flag", 0),
            validator_address=d.get("validator_address", b""),
            timestamp=Timestamp.from_proto(d.get("timestamp") or {}),
            signature=d.get("signature", b""),
        )


@dataclass
class Commit:
    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    signatures: list[CommitSig] = field(default_factory=list)
    _hash: bytes | None = field(default=None, repr=False, compare=False)

    def size(self) -> int:
        return len(self.signatures)

    def get_vote(self, val_idx: int) -> Vote:
        """Reconstruct the precommit Vote of validator val_idx.

        Reference: block.go GetVote (:898)."""
        cs = self.signatures[val_idx]
        return Vote(
            type=canonical.PRECOMMIT_TYPE,
            height=self.height,
            round=self.round,
            block_id=cs.block_id(self.block_id),
            timestamp=cs.timestamp,
            validator_address=cs.validator_address,
            validator_index=val_idx,
            signature=cs.signature,
        )

    def vote_sign_bytes_maker(self, chain_id: str, cs: CommitSig):
        """make(ts) -> the canonical signed bytes of a vote of this
        commit with cs's block-id flag and timestamp ts.

        A commit's votes share every signed field except the
        timestamp (and the block-id variant selected by the flag), so
        the canonical marshal runs once per (chain id, flag) and each
        vote splices its timestamp
        (canonical.vote_sign_bytes_template).  A loop over the
        signatures takes the maker once a flag and calls it a vote
        (validation._walk_commit).  The memo assumes commits are not
        mutated in place after first use (nothing does; tests that
        rebuild signatures replace whole CommitSig objects, and the
        timestamp/flag are part of the lookup)."""
        tmpls = self.__dict__.setdefault("_vsb_tmpls", {})
        key = (chain_id, cs.block_id_flag)
        make = tmpls.get(key)
        if make is None:
            make = tmpls[key] = canonical.vote_sign_bytes_template(
                chain_id, canonical.PRECOMMIT_TYPE, self.height,
                self.round, cs.block_id(self.block_id))
        return make

    def vote_sign_bytes(self, chain_id: str, val_idx: int) -> bytes:
        """Canonical signed bytes of validator val_idx's vote, spliced
        from the commit's template (vote_sign_bytes_maker): 1.1 us a
        call where Vote.sign_bytes of get_vote(val_idx) takes about 9
        and this method took 3.0 through the generic encoder (CPU
        sandbox, PR 30); byte-for-byte parity with the Vote path is
        pinned in tests/test_types.py.

        Reference: block.go VoteSignBytes (:921)."""
        cs = self.signatures[val_idx]
        return self.vote_sign_bytes_maker(chain_id, cs)(cs.timestamp)

    def validate_basic(self) -> None:
        if self.height < 0:
            raise CommitError("negative Height")
        if self.round < 0:
            raise CommitError("negative Round")
        if self.height >= 1:
            if self.block_id.is_nil():
                raise CommitError("commit cannot be for nil block")
            if not self.signatures:
                raise CommitError("no signatures in commit")
            for i, cs in enumerate(self.signatures):
                try:
                    cs.validate_basic()
                except CommitError as e:
                    raise CommitError(f"wrong CommitSig #{i}: {e}") from e

    def hash(self) -> bytes:
        """Merkle root over CommitSig proto bytes (reference: :988)."""
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [encode(pb.COMMIT_SIG, cs.to_proto())
                 for cs in self.signatures])
        return self._hash

    def median_time(self, validators) -> Timestamp:
        """Voting-power-weighted median of commit vote timestamps (BFT time).

        Reference: block.go MedianTime (:968), types/time WeightedMedian."""
        weighted: list[tuple[Timestamp, int]] = []
        total_power = 0
        for cs in self.signatures:
            if cs.absent_flag():
                continue
            _, val = validators.get_by_address(cs.validator_address)
            if val is not None:
                total_power += val.voting_power
                weighted.append((cs.timestamp, val.voting_power))
        median = total_power // 2
        weighted.sort(key=lambda wt: wt[0].unix_ns())
        for ts, w in weighted:
            if median < w:
                return ts
            median -= w
        return Timestamp(0, 0)

    def to_proto(self) -> dict:
        d: dict = {"block_id": self.block_id.to_proto(),
                   "signatures": [cs.to_proto() for cs in self.signatures]}
        if self.height:
            d["height"] = self.height
        if self.round:
            d["round"] = self.round
        return d

    @classmethod
    def from_proto(cls, d: dict) -> "Commit":
        return cls(
            height=d.get("height", 0),
            round=d.get("round", 0),
            block_id=BlockID.from_proto(d.get("block_id") or {}),
            signatures=[CommitSig.from_proto(s)
                        for s in d.get("signatures", [])],
        )

    def wrapped_extended_commit(self) -> "ExtendedCommit":
        """Wrap as an ExtendedCommit with empty extensions (reference:
        :1013)."""
        return ExtendedCommit(
            height=self.height, round=self.round, block_id=self.block_id,
            extended_signatures=[
                ExtendedCommitSig(
                    block_id_flag=cs.block_id_flag,
                    validator_address=cs.validator_address,
                    timestamp=cs.timestamp, signature=cs.signature)
                for cs in self.signatures])


@dataclass
class AggregateCommit:
    """One BLS signature + a signer bitmap for a whole commit
    (TPU-native extension; docs/aggregate_commits.md).

    In aggregate-commit mode every precommit FOR a block signs the
    same canonical message — the zero-timestamp canonical precommit
    over (chain_id, height, round, block_id) — so the signatures sum
    in G2 and verification is one 2-Miller-loop pairing check
    regardless of validator count.  Bit i of ``signers`` means
    validator index i (in the height's validator set) precommitted
    the block; nil and absent precommits are simply unset (their
    signatures cover different messages and cannot be aggregated in).

    There is no per-vote timestamp, so BFT time's weighted median is
    unavailable: consensus params require PBTS at or before the
    aggregate enable height (types/params.py FeatureParams.validate).
    """
    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    signers: BitArray = field(default_factory=lambda: BitArray(0))
    signature: bytes = b""
    _hash: bytes | None = field(default=None, repr=False, compare=False)

    BLS_SIGNATURE_SIZE = 96

    def size(self) -> int:
        """Validator slots covered (= validator-set size), matching
        Commit.size() so shared size checks work on either kind."""
        return self.signers.size()

    def signed_indices(self) -> list[int]:
        return self.signers.true_indices()

    def signers_bytes(self) -> bytes:
        """Canonical wire form of the bitmap: little-endian packed,
        (size+7)//8 bytes, padding bits zero."""
        return self.signers.to_le_bytes()

    def vote_sign_bytes(self, chain_id: str) -> bytes:
        """THE message every aggregated precommit signed: canonical
        precommit with the zero timestamp (consensus/state.py signs
        precommits with a zero timestamp once aggregate mode is
        enabled, so all signers share one sign-bytes message)."""
        return canonical.vote_sign_bytes(
            chain_id, canonical.PRECOMMIT_TYPE, self.height, self.round,
            self.block_id, Timestamp.zero())

    def validate_basic(self) -> None:
        if self.height < 0:
            raise CommitError("negative Height")
        if self.round < 0:
            raise CommitError("negative Round")
        if self.height >= 1:
            if self.block_id.is_nil():
                raise CommitError(
                    "aggregate commit cannot be for nil block")
            if self.signers.size() == 0:
                raise CommitError("no validator slots in "
                                  "aggregate commit")
            if self.signers.is_empty():
                raise CommitError("no signers in aggregate commit")
            if len(self.signature) != self.BLS_SIGNATURE_SIZE:
                raise CommitError(
                    f"aggregate signature must be "
                    f"{self.BLS_SIGNATURE_SIZE} bytes, "
                    f"got {len(self.signature)}")

    def hash(self) -> bytes:
        """Merkle leaf hash over the proto bytes (the aggregate
        analogue of Commit.hash's merkle over CommitSig protos)."""
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [encode(pb.AGGREGATE_COMMIT, self.to_proto())])
        return self._hash

    def median_time(self, validators) -> Timestamp:
        """Aggregate commits carry no per-vote timestamps; BFT time is
        never computed for them (PBTS is required by params
        validation).  Reaching this is a wiring bug, not a data
        error."""
        raise CommitError(
            "aggregate commit has no per-vote timestamps (BFT time "
            "requires per-signature commits; enable PBTS)")

    def to_proto(self) -> dict:
        d: dict = {"block_id": self.block_id.to_proto()}
        if self.height:
            d["height"] = self.height
        if self.round:
            d["round"] = self.round
        if self.signers.size():
            d["signer_count"] = self.signers.size()
        sb = self.signers_bytes()
        if sb:
            d["signers"] = sb
        if self.signature:
            d["signature"] = self.signature
        return d

    @classmethod
    def from_proto(cls, d: dict) -> "AggregateCommit":
        count = d.get("signer_count", 0)
        try:
            ba = BitArray.from_le_bytes(d.get("signers", b""), count)
        except ValueError as e:
            raise CommitError(f"signer bitmap: {e}") from None
        return cls(
            height=d.get("height", 0),
            round=d.get("round", 0),
            block_id=BlockID.from_proto(d.get("block_id") or {}),
            signers=ba,
            signature=d.get("signature", b""),
        )

    @classmethod
    def from_commit(cls, commit: Commit) -> "AggregateCommit":
        """Aggregate a per-signature commit's FOR-block signatures
        (the proposer path: the precommit vote set is materialized as
        a Commit first, then aggregated — O(n) G2 adds through the
        native batched-inversion tree).  All COMMIT-flag signatures
        must be BLS; nil/absent slots stay unset."""
        from ..crypto import bls12381
        ba = BitArray(len(commit.signatures))
        sigs = []
        for i, cs in enumerate(commit.signatures):
            if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
                continue
            if len(cs.signature) != cls.BLS_SIGNATURE_SIZE:
                raise CommitError(
                    f"commit sig #{i} is not a BLS signature "
                    f"({len(cs.signature)} bytes)")
            ba.set_index(i, True)
            sigs.append(cs.signature)
        if not sigs:
            raise CommitError("no FOR-block signatures to aggregate")
        try:
            agg = bls12381.aggregate(sigs)
        except ValueError as e:
            raise CommitError(f"cannot aggregate commit: {e}") from e
        return cls(height=commit.height, round=commit.round,
                   block_id=commit.block_id, signers=ba, signature=agg)


@dataclass
class ExtendedCommitSig(CommitSig):
    extension: bytes = b""
    extension_signature: bytes = b""
    non_rp_extension: bytes = b""
    non_rp_extension_signature: bytes = b""

    def ensure_extension(self, ext_enabled: bool) -> None:
        """Reference: block.go EnsureExtension (:791) — BOTH signatures
        (replay-protected and non-RP) required on COMMIT entries."""
        if ext_enabled:
            if self.block_id_flag == BLOCK_ID_FLAG_COMMIT and \
                    (not self.extension_signature or
                     not self.non_rp_extension_signature):
                raise CommitError(
                    "vote extension signature missing with extensions "
                    "enabled")
            if self.block_id_flag != BLOCK_ID_FLAG_COMMIT and \
                    (self.extension or self.non_rp_extension or
                     self.extension_signature or
                     self.non_rp_extension_signature):
                raise CommitError(
                    "non-commit vote extension (signature) present")
        else:
            if self.extension or self.extension_signature or \
                    self.non_rp_extension or self.non_rp_extension_signature:
                raise CommitError(
                    "vote extension present with extensions disabled")

    def to_proto(self) -> dict:
        d = super().to_proto()
        if self.extension:
            d["extension"] = self.extension
        if self.extension_signature:
            d["extension_signature"] = self.extension_signature
        if self.non_rp_extension:
            d["non_rp_extension"] = self.non_rp_extension
        if self.non_rp_extension_signature:
            d["non_rp_extension_signature"] = self.non_rp_extension_signature
        return d

    @classmethod
    def from_proto(cls, d: dict) -> "ExtendedCommitSig":
        return cls(
            block_id_flag=d.get("block_id_flag", 0),
            validator_address=d.get("validator_address", b""),
            timestamp=Timestamp.from_proto(d.get("timestamp") or {}),
            signature=d.get("signature", b""),
            extension=d.get("extension", b""),
            extension_signature=d.get("extension_signature", b""),
            non_rp_extension=d.get("non_rp_extension", b""),
            non_rp_extension_signature=d.get(
                "non_rp_extension_signature", b""),
        )


@dataclass
class ExtendedCommit:
    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    extended_signatures: list[ExtendedCommitSig] = field(
        default_factory=list)

    def size(self) -> int:
        return len(self.extended_signatures)

    def is_commit(self) -> bool:
        return len(self.extended_signatures) != 0

    def to_commit(self) -> Commit:
        """Strip extensions (reference: block.go ToCommit :1184)."""
        return Commit(
            height=self.height, round=self.round, block_id=self.block_id,
            signatures=[
                CommitSig(block_id_flag=ecs.block_id_flag,
                          validator_address=ecs.validator_address,
                          timestamp=ecs.timestamp,
                          signature=ecs.signature)
                for ecs in self.extended_signatures])

    def get_extended_vote(self, val_idx: int) -> Vote:
        """Reference: block.go GetExtendedVote (:1200)."""
        ecs = self.extended_signatures[val_idx]
        return Vote(
            type=canonical.PRECOMMIT_TYPE,
            height=self.height, round=self.round,
            block_id=ecs.block_id(self.block_id),
            timestamp=ecs.timestamp,
            validator_address=ecs.validator_address,
            validator_index=val_idx,
            signature=ecs.signature,
            extension=ecs.extension,
            extension_signature=ecs.extension_signature,
            non_rp_extension=ecs.non_rp_extension,
            non_rp_extension_signature=ecs.non_rp_extension_signature,
        )

    def ensure_extensions(self, ext_enabled: bool) -> None:
        for ecs in self.extended_signatures:
            ecs.ensure_extension(ext_enabled)

    def validate_basic(self) -> None:
        if self.height < 0:
            raise CommitError("negative Height")
        if self.round < 0:
            raise CommitError("negative Round")
        if self.height >= 1:
            if self.block_id.is_nil():
                raise CommitError("extended commit cannot be for nil block")
            if not self.extended_signatures:
                raise CommitError("no signatures in commit")
            for i, ecs in enumerate(self.extended_signatures):
                try:
                    ecs.validate_basic()
                except CommitError as e:
                    raise CommitError(
                        f"wrong ExtendedCommitSig #{i}: {e}") from e

    def to_proto(self) -> dict:
        d: dict = {
            "block_id": self.block_id.to_proto(),
            "extended_signatures": [ecs.to_proto()
                                    for ecs in self.extended_signatures],
        }
        if self.height:
            d["height"] = self.height
        if self.round:
            d["round"] = self.round
        return d

    @classmethod
    def from_proto(cls, d: dict) -> "ExtendedCommit":
        return cls(
            height=d.get("height", 0),
            round=d.get("round", 0),
            block_id=BlockID.from_proto(d.get("block_id") or {}),
            extended_signatures=[
                ExtendedCommitSig.from_proto(s)
                for s in d.get("extended_signatures", [])],
        )
