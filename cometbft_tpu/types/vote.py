"""Vote: a prevote or precommit, optionally carrying vote extensions.

Reference: types/vote.go — Vote struct (:66-81), Verify/VerifyWithExtension/
VerifyExtension (:247,256,281), ValidateBasic, MaxVoteBytes/extension caps.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

from ..crypto.keys import PubKey
from ..libs import metrics as _libmetrics
from ..libs import tracing as _tracing
from . import canonical
from .block_id import BlockID
from .part_set import PartSetError
from .timestamp import Timestamp

# max(ed25519=64, bls12_381=96); reference: types/signable.go:13
MAX_SIGNATURE_SIZE = 96

# reference: types/vote.go:20 — 1 MiB cap on any single extension
MAX_VOTE_EXTENSION_SIZE = 1024 * 1024

# BlockIDFlag (proto/cometbft/types/v2/validator.proto)
BLOCK_ID_FLAG_UNKNOWN = 0
BLOCK_ID_FLAG_ABSENT = 1
BLOCK_ID_FLAG_COMMIT = 2
BLOCK_ID_FLAG_NIL = 3


class VoteError(Exception):
    pass


class InvalidSignatureError(VoteError):
    pass


# ---------------------------------------------------------------------------
# Verified-signature memo + burst pre-verification — the tally-path
# batching the reference leaves on the table (SURVEY: vote_set.go:219-236
# verifies per vote inside AddVote).  The consensus receive loop drains
# whatever vote messages are queued, batch-verifies their signatures
# through the grouped batch machinery (TPU kernel / native MSM / RLC
# pairings product by key type), and memoizes the VALID triples; the
# serial state-machine processing then hits the memo instead of paying
# a per-signature verify.  Only positives are cached (a valid
# (pubkey, message, signature) triple is valid forever), the memo is
# bounded, and processing order is unchanged — determinism and verdicts
# are identical to the unbatched path.

import hashlib as _hashlib
from collections import OrderedDict as _OrderedDict

_VERIFIED: "_OrderedDict[tuple[bytes, bytes, bytes], None]" = \
    _OrderedDict()
_VERIFIED_MAX = 8192

# Negative memo: verification is deterministic, so a failed
# (pubkey, msg-hash, sig) triple is invalid forever.  Without it, a
# byzantine peer re-sending vote storms with one bad signature per
# burst gets amplified work per message: the batch rejects, the mask
# pass pays the slow per-signature fallback on the bad entry, and the
# serial path then re-verifies the SAME bad entry again (only
# positives used to be memoized).  Bounded like the positive memo.
_REJECTED: "_OrderedDict[tuple[bytes, bytes, bytes], None]" = \
    _OrderedDict()
_REJECTED_MAX = 4096


def _memo_key(pub_key: PubKey, msg: bytes,
              sig: bytes) -> tuple[bytes, bytes, bytes]:
    # the message is HASHED into the key: extension sign bytes can be
    # ~1 MiB, and 8192 entries of embedded messages would be a
    # byzantine-controllable multi-GB memo; a digest bounds every
    # entry to ~130 bytes
    return (pub_key.bytes(), _hashlib.sha256(msg).digest(), bytes(sig))


def _memo_add(key: tuple[bytes, bytes, bytes]) -> None:
    _VERIFIED[key] = None
    if len(_VERIFIED) > _VERIFIED_MAX:
        _VERIFIED.popitem(last=False)


def _memo_reject(key: tuple[bytes, bytes, bytes]) -> None:
    _REJECTED[key] = None
    if len(_REJECTED) > _REJECTED_MAX:
        _REJECTED.popitem(last=False)


# How a vote's signature was judged on the serial path, and what the
# batches left in the memos: plain integers, read at scrape time as
# cometbft_consensus_vote_verify_total{path} and
# cometbft_consensus_vote_preverified_total{verdict} (a metric object
# a call would cost more than the dict lookup counted).  A node whose
# votes arrive in bursts shows "memo"; "serial" is one signature
# verified on the CPU: a vote no batch covered, or the confirmation
# of a lane a batch refused.
_VERIFY_COUNTS = {"memo": 0, "serial": 0}
_PREVERIFIED = {"valid": 0, "invalid": 0, "unjudged": 0}


def verify_counts() -> tuple[int, int]:
    """(memo answers, serial verifications) so far in this process."""
    return _VERIFY_COUNTS["memo"], _VERIFY_COUNTS["serial"]


_libmetrics.DEFAULT.counter_func(
    "consensus", "vote_verify_total",
    "Vote signatures judged on the serial path, by how: answered by "
    "the verified/rejected memo a batch filled, or verified one by "
    "one on the CPU.", "path", lambda: _VERIFY_COUNTS)
_libmetrics.DEFAULT.counter_func(
    "consensus", "vote_preverified_total",
    "Vote signatures a burst pre-verification put through a batch "
    "verifier, by the verdict it left in the memo (unjudged: left to "
    "the serial path).", "verdict", lambda: _PREVERIFIED)


def _serial_verify(pub_key: PubKey, msg: bytes, sig: bytes,
                   key: tuple[bytes, bytes, bytes]) -> bool:
    """One signature on the CPU; its verdict enters the memos."""
    _VERIFY_COUNTS["serial"] += 1
    ok = pub_key.verify_signature(msg, sig)
    if ok:
        _memo_add(key)
    else:
        _memo_reject(key)
    return ok


def checked_verify(pub_key: PubKey, msg: bytes, sig: bytes) -> bool:
    """pub_key.verify_signature with the verified/rejected memos."""
    key = _memo_key(pub_key, msg, sig)
    if key in _VERIFIED:
        _VERIFIED.move_to_end(key)
        _VERIFY_COUNTS["memo"] += 1
        return True
    if key in _REJECTED:
        _REJECTED.move_to_end(key)
        _VERIFY_COUNTS["memo"] += 1
        return False
    return _serial_verify(pub_key, msg, sig, key)


def preverify_signatures(entries) -> int:
    """Batch-verify (pub_key, msg, sig) triples and memoize both
    verdicts; returns how many of them were fresh (in neither memo)
    and so went to a batch.  Never raises and proves nothing on its
    own: entries the batch could not judge (None mask — unsupported
    key type, malformed input, singleton group, verifier error) are
    left for the caller's serial path to verify and reject with its
    own errors.

    A False mask entry is confirmed by ONE serial verify before it
    enters the negative memo: the CPU/BLS batch verifiers' reject
    masks are already exact (per-signature fallback), but the TPU
    kernel's are the kernel's own verdicts — the serial verifier must
    keep final say, or a kernel false-negative would be cached as
    invalid-forever and this node would reject votes its peers accept
    (consensus divergence).  The confirmation costs what the caller's
    serial path would have paid anyway; re-sent storms then hit the
    memo."""
    from ..crypto import batch as crypto_batch

    fresh = []
    keys = []
    for pub_key, msg, sig in entries:
        key = _memo_key(pub_key, msg, sig)
        if key in _VERIFIED or key in _REJECTED:
            continue
        fresh.append((pub_key, msg, sig))
        keys.append(key)
    if len(fresh) < 2:
        return 0
    mask = crypto_batch.batch_verify_by_type(fresh)
    for (pub_key, msg, sig), key, good in zip(fresh, keys, mask):
        if good:
            _memo_add(key)
            _PREVERIFIED["valid"] += 1
        elif good is None:
            _PREVERIFIED["unjudged"] += 1
        elif _serial_verify(pub_key, msg, sig, key):
            _PREVERIFIED["valid"] += 1   # batch false-negative fixed
        else:
            _PREVERIFIED["invalid"] += 1
    return len(fresh)


def preverify_signatures_async(entries, under=None):
    """``preverify_signatures`` on the verification staging worker:
    returns a concurrent Future that resolves (to the number of fresh
    entries) once the burst's verdicts are memoized — the consensus
    receive routine awaits it as a verdict barrier while the event
    loop keeps draining gossip (consensus/state.py).  ``under`` is the
    caller's open span: the worker's context is its own, so the
    seam's ``batch_verify`` span is made its child by name.  Memo
    reads/writes are single-op dict mutations, atomic under the GIL,
    so the worker and the loop-side ``checked_verify`` interleave
    safely; the memo is advisory either way (a miss just re-verifies
    serially)."""
    from ..crypto import pipeline

    def run() -> int:
        with _tracing.under(under):
            return preverify_signatures(entries)
    return pipeline.submit(run)


@dataclass
class Vote:
    type: int = canonical.UNKNOWN_TYPE
    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    timestamp: Timestamp = field(default_factory=Timestamp.zero)
    validator_address: bytes = b""
    validator_index: int = 0
    signature: bytes = b""
    extension: bytes = b""
    extension_signature: bytes = b""
    non_rp_extension: bytes = b""
    non_rp_extension_signature: bytes = b""

    # ------------------------------------------------------------------
    def sign_bytes(self, chain_id: str) -> bytes:
        # memoized on the FULL signed-field tuple: the burst
        # pre-verification and the serial verify both marshal the same
        # canonical bytes on the consensus hot loop.  Keying every
        # signed field (not just chain id + timestamp) means staleness
        # safety is enforced rather than resting on a never-mutate
        # invariant — privval's double-sign protection really does
        # rebind vote.timestamp on the same-HRS re-sign path
        # (privval/file.py) after sign bytes may have been computed,
        # and any future mutation of another signed field now misses
        # the memo instead of silently signing stale bytes.
        # (signature/extensions are set later but are not signed over.)
        key = (chain_id, self.type, self.height, self.round,
               self.block_id, self.timestamp)
        cache = self.__dict__.get("_sb_memo")
        if cache is not None and cache[0] == key:
            return cache[1]
        sb = canonical.vote_sign_bytes(
            chain_id, self.type, self.height, self.round, self.block_id,
            self.timestamp)
        self.__dict__["_sb_memo"] = (key, sb)
        return sb

    def extension_sign_bytes(self, chain_id: str) -> bytes:
        return canonical.vote_extension_sign_bytes(
            chain_id, self.height, self.round, self.extension)

    def non_rp_extension_sign_bytes(self) -> bytes:
        """Reference: vote.go VoteExtensionSignBytes (:173-183) — the
        non-replay-protected extension signs its raw bytes (no chain-id /
        height canonicalization, by design)."""
        return self.non_rp_extension

    def is_nil(self) -> bool:
        return self.block_id.is_nil()

    # ------------------------------------------------------------------
    def _verify_vote_sig(self, chain_id: str, pub_key: PubKey) -> None:
        if pub_key.address() != self.validator_address:
            raise InvalidSignatureError(
                "vote validator address does not match pubkey")
        if not checked_verify(pub_key, self.sign_bytes(chain_id),
                              self.signature):
            raise InvalidSignatureError("invalid vote signature")

    def verify(self, chain_id: str, pub_key: PubKey) -> None:
        """Reference: vote.go Verify — vote signature only."""
        self._verify_vote_sig(chain_id, pub_key)

    def verify_vote_and_extension(self, chain_id: str,
                                  pub_key: PubKey) -> None:
        """Reference: vote.go VerifyVoteAndExtension — for precommits on a
        block, additionally checks the extension signature."""
        self._verify_vote_sig(chain_id, pub_key)
        if (self.type == canonical.PRECOMMIT_TYPE and
                not self.block_id.is_nil()):
            self.verify_extension(chain_id, pub_key)

    def verify_extension(self, chain_id: str, pub_key: PubKey) -> None:
        """Reference: vote.go VerifyExtension (:280-299) — both the
        replay-protected and the non-RP extension signatures are required
        and checked for non-nil precommits."""
        if self.type != canonical.PRECOMMIT_TYPE or self.block_id.is_nil():
            return
        if not self.extension_signature or \
                not self.non_rp_extension_signature:
            raise InvalidSignatureError("vote extension signature missing")
        if not checked_verify(pub_key,
                              self.extension_sign_bytes(chain_id),
                              self.extension_signature):
            raise InvalidSignatureError("invalid vote extension signature")
        if not checked_verify(pub_key,
                              self.non_rp_extension_sign_bytes(),
                              self.non_rp_extension_signature):
            raise InvalidSignatureError(
                "invalid non-RP vote extension signature")

    # ------------------------------------------------------------------
    def validate_basic(self) -> None:
        """Reference: vote.go ValidateBasic."""
        if not canonical.is_vote_type_valid(self.type):
            raise VoteError(f"invalid vote type {self.type}")
        if self.height <= 0:
            raise VoteError("vote height must be positive")
        if self.round < 0:
            raise VoteError("vote round must be non-negative")
        try:
            self.block_id.validate_basic()
        except PartSetError as e:
            raise VoteError(f"wrong BlockID: {e}") from e
        if not self.block_id.is_nil() and not self.block_id.is_complete():
            raise VoteError("BlockID must be either empty or complete")
        if len(self.validator_address) != 20:
            raise VoteError("wrong validator address size")
        if self.validator_index < 0:
            raise VoteError("negative validator index")
        if len(self.signature) == 0:
            raise VoteError("signature is missing")
        if len(self.signature) > MAX_SIGNATURE_SIZE:
            raise VoteError("signature is too big")
        if self.type == canonical.PRECOMMIT_TYPE and \
                not self.block_id.is_nil():
            if len(self.extension) > MAX_VOTE_EXTENSION_SIZE:
                raise VoteError("vote extension too big")
            if self.extension and not self.extension_signature:
                raise VoteError("vote extension signature is missing")
            if len(self.non_rp_extension) > MAX_VOTE_EXTENSION_SIZE:
                raise VoteError("non-RP vote extension too big")
            if len(self.non_rp_extension_signature) > MAX_SIGNATURE_SIZE:
                raise VoteError("non-RP extension signature is too big")
            if self.non_rp_extension and \
                    not self.non_rp_extension_signature:
                raise VoteError("non-RP extension signature is missing")
            # reference vote.go:385 — the two extension signatures come
            # as a pair: both present (extensions enabled) or neither
            if bool(self.extension_signature) != \
                    bool(self.non_rp_extension_signature):
                raise VoteError(
                    "extension signatures must both be present or absent")
        else:
            # reference: extensions only allowed on non-nil precommits
            if self.extension or self.extension_signature or \
                    self.non_rp_extension or self.non_rp_extension_signature:
                raise VoteError(
                    "unexpected vote extension on non-precommit vote")

    # ------------------------------------------------------------------
    def commit_sig(self) -> dict:
        """CommitSig view of this vote (reference: vote.go CommitSig)."""
        if self.block_id.is_nil():
            flag = BLOCK_ID_FLAG_NIL
        else:
            flag = BLOCK_ID_FLAG_COMMIT
        return {
            "block_id_flag": flag,
            "validator_address": self.validator_address,
            "timestamp": self.timestamp,
            "signature": self.signature,
        }

    def to_proto(self) -> dict:
        d: dict = {
            "block_id": self.block_id.to_proto(),
            "timestamp": self.timestamp.to_proto(),
        }
        if self.type:
            d["type"] = self.type
        if self.height:
            d["height"] = self.height
        if self.round:
            d["round"] = self.round
        if self.validator_address:
            d["validator_address"] = self.validator_address
        if self.validator_index:
            d["validator_index"] = self.validator_index
        if self.signature:
            d["signature"] = self.signature
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
    def from_proto(cls, d: dict) -> "Vote":
        return cls(
            type=d.get("type", 0),
            height=d.get("height", 0),
            round=d.get("round", 0),
            block_id=BlockID.from_proto(d.get("block_id") or {}),
            timestamp=Timestamp.from_proto(d.get("timestamp") or {}),
            validator_address=d.get("validator_address", b""),
            validator_index=d.get("validator_index", 0),
            signature=d.get("signature", b""),
            extension=d.get("extension", b""),
            extension_signature=d.get("extension_signature", b""),
            non_rp_extension=d.get("non_rp_extension", b""),
            non_rp_extension_signature=d.get(
                "non_rp_extension_signature", b""),
        )

    def copy(self) -> "Vote":
        return replace(self)

    def __str__(self) -> str:
        tname = {1: "Prevote", 2: "Precommit"}.get(self.type, "?")
        return (f"Vote{{{self.validator_index}:"
                f"{self.validator_address.hex().upper()[:12]} "
                f"{self.height}/{self.round:02d} {tname} "
                f"{self.block_id}}}")
