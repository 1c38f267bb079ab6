"""Commit verification — the primary TPU offload seam.

Reference: types/validation.go.  Semantics preserved exactly:
  * batching requires >= 2 signatures, a batch-capable key type, and all
    validators sharing one key type (:15-21);
  * VerifyCommit checks ALL signatures (incentivization contract),
    VerifyCommitLight* stop at 2/3 unless the AllSignatures variant;
  * on batch failure, the first invalid signature is identified (:384-397);
  * signature-cache hits skip verification and successes populate the cache.

The batch path dispatches through crypto.batch.create_batch_verifier, which
routes ed25519 batches to the TPU kernel (ops/ed25519_jax.py): one padded
device batch verifies every signature and the voting-power tally is a masked
segment-sum in the same XLA program.

Beyond the reference: MIXED-key commits — where the reference falls back to
per-signature verification outright — run through _verify_commit_grouped,
which batches each key-type group separately (ed25519 → TPU kernel,
bls12381 → one RLC pairings product) and verifies the rest inline, with
verdicts identical to the per-signature path.
"""
from __future__ import annotations

import hashlib
from typing import Callable, NamedTuple, Optional

from ..crypto import batch as crypto_batch
from ..libs import tracing
from ..libs.bits import BitArray
from .commit import AggregateCommit, Commit, CommitSig, CommitError
from .block_id import BlockID
from .signature_cache import SignatureCache, SignatureCacheValue
from .validator_set import ValidatorSet
from .vote import BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT

BATCH_VERIFY_THRESHOLD = 2

# metrics v2: commit-verification latency split by commit kind
# ("aggregate" = the O(1) BLS pairing path; "batch"/"grouped"/
# "single" = the per-signature paths).  Process-global registry —
# this module has no node context; /metrics merges DEFAULT in.
_COMMIT_VERIFY_HIST = None


def commit_verify_histogram():
    global _COMMIT_VERIFY_HIST
    if _COMMIT_VERIFY_HIST is None:
        from ..libs import metrics as libmetrics
        _COMMIT_VERIFY_HIST = libmetrics.DEFAULT.histogram(
            "consensus", "commit_verify_seconds",
            "Commit verification latency in seconds, by verification "
            "kind (aggregate = O(1) BLS pairing path; "
            "batch/grouped/single = per-signature paths).",
            labels=("kind",),
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
    return _COMMIT_VERIFY_HIST


class _observe_kind:
    """Context manager around one commit verification: the
    ``commit_verify`` span (its ``height`` is the request identifier
    every span below inherits) and, from the same pair of clock
    readings, the kind-labeled histogram (failures observe too — a
    rejected commit still paid the verification cost)."""

    __slots__ = ("kind", "sp")

    def __init__(self, kind: str, height: int):
        self.kind = kind
        self.sp = tracing.timed(tracing.CONSENSUS, "commit_verify",
                                height, runtime=True)

    def __enter__(self):
        self.sp.__enter__()
        return self

    def __exit__(self, *exc):
        self.sp.__exit__(*exc)
        # bounded: every instantiation site passes one of the four
        # literal kinds {aggregate, batch, grouped, single}
        kind = self.kind
        commit_verify_histogram().with_labels(kind).observe(
            self.sp.seconds)
        return False


class Fraction(NamedTuple):
    numerator: int
    denominator: int


class VerificationError(Exception):
    pass


class NotEnoughVotingPowerError(VerificationError):
    def __init__(self, got: int, needed: int):
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, "
            f"needed more than {needed}")
        self.got = got
        self.needed = needed


def _should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    return (len(commit.signatures) >= BATCH_VERIFY_THRESHOLD and
            crypto_batch.supports_batch_verifier(
                vals.get_proposer().pub_key) and
            vals.all_keys_have_same_type())


def _should_group_verify(vals: ValidatorSet, commit: Commit) -> bool:
    """Mixed-key commits: batch per key-type group when any batchable
    type appears at least twice.  The reference disables batching
    entirely for mixed sets (types/validation.go:15-21 +
    AllKeysHaveSameType); grouping recovers the batch win for the
    dominant key types while unsupported ones verify inline."""
    if len(commit.signatures) < BATCH_VERIFY_THRESHOLD:
        return False
    counts: dict[str, int] = {}
    for val in vals.validators:
        if val.pub_key is None:
            continue
        if crypto_batch.supports_batch_verifier(val.pub_key):
            kt = val.pub_key.type()
            counts[kt] = counts.get(kt, 0) + 1
            if counts[kt] >= 2:
                return True
    return False


def _verify_basic_vals_and_commit(vals: ValidatorSet, commit,
                                  height: int, block_id: BlockID) -> None:
    if vals is None:
        raise VerificationError("nil validator set")
    if commit is None:
        raise VerificationError("nil commit")
    if vals.size() != commit.size():
        raise VerificationError(
            f"invalid commit -- wrong set size: {vals.size()} vs "
            f"{commit.size()}")
    if height != commit.height:
        raise VerificationError(
            f"invalid commit -- wrong height: {height} vs {commit.height}")
    if block_id != commit.block_id:
        raise VerificationError(
            f"invalid commit -- wrong block ID: want {block_id}, "
            f"got {commit.block_id}")


def _dispatch_aggregate(chain_id: str, vals: ValidatorSet,
                        block_id: BlockID, height: int,
                        commit: AggregateCommit,
                        cache: Optional[SignatureCache]) -> None:
    """The O(1) arm shared by verify_commit and verify_commit_light:
    one aggregate signature covers every signer, so "all signatures"
    and "stop at 2/3" coincide."""
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    with _observe_kind("aggregate", height):
        _verify_aggregate_commit(
            chain_id, vals, commit,
            vals.total_voting_power() * 2 // 3, cache=cache)


def _verify_per_signature(height: int, chain_id: str,
                          vals: ValidatorSet, commit: Commit,
                          voting_power_needed: int, ignore_sig,
                          count_sig, count_all_signatures: bool,
                          look_up_by_index: bool,
                          cache: Optional[SignatureCache]) -> None:
    """Pick the per-signature path the set's keys allow and run it
    inside its commit_verify span / histogram observation."""
    if _should_batch_verify(vals, commit):
        kind, verify = "batch", _verify_commit_batch
    elif _should_group_verify(vals, commit):
        kind, verify = "grouped", _verify_commit_grouped
    else:
        kind, verify = "single", _verify_commit_single
    with _observe_kind(kind, height):
        verify(chain_id, vals, commit, voting_power_needed, ignore_sig,
               count_sig, count_all_signatures, look_up_by_index, cache)


def verify_commit(chain_id: str, vals: ValidatorSet, block_id: BlockID,
                  height: int, commit: Commit | AggregateCommit,
                  cache: Optional[SignatureCache] = None) -> None:
    """+2/3 signed; checks ALL signatures (reference: VerifyCommit :30).

    AggregateCommit commits take the O(1) pairing path
    (_dispatch_aggregate)."""
    if isinstance(commit, AggregateCommit):
        _dispatch_aggregate(chain_id, vals, block_id, height, commit,
                            cache)
        return
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    _verify_per_signature(
        height, chain_id, vals, commit,
        vals.total_voting_power() * 2 // 3,
        lambda c: c.block_id_flag == BLOCK_ID_FLAG_ABSENT,
        lambda c: c.block_id_flag == BLOCK_ID_FLAG_COMMIT,
        count_all_signatures=True, look_up_by_index=True, cache=cache)


def verify_commit_light(chain_id: str, vals: ValidatorSet,
                        block_id: BlockID, height: int,
                        commit: Commit | AggregateCommit,
                        count_all_signatures: bool = False,
                        cache: Optional[SignatureCache] = None) -> None:
    """Light-client variant: stops at 2/3 unless count_all_signatures.

    Reference: VerifyCommitLight / ...AllSignatures / ...WithCache (:65)."""
    if isinstance(commit, AggregateCommit):
        _dispatch_aggregate(chain_id, vals, block_id, height, commit,
                            cache)
        return
    _verify_basic_vals_and_commit(vals, commit, height, block_id)
    _verify_per_signature(
        height, chain_id, vals, commit,
        vals.total_voting_power() * 2 // 3,
        lambda c: c.block_id_flag != BLOCK_ID_FLAG_COMMIT,
        lambda c: True,
        count_all_signatures=count_all_signatures,
        look_up_by_index=True, cache=cache)


def verify_commit_light_trusting(
        chain_id: str, vals: ValidatorSet,
        commit: Commit | AggregateCommit,
        trust_level: Fraction, count_all_signatures: bool = False,
        cache: Optional[SignatureCache] = None,
        signer_vals: Optional[ValidatorSet] = None) -> None:
    """trustLevel (e.g. 1/3) of a TRUSTED validator set signed; used for
    skipping verification.  Looks validators up by address since the sets
    need not correspond (reference: VerifyCommitLightTrusting :150).

    For an AggregateCommit the signer bitmap indexes the set that
    SIGNED the commit's height, so the caller must supply that set as
    ``signer_vals`` (the light client has it — the untrusted header's
    validator set, already checked against validators_hash).
    signer_vals is used ONLY to map bitmap indices to addresses: the
    pairing runs against the TRUSTED set's keys for those addresses
    (signer_vals may be self-certified by the header under
    verification, so its claimed keys prove nothing — see
    _verify_aggregate_commit), and a signer outside the trusted set
    reports as not-enough-provable-power so skipping callers bisect."""
    if vals is None:
        raise VerificationError("nil validator set")
    if trust_level.denominator == 0:
        raise VerificationError("trustLevel has zero Denominator")
    if commit is None:
        raise VerificationError("nil commit")
    product = vals.total_voting_power() * trust_level.numerator
    if product >= (1 << 63):
        raise VerificationError(
            "int64 overflow while calculating voting power needed")
    voting_power_needed = product // trust_level.denominator
    if isinstance(commit, AggregateCommit):
        if signer_vals is None:
            raise VerificationError(
                "aggregate commit trusting verification needs the "
                "signing validator set")
        if signer_vals.size() != commit.size():
            raise VerificationError(
                f"invalid commit -- wrong set size: "
                f"{signer_vals.size()} vs {commit.size()}")
        with _observe_kind("aggregate", commit.height):
            _verify_aggregate_commit(
                chain_id, signer_vals, commit, voting_power_needed,
                cache=cache, tally_vals=vals)
        return
    _verify_per_signature(
        commit.height, chain_id, vals, commit, voting_power_needed,
        lambda c: c.block_id_flag != BLOCK_ID_FLAG_COMMIT,
        lambda c: True,
        count_all_signatures=count_all_signatures,
        look_up_by_index=False, cache=cache)


# ---------------------------------------------------------------------------
# aggregate-commit verification: O(1) pairing work in validator count
# (docs/aggregate_commits.md)

def _agg_memo_key(commit: AggregateCommit, valset_hash: bytes,
                  bitmap: bytes) -> bytes:
    """Verdict-memo key binding (block_id, valset, bitmap, signature);
    hashed so the shared SignatureCache stores 32-byte keys, prefixed
    so it can never collide with a raw signature key.  ``valset_hash``
    and ``bitmap`` describe the set the pubkeys were RESOLVED from —
    on the trusting path that is the trusted set and the bitmap
    re-indexed into it, so a verdict cached against one trusted set
    can never answer for another."""
    h = hashlib.sha256()
    h.update(b"aggcommit/1\x00")
    h.update(valset_hash)
    h.update(commit.block_id.key())
    h.update(bitmap)
    h.update(commit.signature)
    return b"agg:" + h.digest()


# per-valset raw-pubkey table: the G1 point-sum consumes the keys'
# raw 96-byte serializations; re-extracting them (10k method calls +
# key-type checks) on every new signer bitmap costs more than the
# join itself.  Keyed by valset hash, tiny LRU — a handful of live
# valsets exist at once.
_PK_RAWS: "OrderedDict[bytes, Optional[tuple]]" = None  # type: ignore


def _pubkey_raws(vals: ValidatorSet, valset_hash: bytes):
    """Tuple of 96-byte raw BLS pubkey serializations (valset order),
    or None when any validator key is not bls12_381."""
    global _PK_RAWS
    if _PK_RAWS is None:
        from collections import OrderedDict
        _PK_RAWS = OrderedDict()
    _MISS = object()
    entry = _PK_RAWS.get(valset_hash, _MISS)
    if entry is not _MISS:
        _PK_RAWS.move_to_end(valset_hash)
        return entry
    from ..crypto import bls12381
    raws = []
    for v in vals.validators:
        pk = v.pub_key
        if not isinstance(pk, bls12381.Bls12381PubKey):
            raws = None
            break
        raws.append(pk.bytes())
    entry = tuple(raws) if raws is not None else None
    _PK_RAWS[valset_hash] = entry
    if len(_PK_RAWS) > 8:
        _PK_RAWS.popitem(last=False)
    return entry


def _verify_aggregate_commit(
        chain_id: str, vals: ValidatorSet, commit: AggregateCommit,
        voting_power_needed: int,
        cache: Optional[SignatureCache] = None,
        tally_vals: Optional[ValidatorSet] = None) -> None:
    """One pairing check for the whole commit.

    ``vals`` is the set the signer bitmap indexes (the commit
    height's validator set).  When ``tally_vals`` is given (the light
    client's TRUSTED set — the trusting path) every signer is
    resolved through it BY ADDRESS: the power tally and the pubkey
    sum both use the trusted set's entries, never the claimed keys in
    ``vals``.  ``vals`` may be self-certified by the very header under
    verification (a skipping hop checks it only against that header's
    validators_hash), so verifying the pairing against its keys would
    let a rogue aggregate key (pk_r = [x]g1 - sum of trusted keys,
    placed at a fabricated index) cancel the trusted keys and forge
    the 1/3-trust check with zero honest signatures.  A signer whose
    address is NOT in the trusted set cannot be authenticated at all,
    so the hop reports zero provable power (NotEnoughVotingPowerError
    — the light client bisects toward the trusted header until the
    sets overlap, converging on adjacent hops whose valset is
    chain-certified).

    The G1 pubkey sum — the only O(n) step, and it is point adds, not
    pairings — is memoized per (valset_hash, bitmap) in the
    process-global AggregatePubKeyCache; the full verdict is memoized
    in the SignatureCache keyed (block_id, valset_hash, bitmap,
    signature) — both keyed on the set the keys were RESOLVED from
    (the trusted set on the trusting path)."""
    from ..crypto import bls12381

    try:
        commit.validate_basic()
    except CommitError as e:
        raise VerificationError(f"invalid aggregate commit: {e}") from e

    top = commit.signers.highest_true_index()
    if top >= vals.size():
        raise VerificationError(
            f"signer bit {top} out of range for validator set "
            f"of {vals.size()}")

    # voting-power tally (cheap, judged before the pairing as the
    # batch path judges threshold before its deferred verify) —
    # key_vals/key_bits name the set + bitmap the PAIRING runs over
    if tally_vals is None:
        # complement walk: healthy chains have near-full bitmaps, so
        # summing the MISSING validators' power is O(absent), not
        # O(n) — at 10k validators this is what keeps the warm path
        # inside the pairing budget
        key_vals, key_bits = vals, commit.signers
        tallied = vals.total_voting_power()
        for i in commit.signers.not_().true_indices():
            tallied -= vals.validators[i].voting_power
    else:
        # trusting: every signer resolved through the TRUSTED set by
        # address (see docstring — ``vals`` may be self-certified and
        # its claimed keys are never used here); an unknown signer
        # means zero soundly-attributable power, a repeated address
        # means ``vals`` is malformed
        key_vals = tally_vals
        key_bits = BitArray(tally_vals.size())
        tallied = 0
        for i in commit.signed_indices():
            addr = vals.validators[i].address
            tidx = tally_vals.index_by_address(addr)
            if tidx < 0:
                raise NotEnoughVotingPowerError(0, voting_power_needed)
            if key_bits.get_index(tidx):
                raise VerificationError(
                    f"duplicate signer address {addr.hex().upper()} "
                    f"in aggregate commit signer set")
            key_bits.set_index(tidx, True)
            tallied += tally_vals.validators[tidx].voting_power
    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(tallied, voting_power_needed)

    sign_bytes = commit.vote_sign_bytes(chain_id)
    valset_hash = key_vals.hash()
    bitmap = key_bits.to_le_bytes()

    memo_key = _agg_memo_key(commit, valset_hash, bitmap)
    if cache is not None:
        cv = cache.get(memo_key)
        if cv is not None and cv.vote_sign_bytes == sign_bytes:
            return

    def build():
        raws = _pubkey_raws(key_vals, valset_hash)
        if raws is None:
            raise VerificationError(
                "aggregate commits need a bls12_381 validator set")
        if key_bits.popcount() == len(raws):
            blob = b"".join(raws)
        else:
            blob = b"".join(raws[i] for i in key_bits.true_indices())
        return bls12381.aggregate_pub_keys_raw(blob)

    pk_cache = bls12381.aggregate_pubkey_cache()
    agg_pk = pk_cache.get(valset_hash, bitmap)
    fresh = agg_pk is None
    if fresh:
        agg_pk = build()

    if not bls12381.verify_aggregate(agg_pk, sign_bytes,
                                     commit.signature):
        raise VerificationError(
            f"wrong aggregate signature: "
            f"{commit.signature.hex().upper()[:24]}...")

    if fresh:
        # insert only after success: a forged-signature stream with
        # varying bitmaps must not evict the honest sums
        pk_cache.put(valset_hash, bitmap, agg_pk)
    if cache is not None:
        cache.add(memo_key, SignatureCacheValue(b"aggregate",
                                                sign_bytes))

# ---------------------------------------------------------------------------


def _walk_commit(
        chain_id: str, vals: ValidatorSet, commit: Commit,
        voting_power_needed: int,
        ignore_sig: Callable[[CommitSig], bool],
        count_sig: Callable[[CommitSig], bool],
        count_all_signatures: bool, look_up_by_index: bool,
        cache: Optional[SignatureCache], strict: bool,
        handle: Callable, sp=None) -> int:
    """The signature walk shared by the three verification paths
    (single / batch / grouped): ignore filter, optional structural
    validation, by-index or by-address validator lookup with
    double-vote detection, cache short-circuit, voting-power tally
    with the early exit.  Returns the tallied power.

    handle(idx, val, sign_bytes, commit_sig) is called for every
    signature the cache does not satisfy — it verifies inline
    (raising VerificationError) or defers into a batch verifier;
    returning False stops the walk (the grouped path uses this to
    reconcile an inline failure against its deferred groups before
    reporting, so the LOWEST failing index is named either way).

    strict adds commit_sig.validate_basic() (the per-signature path's
    behavior); the same-type batch path omits it, mirroring the
    reference's verifyCommitBatch.  The nil-pubkey check is
    UNCONDITIONAL on every path — see the comment at the raise.

    sp, the caller's ``commit_walk`` span, is told how far the walk
    went (``walked`` signatures) and how many of them the cache
    satisfied (``cache_hits``).
    """
    seen_vals: dict[int, int] = {}
    # block-id flag -> make(ts): the commit's sign-bytes template,
    # taken once a flag, not once a signature
    makers: dict[int, Callable] = {}
    tallied = 0
    cache_hits = 0
    idx = -1
    for idx, commit_sig in enumerate(commit.signatures):
        if ignore_sig(commit_sig):
            continue
        if strict:
            try:
                commit_sig.validate_basic()
            except CommitError as e:
                raise VerificationError(
                    f"invalid signature at index {idx}: {e}") from e
        if look_up_by_index:
            val = vals.validators[idx]
        else:
            val_idx = vals.index_by_address(
                commit_sig.validator_address)
            if val_idx < 0:
                continue
            val = vals.validators[val_idx]
            if val_idx in seen_vals:
                raise VerificationError(
                    f"double vote from {val} "
                    f"({seen_vals[val_idx]} and {idx})")
            seen_vals[val_idx] = idx
        if val.pub_key is None:
            # unconditional (not strict-gated): the same-type gate
            # skips nil-pubkey validators, so a nil key CAN reach the
            # batch path, where BatchVerifier.add would raise
            # TypeError and the cache probe below would crash — the
            # reference's batch path rejects via Add's error return
            raise VerificationError(
                f"validator {val} has a nil PubKey at index {idx}")

        make = makers.get(commit_sig.block_id_flag)
        if make is None:
            make = makers[commit_sig.block_id_flag] = \
                commit.vote_sign_bytes_maker(chain_id, commit_sig)
        vote_sign_bytes = make(commit_sig.timestamp)

        cache_hit = False
        if cache is not None:
            cv = cache.get(commit_sig.signature)
            cache_hit = (cv is not None and
                         cv.validator_address == val.pub_key.address() and
                         cv.vote_sign_bytes == vote_sign_bytes)
        if cache_hit:
            cache_hits += 1
        elif handle(idx, val, vote_sign_bytes, commit_sig) is False:
            break

        if count_sig(commit_sig):
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            break
    if sp is not None:
        sp.note(walked=idx + 1, cache_hits=cache_hits)
    return tallied


def _walk_span(look_up_by_index: bool):
    """The ``commit_walk`` span of a batched path; _walk_commit adds
    ``walked`` and ``cache_hits``."""
    return tracing.span(
        tracing.CONSENSUS, "commit_walk",
        runtime=True,
        lookup="index" if look_up_by_index else "address")


def _verify_commit_batch(
        chain_id: str, vals: ValidatorSet, commit: Commit,
        voting_power_needed: int,
        ignore_sig: Callable[[CommitSig], bool],
        count_sig: Callable[[CommitSig], bool],
        count_all_signatures: bool, look_up_by_index: bool,
        cache: Optional[SignatureCache]) -> None:
    """Reference: verifyCommitBatch (:265) — including its ordering:
    the voting-power threshold is judged before the deferred batch
    runs.  Cache entries record the VERIFIED key's address, never
    commit_sig.validator_address: in by-index mode that field is
    attacker-controlled, and caching it would let one validator's
    signature poison the cache under another validator's address
    (canonical vote sign bytes exclude address/index, so a later
    by-index lookup in the other validator's slot would hit)."""
    bv = crypto_batch.create_batch_verifier(vals.get_proposer().pub_key)
    entries: list[tuple[int, bytes, bytes]] = []

    def handle(idx, val, sign_bytes, commit_sig):
        try:
            bv.add(val.pub_key, sign_bytes, commit_sig.signature)
        except (ValueError, TypeError) as e:
            # malformed (e.g. wrong-length) signature the structural
            # checks let through — the reference returns Add's error
            # here; surface it as the usual wrong-signature verdict
            raise VerificationError(
                f"wrong signature (#{idx}): "
                f"{commit_sig.signature.hex().upper()}") from e
        entries.append((idx, val.pub_key.address(), sign_bytes))

    with _walk_span(look_up_by_index) as sp:
        tallied = _walk_commit(
            chain_id, vals, commit, voting_power_needed, ignore_sig,
            count_sig, count_all_signatures, look_up_by_index, cache,
            strict=False, handle=handle, sp=sp)

    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(tallied, voting_power_needed)

    if not entries:
        return  # everything was cached

    ok, valid_sigs = bv.verify()
    err: Optional[VerificationError] = None
    if ok:
        if cache is not None:
            for idx, addr, sign_bytes in entries:
                cache.add(commit.signatures[idx].signature,
                          SignatureCacheValue(addr, sign_bytes))
    else:
        err = _first_invalid(commit, entries, valid_sigs, cache)

    # what the walk gathered (the verifier's triples, the entries, the
    # sign bytes both hold) is freed here, under a span of its own,
    # and not as this frame unwinds under commit_verify's end: at
    # 10,000 validators that is milliseconds.  Rebinding the names the
    # closure shares drops the last references; nothing here can raise
    with tracing.span(tracing.CONSENSUS, "commit_release"):
        bv = entries = valid_sigs = None
    if err is not None:
        raise err


def _first_invalid(commit: Commit, entries: list, valid_sigs,
                   cache: Optional[SignatureCache]) -> VerificationError:
    """The verdict of a refused batch: the first invalid signature by
    commit index; the valid ones before it enter the cache."""
    for sig_ok, (idx, addr, sign_bytes) in zip(valid_sigs, entries):
        sig = commit.signatures[idx]
        if not sig_ok:
            return VerificationError(
                f"wrong signature (#{idx}): {sig.signature.hex().upper()}")
        if cache is not None:
            cache.add(sig.signature,
                      SignatureCacheValue(addr, sign_bytes))
    return VerificationError(
        "BUG: batch verification failed with no invalid signatures")


def _verify_commit_grouped(
        chain_id: str, vals: ValidatorSet, commit: Commit,
        voting_power_needed: int,
        ignore_sig: Callable[[CommitSig], bool],
        count_sig: Callable[[CommitSig], bool],
        count_all_signatures: bool, look_up_by_index: bool,
        cache: Optional[SignatureCache]) -> None:
    """Mixed-key commit verification with per-key-type batch groups
    (TPU-native extension; see _should_group_verify).  Walk semantics
    match _verify_commit_single (strict structural checks, cache,
    early threshold exit); batchable signatures defer into one
    verifier per key type, unsupported ones verify inline.  Verdict
    parity with the single path: any invalid signature raises
    VerificationError naming the LOWEST failing commit index — an
    inline failure stops the walk and is reconciled against the
    deferred groups before reporting — and does so before the
    voting-power threshold is judged, as inline verification would.
    """
    # key type -> (verifier, [(idx, key address, sign bytes)])
    groups: dict[str, tuple] = {}
    inline_bad: Optional[int] = None

    def handle(idx, val, sign_bytes, commit_sig):
        nonlocal inline_bad
        if crypto_batch.supports_batch_verifier(val.pub_key):
            kt = val.pub_key.type()
            entry = groups.get(kt)
            if entry is None:
                entry = (crypto_batch.create_batch_verifier(val.pub_key),
                         [])
                groups[kt] = entry
            try:
                entry[0].add(val.pub_key, sign_bytes,
                             commit_sig.signature)
            except (ValueError, TypeError):
                # malformed signature the structural checks let
                # through (e.g. wrong length): same verdict as a
                # failed inline verify, reconciled for lowest index
                inline_bad = idx
                return False
            entry[1].append((idx, val.pub_key.address(), sign_bytes))
            return None
        if not val.pub_key.verify_signature(sign_bytes,
                                            commit_sig.signature):
            inline_bad = idx
            return False        # stop: reconcile vs deferred groups
        if cache is not None:
            cache.add(commit_sig.signature, SignatureCacheValue(
                val.pub_key.address(), sign_bytes))
        return None

    with _walk_span(look_up_by_index) as sp:
        tallied = _walk_commit(
            chain_id, vals, commit, voting_power_needed, ignore_sig,
            count_sig, count_all_signatures, look_up_by_index, cache,
            strict=True, handle=handle, sp=sp)

    first_bad: Optional[int] = inline_bad
    for bv, entries in groups.values():
        if not entries:
            continue
        ok, valid_sigs = bv.verify()
        if ok:
            if cache is not None:
                for idx, addr, sign_bytes in entries:
                    cache.add(commit.signatures[idx].signature,
                              SignatureCacheValue(addr, sign_bytes))
            continue
        group_bad = [entries[i][0] for i, sig_ok in enumerate(valid_sigs)
                     if not sig_ok]
        if not group_bad:
            raise VerificationError(
                "BUG: batch verification failed with no invalid "
                "signatures")
        if cache is not None:
            bad_set = set(group_bad)
            for idx, addr, sign_bytes in entries:
                if idx not in bad_set:
                    cache.add(commit.signatures[idx].signature,
                              SignatureCacheValue(addr, sign_bytes))
        if first_bad is None or group_bad[0] < first_bad:
            first_bad = group_bad[0]
    if first_bad is not None:
        sig = commit.signatures[first_bad]
        raise VerificationError(
            f"wrong signature (#{first_bad}): "
            f"{sig.signature.hex().upper()}")

    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(tallied, voting_power_needed)


def _verify_commit_single(
        chain_id: str, vals: ValidatorSet, commit: Commit,
        voting_power_needed: int,
        ignore_sig: Callable[[CommitSig], bool],
        count_sig: Callable[[CommitSig], bool],
        count_all_signatures: bool, look_up_by_index: bool,
        cache: Optional[SignatureCache]) -> None:
    """Reference: verifyCommitSingle (:413)."""

    def handle(idx, val, sign_bytes, commit_sig):
        if not val.pub_key.verify_signature(sign_bytes,
                                            commit_sig.signature):
            raise VerificationError(
                f"wrong signature (#{idx}): "
                f"{commit_sig.signature.hex().upper()}")
        if cache is not None:
            cache.add(commit_sig.signature, SignatureCacheValue(
                val.pub_key.address(), sign_bytes))

    tallied = _walk_commit(
        chain_id, vals, commit, voting_power_needed, ignore_sig,
        count_sig, count_all_signatures, look_up_by_index, cache,
        strict=True, handle=handle)

    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(tallied, voting_power_needed)
