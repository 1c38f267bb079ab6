"""Light client: trusted-store-backed header tracker.

Reference: light/client.go (:1179) — sequential or skipping (bisection)
verification against a primary provider, witness cross-checking
(detector.go), trust-period handling, backwards verification below the
trusted root.
"""
from __future__ import annotations

from typing import Optional

from ..libs import tracing
from ..libs.log import Logger, new_logger
from ..types.block import LightBlock
from ..types.evidence import LightClientAttackEvidence
from ..types.signature_cache import SignatureCache
from ..types.timestamp import Timestamp
from ..types.validation import Fraction
from .provider import LightBlockNotFoundError, Provider, ProviderError
from .store import TrustedStore
from .verifier import (
    DEFAULT_TRUST_LEVEL, LightClientError, NewValSetCantBeTrustedError,
    header_expired, validate_trust_level, verify, verify_backwards,
)

_S = 1_000_000_000
DEFAULT_MAX_CLOCK_DRIFT_NS = 10 * _S

SEQUENTIAL = "sequential"
SKIPPING = "skipping"

_HOPS = None


def hop_counters() -> dict:
    """outcome -> its child of ``cometbft_light_hops_total``: one
    verify() attempt each, by how it ended.  Process-global registry,
    as the signature cache's counters: a light client has no node."""
    global _HOPS
    if _HOPS is None:
        from ..libs import metrics as libmetrics
        hops = libmetrics.DEFAULT.counter(
            "light", "hops_total",
            "Light-client verification attempts (one trusted -> "
            "candidate hop each) by outcome: verified, cant_trust "
            "(too little of the trusted set signed: bisected), "
            "invalid.", labels=("outcome",))
        _HOPS = {"verified": hops.with_labels("verified"),
                 "cant_trust": hops.with_labels("cant_trust"),
                 "invalid": hops.with_labels("invalid")}
    return _HOPS


class DivergenceError(LightClientError):
    """A witness disagrees with the primary — possible attack
    (reference: detector.go ErrConflictingHeaders)."""

    def __init__(self, witness: Provider, evidence=None):
        super().__init__(f"witness {witness.id()} diverges from primary")
        self.witness = witness
        self.evidence = evidence


class TrustOptions:
    """Reference: light.TrustOptions — period + (height, hash) root."""

    def __init__(self, period_ns: int, height: int, header_hash: bytes):
        self.period_ns = period_ns
        self.height = height
        self.hash = header_hash


class Client:
    def __init__(self, chain_id: str, trust_options: TrustOptions,
                 primary: Provider, witnesses: list[Provider],
                 trusted_store: TrustedStore,
                 verification_mode: str = SKIPPING,
                 trust_level: Fraction = DEFAULT_TRUST_LEVEL,
                 max_clock_drift_ns: int = DEFAULT_MAX_CLOCK_DRIFT_NS,
                 logger: Optional[Logger] = None):
        validate_trust_level(trust_level)
        self.chain_id = chain_id
        self.trust_options = trust_options
        self.primary = primary
        self.witnesses = list(witnesses)
        self.store = trusted_store
        self.mode = verification_mode
        self.trust_level = trust_level
        self.max_clock_drift_ns = max_clock_drift_ns
        self.logger = logger if logger is not None else \
            new_logger("light")

    # ------------------------------------------------------------------
    async def initialize(self,
                         now: Optional[Timestamp] = None) -> LightBlock:
        """Fetch + pin the trust root (reference: initializeWithTrustOptions)."""
        now = now or Timestamp.now()
        existing = self.store.light_block(self.trust_options.height)
        if existing is not None:
            return existing
        lb = await self.primary.light_block(self.trust_options.height)
        if lb.signed_header.header.hash() != self.trust_options.hash:
            raise LightClientError(
                "trusted header hash does not match the trust options")
        lb.validate_basic(self.chain_id)
        if header_expired(lb.signed_header,
                          self.trust_options.period_ns, now):
            raise LightClientError("trusted header is expired")
        self.store.save_light_block(lb)
        return lb

    # ------------------------------------------------------------------
    async def verify_light_block_at_height(
            self, height: int,
            now: Optional[Timestamp] = None) -> LightBlock:
        """Reference: VerifyLightBlockAtHeight."""
        return await self._verify_at(height, now, cache=None)

    async def _verify_at(self, height: int, now: Optional[Timestamp],
                         cache: Optional[SignatureCache]
                         ) -> LightBlock:
        now = now or Timestamp.now()
        if height <= 0:
            raise LightClientError("height must be positive")
        with tracing.span(tracing.LIGHT, "light_sync", height,
                          runtime=True, to=height) as sync:
            with tracing.span(tracing.LIGHT, "light_store_read"):
                existing, base = self._stored(height)
            if existing is not None:
                return existing
            sync.note(**{"from": base.height})
            if height < base.height:
                return await self._backwards(base, height)
            return await self._verify_forward(base, height, now,
                                              cache=cache)

    def _stored(self, height: int) -> tuple:
        """What the store has for a request: ``(the block at height,
        None)``, or ``(None, the block to start from)``: the latest
        when the height lies past it, the first when it lies before
        all (then verified backwards), else the closest below."""
        existing = self.store.light_block(height)
        if existing is not None:
            return existing, None
        latest = self.store.latest()
        if latest is None:
            raise LightClientError("client not initialized")
        if height > latest.height:
            return None, latest
        first = self.store.first()
        if first is not None and height < first.height:
            return None, first
        # between stored roots: verify forward from the closest
        # lower stored block
        return None, self._closest_below(height)

    async def update(self, now: Optional[Timestamp] = None
                     ) -> Optional[LightBlock]:
        """Verify the primary's latest header (reference: Update)."""
        now = now or Timestamp.now()
        latest = self.store.latest()
        if latest is None:
            raise LightClientError("client not initialized")
        new = await self.primary.light_block(0)
        if new.height <= latest.height:
            return None
        with tracing.span(tracing.LIGHT, "light_sync", new.height,
                          runtime=True,
                          **{"from": latest.height, "to": new.height}):
            return await self._verify_forward(latest, new.height, now,
                                              prefetched=new)

    async def verify_to_height(self, height: int,
                               now: Optional[Timestamp] = None
                               ) -> LightBlock:
        """Skipping (bisection) sync to ``height`` with ONE signature
        cache spanning every hop — the scalable consumer loop of the
        proof-serving layer (docs/light_proofs.md).

        Every hop's commit check rides the crypto.batch seam
        (Traced/Guarded verifiers: TPU kernel behind the breaker, CPU
        RLC fallback).  The cache spans the whole sync, so each hop's
        1/3-trust and 2/3 checks — which walk the same commit with
        overlapping old/new validator sets — and any bisection
        re-examination of an already-proved commit skip verified
        signatures instead of re-batching them (adjacent fallback
        hops previously ran uncached entirely)."""
        return await self._verify_at(height, now,
                                     cache=SignatureCache())

    def trusted_light_block(self, height: int) -> Optional[LightBlock]:
        return self.store.light_block(height)

    # ------------------------------------------------------------------
    def _closest_below(self, height: int) -> LightBlock:
        best = None
        for h in self.store.heights():
            if h <= height:
                best = h
        if best is None:
            raise LightClientError("no trusted block below target")
        return self.store.light_block(best)

    async def _verify_forward(self, trusted: LightBlock, height: int,
                              now: Timestamp,
                              prefetched: Optional[LightBlock] = None,
                              cache: Optional[SignatureCache] = None
                              ) -> LightBlock:
        """Inside the caller's ``light_sync`` span."""
        trace: list[LightBlock] = [trusted]
        if self.mode == SEQUENTIAL:
            lb = await self._verify_sequential(trusted, height, now,
                                               trace, cache)
        else:
            lb = await self._verify_skipping(trusted, height, now,
                                             prefetched, trace, cache)
        with tracing.span(tracing.LIGHT, "light_detect"):
            await self._detect_divergence(lb, now, trace)
        return lb

    async def _fetch(self, provider: Provider,
                     height: int) -> LightBlock:
        with tracing.span(tracing.LIGHT, "light_fetch"):
            return await provider.light_block(height)

    def _hop(self, trusted: LightBlock, candidate: LightBlock,
             now: Timestamp, cache: Optional[SignatureCache]) -> None:
        """One verify() attempt, as a ``light_hop`` span and a step
        of ``cometbft_light_hops_total``; saves a verified candidate.
        Raises what verify() raises."""
        with tracing.span(tracing.LIGHT, "light_hop", candidate.height,
                          trusted=trusted.height,
                          candidate=candidate.height) as sp:
            outcome = "invalid"
            try:
                verify(trusted.signed_header, trusted.validator_set,
                       candidate.signed_header, candidate.validator_set,
                       self.trust_options.period_ns, now,
                       self.max_clock_drift_ns, self.trust_level,
                       cache=cache)
                outcome = "verified"
            except NewValSetCantBeTrustedError:
                outcome = "cant_trust"
                raise
            finally:
                sp.note(outcome=outcome)
                hop_counters()[outcome].add()
        with tracing.span(tracing.LIGHT, "light_store_save"):
            self.store.save_light_block(candidate)

    async def _verify_sequential(self, trusted: LightBlock,
                                 height: int, now: Timestamp,
                                 trace: Optional[list] = None,
                                 cache: Optional[SignatureCache] = None
                                 ) -> LightBlock:
        """Verify every header between trusted and height (reference:
        verifySequential)."""
        current = trusted
        for h in range(trusted.height + 1, height + 1):
            nxt = await self._fetch(self.primary, h)
            self._hop(current, nxt, now, cache)
            if trace is not None:
                trace.append(nxt)
            current = nxt
        return current

    async def _verify_skipping(self, trusted: LightBlock, height: int,
                               now: Timestamp,
                               prefetched: Optional[LightBlock] = None,
                               trace: Optional[list] = None,
                               cache: Optional[SignatureCache] = None
                               ) -> LightBlock:
        """Bisection (reference: verifySkipping): try to jump straight
        to the target; on insufficient trust, bisect."""
        target = prefetched if prefetched is not None and \
            prefetched.height == height else \
            await self._fetch(self.primary, height)
        verified = trusted
        pivots = [target]
        while pivots:
            candidate = pivots[-1]
            try:
                self._hop(verified, candidate, now, cache)
                if trace is not None:
                    trace.append(candidate)
                verified = candidate
                pivots.pop()
            except NewValSetCantBeTrustedError as e:
                # can't jump that far: bisect
                pivot_height = (verified.height + candidate.height) // 2
                if pivot_height in (verified.height, candidate.height):
                    raise LightClientError(
                        "bisection failed: no trust path to target"
                    ) from e
                pivots.append(
                    await self._fetch(self.primary, pivot_height))
        return verified

    async def _backwards(self, first: LightBlock,
                         height: int) -> LightBlock:
        """Verify below the oldest trusted header via hash links
        (reference: backwards)."""
        current = first
        for h in range(first.height - 1, height - 1, -1):
            older = await self.primary.light_block(h)
            verify_backwards(older.signed_header.header,
                             current.signed_header.header)
            self.store.save_light_block(older)
            current = older
        return current

    # ------------------------------------------------------------------
    async def _detect_divergence(self, verified: LightBlock,
                                 now: Timestamp,
                                 trace: Optional[list] = None) -> None:
        """Cross-check the verified header against witnesses; on
        divergence, bisect OUR trace against the witness to find the
        common block, attribute the equivocators, and report evidence to
        both sides (reference: detector.go detectDivergence +
        examineConflictingHeaderAgainstTrace :236 +
        newLightClientAttackEvidence :420)."""
        if not self.witnesses:
            return
        h = verified.height
        target_hash = verified.signed_header.header.hash()
        trace = trace or [verified]
        bad: list[Provider] = []
        for w in self.witnesses:
            try:
                wlb = await self._fetch(w, h)
            except (ProviderError, LightBlockNotFoundError):
                continue
            if wlb.signed_header.header.hash() == target_hash:
                continue
            ev = await self._build_attack_evidence(w, wlb, trace)
            try:
                await self.primary.report_evidence(ev)
                await w.report_evidence(ev)
            except ProviderError:
                pass
            bad.append(w)
        if bad:
            for w in bad:
                self.witnesses.remove(w)
            raise DivergenceError(bad[0], evidence=None)

    async def _build_attack_evidence(self, witness: Provider,
                                     conflicting: LightBlock,
                                     trace: list
                                     ) -> LightClientAttackEvidence:
        """Walk the trace to the LAST block the witness agrees with —
        that is the common block; the trusted block is our verified end
        of trace (reference: examineConflictingHeaderAgainstTrace)."""
        common = trace[0]
        for tb in trace:
            try:
                wb = await witness.light_block(tb.height)
            except (ProviderError, LightBlockNotFoundError):
                break
            if wb.signed_header.header.hash() != \
                    tb.signed_header.header.hash():
                break
            common = tb
        trusted = trace[-1]
        if conflicting.height != common.height:
            common_height = common.height
            timestamp = common.signed_header.header.time
            total_power = common.validator_set.total_voting_power()
        else:
            common_height = trusted.height
            timestamp = trusted.signed_header.header.time
            total_power = trusted.validator_set.total_voting_power()
        ev = LightClientAttackEvidence(
            conflicting_block=conflicting,
            common_height=common_height,
            byzantine_validators=[],
            total_voting_power=total_power,
            timestamp=timestamp)
        ev.byzantine_validators = ev.get_byzantine_validators(
            common.validator_set, trusted.signed_header)
        return ev
