"""ValidatorSet: sorted validator list with proposer-priority round-robin.

Reference: types/validator_set.go — deterministic proposer selection
(:122-250), change-set updates with priority rescaling (:430-717), hash over
SimpleValidator bytes.  Byte-for-byte reproducibility of the priority
arithmetic (int64 clipping, floor-average centering) is consensus-critical.
"""
from __future__ import annotations

from array import array
from typing import Iterable, Optional, Sequence

from ..crypto import merkle
from ..crypto.ed25519 import Ed25519PubKey
from .validator import (
    INT64_MAX, INT64_MIN, MAX_TOTAL_VOTING_POWER,
    PRIORITY_WINDOW_SIZE_FACTOR, Validator, ValidatorError,
    safe_add_clip, safe_sub_clip,
)


class ValidatorSetError(Exception):
    pass


class TotalVotingPowerOverflowError(ValidatorSetError):
    pass


def _by_voting_power_key(v: Validator):
    # descending voting power, then ascending address
    return (-v.voting_power, v.address)


class ValidatorSet:
    def __init__(self, validators: Optional[Iterable[Validator]] = None):
        """NewValidatorSet: apply initial change-set then rotate proposer
        once.  Raises on invalid input (reference panics)."""
        self.validators: list[Validator] = []
        self.proposer: Optional[Validator] = None
        self._total_voting_power = 0
        self._all_keys_same_type = True
        self._hash_memo: Optional[bytes] = None
        self._addr_index_memo: Optional[dict] = None
        self._key_table_memo = None     # see ed25519_key_table
        vals = [v.copy() for v in (validators or [])]
        if vals:
            self._update_with_change_set(vals, allow_deletes=False)
            self.increment_proposer_priority(1)

    # ------------------------------------------------------------------
    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def __len__(self) -> int:
        return len(self.validators)

    def size(self) -> int:
        return len(self.validators)

    def copy(self) -> "ValidatorSet":
        cp = ValidatorSet()
        cp.validators = [v.copy() for v in self.validators]
        cp.proposer = self.proposer.copy() if self.proposer else None
        cp._total_voting_power = self._total_voting_power
        cp._all_keys_same_type = self._all_keys_same_type
        cp._hash_memo = self._hash_memo
        # _addr_index_memo and _key_table_memo stay None: rebuilt
        # lazily on first use
        return cp

    def has_address(self, address: bytes) -> bool:
        return any(v.address == address for v in self.validators)

    def get_by_address(self, address: bytes) -> tuple[int, Optional[Validator]]:
        for i, v in enumerate(self.validators):
            if v.address == address:
                return i, v.copy()
        return -1, None

    def index_by_address(self, address: bytes) -> int:
        """Index of the validator with ``address``, or -1.  O(1) after
        the first call: the address->index map is built once per
        mutation generation (invalidated with the hash memo in
        _update_with_change_set) — the aggregate-commit trusting path
        resolves every signer by address, which with the linear
        get_by_address scan was O(n^2) at 10k validators."""
        memo = self._addr_index_memo
        if memo is None:
            memo = {v.address: i for i, v in enumerate(self.validators)}
            self._addr_index_memo = memo
        return memo.get(address, -1)

    def ed25519_key_table(self) -> Optional[tuple[tuple, bytes]]:
        """(keys, powers) for the native commit walk
        (types/validation._walk_native): every validator's raw 32-byte
        ed25519 key in set order (None for a nil key) and the voting
        powers as native int64s; None when a key is of another type.
        Built once per mutation generation: keys and powers change
        only through _update_with_change_set, which drops it with the
        hash memo, and a copy builds its own."""
        memo = self._key_table_memo
        if memo is None:
            memo = self._key_table_memo = self._build_key_table()
        return memo or None

    def _build_key_table(self):
        keys = []
        for v in self.validators:
            pk = v.pub_key
            if pk is None:
                keys.append(None)
            elif isinstance(pk, Ed25519PubKey):
                keys.append(pk.bytes())
            else:
                return False    # memoised: not a table to build
        try:
            powers = array("q", [v.voting_power for v in self.validators])
        except OverflowError:
            return False
        return tuple(keys), powers.tobytes()

    def get_by_index(self, index: int) -> tuple[bytes, Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v.copy()

    def all_keys_have_same_type(self) -> bool:
        return self._all_keys_same_type

    def _check_all_keys_same_type(self) -> None:
        types = {v.pub_key.type() for v in self.validators
                 if v.pub_key is not None}
        self._all_keys_same_type = len(types) <= 1

    # ------------------------------------------------------------------
    def total_voting_power(self) -> int:
        if self._total_voting_power == 0 and self.validators:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total = safe_add_clip(total, v.voting_power)
            if total > MAX_TOTAL_VOTING_POWER:
                raise TotalVotingPowerOverflowError(
                    f"total voting power exceeds {MAX_TOTAL_VOTING_POWER}")
        self._total_voting_power = total

    # ------------------------------------------------------------------
    # Proposer selection (reference: validator_set.go:122-250)

    def get_proposer(self) -> Validator:
        if not self.validators:
            raise ValidatorSetError("empty validator set")
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer = None
        for v in self.validators:
            proposer = v if proposer is None else \
                proposer.compare_proposer_priority(v)
        return proposer

    def increment_proposer_priority(self, times: int) -> None:
        if self.is_nil_or_empty():
            raise ValidatorSetError("empty validator set")
        if times <= 0:
            raise ValidatorSetError(
                "cannot call increment_proposer_priority with "
                "non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        cp = self.copy()
        cp.increment_proposer_priority(times)
        return cp

    def advance_proposer_priority_step(self) -> None:
        """One raw increment step WITHOUT the rescale+shift prologue —
        the k-th loop iteration of increment_proposer_priority(k).
        Chaining increment(1) calls instead would re-run the prologue
        each step and diverge from a one-shot increment(k) whenever
        the priority spread exceeds the rescale window; the state
        store's roll-forward cache uses this to stay bit-identical to
        the cold LoadValidators path."""
        self.proposer = self._increment_proposer_priority()

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = safe_add_clip(
                v.proposer_priority, v.voting_power)
        mostest = self._find_proposer()
        mostest.proposer_priority = safe_sub_clip(
            mostest.proposer_priority, self.total_voting_power())
        return mostest

    def rescale_priorities(self, diff_max: int) -> None:
        if self.is_nil_or_empty():
            raise ValidatorSetError("empty validator set")
        if diff_max <= 0:
            return
        diff = self._max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                # Go int64 division truncates toward zero
                p = v.proposer_priority
                v.proposer_priority = -(-p // ratio) if p < 0 else p // ratio

    def _max_min_priority_diff(self) -> int:
        mx = max(v.proposer_priority for v in self.validators)
        mn = min(v.proposer_priority for v in self.validators)
        return abs(mx - mn)

    def _compute_avg_proposer_priority(self) -> int:
        # big-int sum then floor division (Go big.Int.Div is Euclidean,
        # equal to floor for positive divisor)
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        return total // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = safe_sub_clip(v.proposer_priority, avg)

    # ------------------------------------------------------------------
    # Change-set updates (reference: validator_set.go:430-717)

    def update_with_change_set(self, changes: Sequence[Validator]) -> None:
        self._update_with_change_set(
            [v.copy() for v in changes], allow_deletes=True)

    def _update_with_change_set(self, changes: list[Validator],
                                allow_deletes: bool) -> None:
        if not changes:
            return
        self._hash_memo = None
        self._addr_index_memo = None
        self._key_table_memo = None
        updates, deletes = self._process_changes(changes)
        if not allow_deletes and deletes:
            raise ValidatorSetError(
                "cannot process validators with voting power 0")
        new_count = sum(1 for u in updates
                        if not self.has_address(u.address))
        if new_count == 0 and len(self.validators) == len(deletes):
            raise ValidatorSetError(
                "applying the validator changes would result in empty set")
        removed_power = self._verify_removals(deletes)
        tvp_after_updates = self._verify_updates(updates, removed_power)
        self._compute_new_priorities(updates, tvp_after_updates)
        self._apply_updates(updates)
        self._apply_removals(deletes)
        self._check_all_keys_same_type()
        self._update_total_voting_power()
        self.rescale_priorities(
            PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        self.validators.sort(key=_by_voting_power_key)

    @staticmethod
    def _process_changes(changes: list[Validator]):
        changes = sorted(changes, key=lambda v: v.address)
        updates: list[Validator] = []
        deletes: list[Validator] = []
        prev_addr = None
        for v in changes:
            if v.address == prev_addr:
                raise ValidatorSetError(f"duplicate entry {v}")
            if v.voting_power < 0:
                raise ValidatorSetError("voting power can't be negative")
            if v.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValidatorSetError(
                    f"voting power can't exceed {MAX_TOTAL_VOTING_POWER}")
            if v.voting_power == 0:
                deletes.append(v)
            else:
                updates.append(v)
            prev_addr = v.address
        return updates, deletes

    def _verify_updates(self, updates: list[Validator],
                        removed_power: int) -> int:
        def delta(u: Validator) -> int:
            _, val = self.get_by_address(u.address)
            return u.voting_power - val.voting_power if val else \
                u.voting_power

        tvp_after_removals = self.total_voting_power() - removed_power
        for u in sorted(updates, key=delta):
            tvp_after_removals += delta(u)
            if tvp_after_removals > MAX_TOTAL_VOTING_POWER:
                raise TotalVotingPowerOverflowError(
                    "total voting power overflow")
        return tvp_after_removals + removed_power

    def _verify_removals(self, deletes: list[Validator]) -> int:
        removed = 0
        for d in deletes:
            _, val = self.get_by_address(d.address)
            if val is None:
                raise ValidatorSetError(
                    f"failed to find validator {d.address.hex()} to remove")
            removed += val.voting_power
        if len(deletes) > len(self.validators):
            raise ValidatorSetError("more deletes than validators")
        return removed

    def _compute_new_priorities(self, updates: list[Validator],
                                updated_tvp: int) -> None:
        for u in updates:
            _, val = self.get_by_address(u.address)
            if val is None:
                # new validator starts at -1.125*totalVotingPower so
                # unbond/re-bond can't reset a negative priority
                u.proposer_priority = -(updated_tvp + (updated_tvp >> 3))
            else:
                u.proposer_priority = val.proposer_priority

    def _apply_updates(self, updates: list[Validator]) -> None:
        existing = sorted(self.validators, key=lambda v: v.address)
        merged: list[Validator] = []
        i = j = 0
        while i < len(existing) and j < len(updates):
            if existing[i].address < updates[j].address:
                merged.append(existing[i])
                i += 1
            else:
                merged.append(updates[j])
                if existing[i].address == updates[j].address:
                    i += 1
                j += 1
        merged.extend(existing[i:])
        merged.extend(updates[j:])
        self.validators = merged

    def _apply_removals(self, deletes: list[Validator]) -> None:
        if not deletes:
            return
        gone = {d.address for d in deletes}
        self.validators = [v for v in self.validators
                           if v.address not in gone]

    # ------------------------------------------------------------------
    def hash(self) -> bytes:
        """Merkle root over SimpleValidator bytes (reference:
        validator_set.go Hash).

        Memoized: the hash covers (pubkey, power) only, which change
        solely through update_with_change_set (the invalidation
        point) — proposer-priority rotation does not touch it.  At
        10k validators the recompute is ~40 ms and sat directly on
        the aggregate-commit verify path."""
        if self._hash_memo is None:
            self._hash_memo = merkle.hash_from_byte_slices(
                [v.bytes() for v in self.validators])
        return self._hash_memo

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValidatorSetError("validator set is nil or empty")
        for v in self.validators:
            v.validate_basic()
        if self.proposer is None:
            raise ValidatorSetError("proposer failed validate basic")
        self.proposer.validate_basic()
        if not any(v.address == self.proposer.address
                   for v in self.validators):
            raise ValidatorSetError("proposer not in validator set")

    def to_proto(self) -> dict:
        d: dict = {
            "validators": [v.to_proto() for v in self.validators],
            "total_voting_power": self.total_voting_power(),
        }
        if self.proposer is not None:
            d["proposer"] = self.proposer.to_proto()
        return d

    @classmethod
    def from_proto(cls, d: dict) -> "ValidatorSet":
        vs = cls()
        vs.validators = [Validator.from_proto(v)
                         for v in d.get("validators", [])]
        if d.get("proposer") is not None:
            vs.proposer = Validator.from_proto(d["proposer"])
        vs._check_all_keys_same_type()
        if vs.validators:
            vs._update_total_voting_power()
        return vs

    def __iter__(self):
        return iter(self.validators)

    def __str__(self) -> str:
        prop = self.proposer.address.hex().upper()[:12] \
            if self.proposer else "nil"
        return (f"ValidatorSet{{P:{prop} N:{len(self.validators)} "
                f"TVP:{self.total_voting_power()}}}")
