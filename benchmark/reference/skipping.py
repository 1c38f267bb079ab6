"""The plain reference of configuration ``light-1k``: upstream's
skipping verification (light/client.go verifySkipping over
light/verifier.go VerifyNonAdjacent / VerifyAdjacent), and the seeded
chain it is run on.

Independent of the program's light client (cometbft_tpu/light/), of its
commit walk (types/validation) and of the BatchVerifier seam: light
blocks are plain tuples, the rule is the few dozen lines of
``verify_skipping`` and ``_hop``, and a signature's verdict comes from
the per-signature CPU verifier (``golden_sample`` holds that verifier
to crypto/_ed25519_ref on seeded lanes, as reference/golden.py does).
What it does not model: header times and the trusting period (the
chain's are a second apart and fresh), and absent or nil votes (every
validator signs for the block).

The chain (``build_chain``) is made with the program's types, as
fixtures.py makes commits: keys by ``seeded_privs``, sets by
``make_valset``, commits by ``signed_commit``, headers that carry the
real ``validators_hash`` / ``next_validators_hash`` / ``last_block_id``.
At every height the ``churn`` oldest keys leave the set and as many new
ones join it.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Callable, Optional

from . import fixtures

VERIFIED = "verified"
CANT_TRUST = "cant_trust"
INVALID = "invalid"


@dataclass(frozen=True)
class PlainBlock:
    """A light block as plain data.  ``validators``: one ``(address,
    pubkey, power)`` per validator in the set's order; ``lanes``: one
    ``(address, sign_bytes, signature)`` per commit signature in index
    order, every one a vote for the block.  ``set_hash`` is the hash of
    the set that came with the block, ``validators_hash`` what its
    header says."""
    height: int
    validators: tuple
    lanes: tuple
    validators_hash: bytes
    next_validators_hash: bytes
    set_hash: bytes
    header_hash: bytes


@dataclass(frozen=True)
class Check:
    """One commit check of a hop.  ``walked``: how many commit
    signatures it looked at before it knew its tally; ``taken``: the
    commit indices of the signatures the tally rests on; ``verified``:
    those of them that HAD to be verified, because nothing earlier in
    the request had proved them under the same key and message
    (upstream's SignatureCache)."""
    walked: int
    taken: tuple
    verified: tuple = ()


@dataclass(frozen=True)
class Hop:
    """One attempt to get from a trusted height to a candidate.
    ``index`` is the commit index an ``invalid`` verdict names when a
    signature is wrong.  ``trusting`` is the check of the trusted set
    (signers by address), ``light`` the check of the new set (by
    index); a check the attempt never came to is None."""
    trusted: int
    candidate: int
    outcome: str
    index: Optional[int] = None
    trusting: Optional[Check] = None
    light: Optional[Check] = None


def cpu_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    from cometbft_tpu.crypto import ed25519
    return ed25519.Ed25519PubKey(pub).verify_signature(msg, sig)


def _verify(lanes, walked: int, taken: list, keys: list, proved: set,
            verify_sig) -> tuple:
    """(the Check, the lowest wrong commit index or None)."""
    todo = [(i, (k, lanes[i][1], lanes[i][2]))
            for i, k in zip(taken, keys)
            if (k, lanes[i][1], lanes[i][2]) not in proved]
    check = Check(walked, tuple(taken), tuple(i for i, _ in todo))
    for i, item in todo:
        if not verify_sig(*item):
            return check, i
        proved.add(item)
    return check, None


def _hop(trusted: PlainBlock, cand: PlainBlock, trust_level: tuple,
         verify_sig: Callable, proved: set) -> Hop:
    def hop(outcome, **kw):
        return Hop(trusted.height, cand.height, outcome, **kw)

    if cand.validators_hash != cand.set_hash or \
            len(cand.lanes) != len(cand.validators):
        return hop(INVALID)
    trusting = None
    if cand.height == trusted.height + 1:
        if cand.validators_hash != trusted.next_validators_hash:
            return hop(INVALID)
    else:
        # more than trust_level of the TRUSTED set's power, signers
        # looked up by address, in commit order, up to the one that
        # carries the tally past the mark
        known = {addr: (pub, power)
                 for addr, pub, power in trusted.validators}
        needed = sum(p for _, p in known.values()) \
            * trust_level[0] // trust_level[1]
        tally, taken, voted = 0, [], set()
        for i, (addr, _, _) in enumerate(cand.lanes):
            if addr not in known:
                continue
            if addr in voted:
                return hop(INVALID)             # a double vote
            voted.add(addr)
            taken.append(i)
            tally += known[addr][1]
            if tally > needed:
                break
        if tally <= needed:     # judged before any signature is verified
            return hop(CANT_TRUST, trusting=Check(len(cand.lanes),
                                                  tuple(taken)))
        trusting, bad = _verify(
            cand.lanes, taken[-1] + 1, taken,
            [known[cand.lanes[i][0]][0] for i in taken], proved,
            verify_sig)
        if bad is not None:
            return hop(INVALID, index=bad, trusting=trusting)
    # more than 2/3 of the NEW set's power, by index
    needed = sum(p for _, _, p in cand.validators) * 2 // 3
    tally, taken = 0, []
    for i, (_, _, power) in enumerate(cand.validators):
        taken.append(i)
        tally += power
        if tally > needed:
            break
    if tally <= needed:
        return hop(INVALID, trusting=trusting)
    light, bad = _verify(cand.lanes, len(taken), taken,
                         [cand.validators[i][1] for i in taken],
                         proved, verify_sig)
    return hop(VERIFIED if bad is None else INVALID, index=bad,
               trusting=trusting, light=light)


def verify_skipping(fetch: Callable[[int], PlainBlock],
                    trusted_height: int, target_height: int,
                    trust_level: tuple = (1, 3),
                    verify_sig: Callable = cpu_verify) -> list[Hop]:
    """Upstream's verifySkipping: try the target; on less than
    ``trust_level`` of the trusted power push the midpoint; on success
    pop.  Every attempt is returned, in order; the walk ends at the
    first ``invalid`` one (never bisected) or with the target
    verified."""
    proved: set = set()
    verified = fetch(trusted_height)
    pivots = [fetch(target_height)]
    hops: list[Hop] = []
    while pivots:
        cand = pivots[-1]
        hops.append(_hop(verified, cand, trust_level, verify_sig,
                         proved))
        if hops[-1].outcome == VERIFIED:
            verified = pivots.pop()
        elif hops[-1].outcome == CANT_TRUST and \
                cand.height - verified.height > 1:
            pivots.append(fetch((verified.height + cand.height) // 2))
        else:
            break
    return hops


def dispatched(block: PlainBlock, trusted: PlainBlock, hop: Hop) -> list:
    """The batches of lanes ``(pubkey, sign_bytes, signature)`` a hop
    had to have verified: the trusting check's under the TRUSTED set's
    keys, the light check's under the new set's."""
    key = {addr: pub for addr, pub, _ in trusted.validators}
    out = []
    if hop.trusting is not None and hop.trusting.verified:
        out.append([(key[block.lanes[i][0]],) + block.lanes[i][1:]
                    for i in hop.trusting.verified])
    if hop.light is not None and hop.light.verified:
        out.append([(block.validators[i][1],) + block.lanes[i][1:]
                    for i in hop.light.verified])
    return out


def golden_sample(blocks, rng: random.Random, lanes: int = 8) -> int:
    """The per-signature CPU verifier against the golden model
    (crypto/_ed25519_ref, pure Python) on seeded lanes of ``blocks``,
    each as signed and with one bit of the signature flipped."""
    from cometbft_tpu.crypto import _ed25519_ref as ref
    blocks = list(blocks)
    for _ in range(lanes):
        block = blocks[rng.randrange(len(blocks))]
        i = rng.randrange(len(block.lanes))
        pub, (_, msg, sig) = block.validators[i][1], block.lanes[i]
        for s in (sig, fixtures.flip_bit(rng, sig, 0, 64)):
            if cpu_verify(pub, msg, s) != ref.verify(pub, msg, s):
                raise RuntimeError(
                    f"the CPU verifier and the golden model disagree "
                    f"at height {block.height}, lane {i}")
    return 2 * lanes


# -- the seeded chain ------------------------------------------------------

@dataclass
class Chain:
    chain_id: str
    blocks: dict            # height -> LightBlock (the program's type)
    now: object             # Timestamp: ``now_after_tip_s`` past the tip

    def header_hash(self, height: int) -> bytes:
        return self.blocks[height].signed_header.commit.block_id.hash


def build_chain(chain_id: str, seed: int, validators: int, power: int,
                churn: int, heights: int,
                now_after_tip_s: int = 600) -> Chain:
    """Heights 1..``heights``, each signed by every one of its
    ``validators`` validators; the set at height h is keys
    [(h-1)*churn, (h-1)*churn + validators) of one seeded sequence, so
    it keeps ``validators - k*churn`` of the set k heights before.
    Header times are a second apart, every vote has its own
    timestamp."""
    from cometbft_tpu.types.block import Header, LightBlock, SignedHeader
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp

    keys = fixtures.seeded_privs(validators + churn * heights, seed,
                                 "light")

    def valset(height: int):
        lo = (height - 1) * churn
        return fixtures.make_valset(keys[lo:lo + validators], power)

    blocks: dict = {}
    prev_id = BlockID()
    vset, privs = valset(1)
    for h in range(1, heights + 1):
        next_vset, next_privs = valset(h + 1)
        header = Header(
            chain_id=chain_id, height=h,
            time=Timestamp(fixtures.BASE_TIME_S + h, 0),
            last_block_id=prev_id, validators_hash=vset.hash(),
            next_validators_hash=next_vset.hash(),
            proposer_address=vset.get_proposer().address)
        prev_id = BlockID(
            hash=header.hash(),
            part_set_header=PartSetHeader(1, b"\x57" * 32))
        commit = fixtures.signed_commit(chain_id, vset, privs, h,
                                        prev_id)
        blocks[h] = LightBlock(
            signed_header=SignedHeader(header=header, commit=commit),
            validator_set=vset)
        vset, privs = next_vset, next_privs
    return Chain(chain_id, blocks, Timestamp(
        fixtures.BASE_TIME_S + heights + now_after_tip_s, 0))


def plain(chain_id: str, lb) -> PlainBlock:
    """A LightBlock of the program as plain data."""
    header, commit = lb.signed_header.header, lb.signed_header.commit
    out = PlainBlock(
        height=header.height,
        validators=tuple((v.address, v.pub_key.bytes(), v.voting_power)
                         for v in lb.validator_set.validators),
        lanes=tuple((cs.validator_address,
                     commit.vote_sign_bytes(chain_id, i), cs.signature)
                    for i, cs in enumerate(commit.signatures)),
        validators_hash=header.validators_hash,
        next_validators_hash=header.next_validators_hash,
        set_hash=lb.validator_set.hash(),
        header_hash=header.hash())
    # the block is handed on as a decoder would hand it: no memo of ours
    commit.__dict__.pop("_vsb_tmpls", None)
    return out
