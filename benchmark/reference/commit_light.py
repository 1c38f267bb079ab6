"""The plain reference of configuration ``valset-10k``: the quorum rule
of upstream's VerifyCommitLight (types/validation.go), over one
commit's lanes in index order.

Independent of the program's walk (types/validation._walk_commit), of
the BatchVerifier seam and of every kernel: per-signature verdicts come
from crypto/_ed25519_ref.verify (pure-Python ZIP-215) and the rule is
the dozen lines below.  The CPU tests hold verify_commit_light to it,
forgery by forgery (tests/test_commit_tiled.py).
"""
from __future__ import annotations

ACCEPTED = "accepted"
WRONG_SIGNATURE = "wrong_signature"
NOT_ENOUGH_POWER = "not_enough_power"


def verify_light(lanes, total_power: int) -> tuple:
    """``lanes``: one ``(pubkey, sign_bytes, signature, power)`` per
    commit signature, in index order, every one a vote for the block.

    Signatures are taken in index order up to and including the one
    that carries the tally past 2/3 of ``total_power``; none after it
    is looked at.  Returns ``(ACCEPTED, None)``, or
    ``(WRONG_SIGNATURE, i)`` with the lowest wrong index among those
    taken, or ``(NOT_ENOUGH_POWER, tallied)`` — judged first, as
    upstream's batch path does."""
    from cometbft_tpu.crypto import _ed25519_ref as ref

    needed = total_power * 2 // 3
    tallied, taken = 0, 0
    for _, _, _, power in lanes:
        taken += 1
        tallied += power
        if tallied > needed:
            break
    if tallied <= needed:
        return NOT_ENOUGH_POWER, tallied
    for i, (pub, msg, sig, _) in enumerate(lanes[:taken]):
        if not ref.verify(pub, msg, sig):
            return WRONG_SIGNATURE, i
    return ACCEPTED, None
