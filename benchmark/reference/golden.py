"""The comparison that decides ``correct`` for signature verdicts.

Copies of chip_smoke.py's _edge_lanes and _check_mask (originals listed
in PERF.md's Open questions): the per-lane mask of the BatchVerifier
seam against crypto/_ed25519_ref (the golden model, independent of
every kernel) on forged and ZIP-215 edge lanes plus a seeded honest
sample, and against the per-signature CPU verifier on every lane.
"""
from __future__ import annotations

import random

from .fixtures import flip_bit


def edge_lanes(rng: random.Random, items: list) -> list:
    """Forged lanes and the ZIP-215 edge vectors, seeded: (pub, msg,
    sig) triples whose verdicts only the golden model is trusted to
    know."""
    from cometbft_tpu.crypto import _ed25519_ref as ref

    def small_order() -> bytes:
        while True:
            pt = ref.decompress(rng.randbytes(32))
            if pt is None:
                continue
            tor = ref.scalar_mult(ref.L, pt)
            if tor != (0, 1):
                return ref.compress(tor)

    def honest():
        return items[rng.randrange(len(items))]

    lanes = []
    for _ in range(3):                      # forged R, forged S
        pub, msg, sig = honest()
        lanes.append((pub, msg, flip_bit(rng, sig, 0, 32)))
        pub, msg, sig = honest()
        lanes.append((pub, msg, flip_bit(rng, sig, 32, 63)))
    pub, msg, sig = honest()
    lanes.append((pub, msg + b"tampered", sig))         # wrong message
    pub, msg, sig = honest()
    lanes.append((pub, msg, sig[:32] + bytes(32)))      # S = 0
    pub, msg, sig = honest()                            # S + L
    s = int.from_bytes(sig[32:], "little") + ref.L
    lanes.append((pub, msg, sig[:32] + s.to_bytes(32, "little")))
    lanes.append((rng.randbytes(32), msg, sig))         # arbitrary A
    pub, msg, sig = honest()
    lanes.append((pub, msg, sig[:32] + rng.randbytes(32)))
    # small-order A and R with S = 0: accepted cofactored, any message
    for msg in (b"", b"arbitrary", rng.randbytes(100)):
        lanes.append((small_order(), msg, small_order() + bytes(32)))
    # non-canonical y: p + 1 encodes the identity (y = 1)
    enc = (ref.P + 1).to_bytes(32, "little")
    lanes.append((small_order(), b"m", enc + bytes(32)))
    lanes.append((enc, b"m", small_order() + bytes(32)))
    return lanes


def commit_items(chain_id: str, vset, commit) -> list:
    return [(vset.validators[i].pub_key.bytes(),
             commit.vote_sign_bytes(chain_id, i),
             commit.signatures[i].signature)
            for i in range(vset.size())]


def seam_mask(items: list) -> tuple[bool, list[bool]]:
    """One batch through the program's BatchVerifier seam, no
    signature cache anywhere near: the verifier sees raw lanes."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519

    bv = crypto_batch.create_batch_verifier(
        ed25519.Ed25519PubKey(items[0][0]))
    for pub, msg, sig in items:
        bv.add(ed25519.Ed25519PubKey(pub), msg, sig)
    ok, mask = bv.verify()
    return bool(ok), [bool(g) for g in mask]


def check_mask(items: list, rng: random.Random,
               honest_sample: int = 32) -> dict:
    """Splice the edge lanes into seeded slots of ``items`` (an honest
    commit's lanes), run the seam at that shape, and compare.  Raises
    on any disagreement; returns what was compared."""
    from cometbft_tpu.crypto import _ed25519_ref as ref

    items = list(items)
    n = len(items)
    special = edge_lanes(rng, items)[:max(1, n // 2)]
    slots = rng.sample(range(n), len(special))
    for slot, lane in zip(slots, special):
        items[slot] = lane
    honest = sorted(set(range(n)) - set(slots))
    sample = rng.sample(honest, min(honest_sample, len(honest)))
    ok, mask = seam_mask(items)
    golden_at = sorted(set(slots) | set(sample))
    bad = [i for i in golden_at if mask[i] != ref.verify(*items[i])]
    if bad:
        raise RuntimeError(
            f"mask disagrees with the golden model at lanes {bad[:8]} "
            f"of {len(golden_at)} checked")
    compare_cpu(items, mask)
    if not all(mask[i] for i in honest) or ok != all(mask):
        raise RuntimeError("an honest lane was rejected")
    return {"lanes": n, "golden_lanes": len(golden_at),
            "edge_lanes": len(special),
            "rejected": sum(1 for i in slots if not mask[i])}


def compare_cpu(items: list, mask: list[bool]) -> None:
    """Every lane of ``mask`` against the per-signature CPU verifier."""
    from cometbft_tpu.crypto import ed25519
    bad = [i for i, (pub, msg, sig) in enumerate(items)
           if mask[i] != ed25519.Ed25519PubKey(pub).verify_signature(
               msg, sig)]
    if bad:
        raise RuntimeError(
            f"mask disagrees with the per-signature CPU verifier at "
            f"lanes {bad[:8]}")
