"""Seeded fixtures: keys, validator sets and signed commits.

Copies of cometbft_tpu/tools/benchmarks.py's seeded_privs, _make_valset,
_signed_commit and seeded_commit (the originals are listed in PERF.md's
Open questions for a later PR to delete), kept with the benchmark so no
later PR can change what a cell verifies.  Everything derives from the
seed; a fresh height is a fresh commit no memo or cache has seen.

One change from the originals: signatures are made over
Commit.vote_sign_bytes (the spliced template: ~1.1 us a vote on the CPU
sandbox since PR 30's dedicated timestamp encoder, ~8 before) instead
of building a Vote per validator (~70 us), because data is made anew
in every run and counts as set-up.  ``check_sign_bytes`` pins the two
paths against each other on seeded lanes.
"""
from __future__ import annotations

import hashlib
import random

BASE_TIME_S = 1_700_000_000


def seeded_privs(n: int, seed: int, tag: str = "val") -> list:
    """n distinct deterministic private keys (ed25519)."""
    from cometbft_tpu.crypto import ed25519
    return [ed25519.gen_priv_key_from_secret(
        b"%s-%d-%d" % (tag.encode(), seed, i)) for i in range(n)]


def make_valset(privs, power: int = 10):
    """(ValidatorSet, privs re-paired to the set's own order)."""
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet

    vset = ValidatorSet([Validator.new(p.pub_key(), power)
                         for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    return vset, [by_addr[v.address] for v in vset.validators]


def signed_commit(chain_id: str, vset, privs, height: int, block_id,
                  base_s: int = BASE_TIME_S):
    """A Commit for ``block_id`` with one real precommit signature per
    validator, each vote with its own timestamp as on a live chain."""
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_COMMIT, Commit, CommitSig,
    )
    from cometbft_tpu.types.timestamp import Timestamp

    commit = Commit(height=height, round=0, block_id=block_id,
                    signatures=[
                        CommitSig(block_id_flag=BLOCK_ID_FLAG_COMMIT,
                                  validator_address=val.address,
                                  timestamp=Timestamp(base_s + height, i))
                        for i, val in enumerate(vset.validators)])
    for i, priv in enumerate(privs):
        commit.signatures[i].signature = priv.sign(
            commit.vote_sign_bytes(chain_id, i))
    # hand the commit over as a decoder would: no memo warmed by us
    commit.__dict__.pop("_vsb_tmpls", None)
    return commit


def check_sign_bytes(chain_id: str, vset, commit, rng: random.Random,
                     lanes: int = 4) -> None:
    """The spliced sign-bytes equal the Vote path's on seeded lanes."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.vote import Vote
    for i in rng.sample(range(vset.size()), min(lanes, vset.size())):
        cs = commit.signatures[i]
        vote = Vote(type=canonical.PRECOMMIT_TYPE, height=commit.height,
                    round=commit.round, block_id=commit.block_id,
                    timestamp=cs.timestamp,
                    validator_address=cs.validator_address,
                    validator_index=i)
        if vote.sign_bytes(chain_id) != \
                commit.vote_sign_bytes(chain_id, i):
            raise RuntimeError(f"sign-bytes paths disagree at lane {i}")
    commit.__dict__.pop("_vsb_tmpls", None)


def seeded_block_id(seed: int, height: int):
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.part_set import PartSetHeader
    return BlockID(
        hash=hashlib.sha256(b"block-%d-%d" % (seed, height)).digest(),
        part_set_header=PartSetHeader(1, b"\x34" * 32))


def flip_bit(rng: random.Random, b: bytes, lo: int, hi: int) -> bytes:
    """b with one seeded bit flipped in bytes [lo, hi)."""
    i = rng.randrange(lo, hi)
    return b[:i] + bytes([b[i] ^ (1 << rng.randrange(8))]) + b[i + 1:]


def seeded_tx(seed: int, height: int, index: int, size: int) -> bytes:
    """A kvstore tx ``key=value`` of exactly ``size`` bytes."""
    key = b"s%d-h%d-t%d" % (seed, height, index)
    fill = hashlib.sha256(key).hexdigest().encode()
    need = size - len(key) - 1
    if need <= 0:
        raise ValueError(f"tx size {size} too small for key {key!r}")
    return key + b"=" + (fill * (need // len(fill) + 1))[:need]
