"""Runnable BASELINE benchmark configs.

BASELINE.md lists five reproduction configs; #1 (the live 4-validator
kvstore testnet) is `tools/manifest.py` + `cometbft_tpu.cmd load`,
and this module packages the verification-workload ones:

  #2  BatchVerifier microbench at 64 / 1k / 10k ed25519 sigs
  #3  light-client skipping verification: the benchmark's cell
      light-1k.skip (benchmark/traffic/skip.py), not this module
  #4  consensus replay: per-height VoteSet tally + Commit verify
  #5  stress: large mixed-key commit + bls12381 aggregate path

Run:  python -m cometbft_tpu.tools.benchmarks [--full] [--config N]
Each config prints one JSON line.  --full uses the BASELINE sizes
(1k/10k); the default sizes finish in seconds on a laptop CPU.
"""
from __future__ import annotations

import argparse
import json
import time


def _now() -> float:
    return time.perf_counter()


# --- seeded workloads -------------------------------------------------------
# Everything a measurement command verifies is made from its --seed
# (gen_priv_key_from_secret, never `secrets`), so two runs of the same
# command check the same data and a reference can recompute it.

MSG_LEN = 110       # a precommit's canonical sign-bytes, roughly


def seeded_privs(n: int, seed: int, tag: str = "val") -> list:
    """n distinct deterministic ed25519 private keys."""
    from ..crypto import ed25519
    return [ed25519.gen_priv_key_from_secret(
        b"%s-%d-%d" % (tag.encode(), seed, i)) for i in range(n)]


def seeded_sig_items(n: int, seed: int) -> list:
    """n (pub_bytes, msg, sig) triples with DISTINCT keys over
    distinct MSG_LEN-byte messages — the shape of an n-validator
    commit burst at the raw BatchVerifier seam."""
    import hashlib
    base = hashlib.sha512(b"msg-%d" % seed).digest() * 2
    items = []
    for i, priv in enumerate(seeded_privs(n, seed, "sig")):
        msg = base[:MSG_LEN - 8] + i.to_bytes(8, "little")
        items.append((priv.pub_key().bytes(), msg, priv.sign(msg)))
    return items


def seeded_commit(n_vals: int, seed: int, height: int = 1,
                  chain_id: str = "seeded-chain"):
    """An n_vals-validator equal-power ed25519 set and the commit it
    signs at ``height``: (chain_id, vset, block_id, commit).
    The keys depend on the seed only, the block id and signatures on
    the height too — a fresh height is a fresh commit no memo has
    seen."""
    import hashlib

    from ..types.block_id import BlockID
    from ..types.part_set import PartSetHeader

    vset, privs = _make_valset(seeded_privs(n_vals, seed))
    bid = BlockID(
        hash=hashlib.sha256(b"block-%d-%d" % (seed, height)).digest(),
        part_set_header=PartSetHeader(1, b"\x34" * 32))
    commit = _signed_commit(chain_id, vset, privs, height, bid)
    return chain_id, vset, bid, commit


def _make_valset(privs):
    """ValidatorSet sorted the consensus way, with privkeys re-paired
    to the sorted order (shared by configs #3/#4/#5)."""
    from ..types.validator_set import Validator, ValidatorSet

    vals = [Validator.new(p.pub_key(), 10) for p in privs]
    pairs = sorted(zip(vals, privs),
                   key=lambda vp: (-vp[0].voting_power,
                                   vp[0].address))
    vals = [p[0] for p in pairs]
    privs = [p[1] for p in pairs]
    return ValidatorSet(vals), privs


def _signed_commit(chain_id, vset, privs, height, bid,
                   base_s=1700000000):
    """Commit with one real precommit signature per validator."""
    from ..types import canonical
    from ..types.commit import (BLOCK_ID_FLAG_COMMIT, Commit,
                                CommitSig)
    from ..types.timestamp import Timestamp
    from ..types.vote import Vote

    sigs = []
    for i, (val, priv) in enumerate(zip(vset.validators, privs)):
        ts = Timestamp(base_s + height, i)
        v = Vote(type=canonical.PRECOMMIT_TYPE, height=height,
                 round=0, block_id=bid, timestamp=ts,
                 validator_address=val.address, validator_index=i)
        sigs.append(CommitSig(block_id_flag=BLOCK_ID_FLAG_COMMIT,
                              validator_address=val.address,
                              timestamp=ts,
                              signature=priv.sign(
                                  v.sign_bytes(chain_id))))
    return Commit(height=height, round=0, block_id=bid,
                  signatures=sigs)


def config2_batch_verify(sizes=(64, 1024, 10_000)) -> dict:
    """Reference seam: crypto/ed25519 BatchVerifier ->
    types/validation.go verifyCommitBatch."""
    from ..crypto import batch, ed25519

    results = {}
    for n in sizes:
        privs = [ed25519.gen_priv_key() for _ in range(n)]
        items = []
        for i, p in enumerate(privs):
            msg = b"vote-%d" % i
            items.append((p.pub_key(), msg, p.sign(msg)))
        bv = batch.create_batch_verifier(items[0][0])
        for pub, msg, sig in items:
            bv.add(pub, msg, sig)
        t0 = _now()
        ok, mask = bv.verify()
        dt = (_now() - t0) * 1000
        assert ok and all(mask)
        results[str(n)] = round(dt, 2)
    return {"config": 2, "metric": "batch_verify_ms_by_size",
            "backend": batch.get_backend(),
            "results_ms": results}


def config4_replay_tally(n_vals=150, heights=10) -> dict:
    """Reference: per-height VoteSet tally (vote_set.go AddVote with
    per-vote verify) + Commit verify (BASELINE config #4's hot
    work, without the disk WAL)."""
    from ..crypto import ed25519
    from ..types import canonical
    from ..types.block_id import BlockID
    from ..types.part_set import PartSetHeader
    from ..types.timestamp import Timestamp
    from ..types.validation import verify_commit
    from ..types.vote import Vote
    from ..types.vote_set import VoteSet

    chain_id = "replay-bench"
    vset, privs = _make_valset(
        [ed25519.gen_priv_key() for _ in range(n_vals)])

    tally_ms = []
    commit_ms = []
    for h in range(1, heights + 1):
        bid = BlockID(hash=bytes([h]) * 32,
                      part_set_header=PartSetHeader(1, b"\x07" * 32))
        votes = []
        for i, (val, priv) in enumerate(zip(vset.validators, privs)):
            ts = Timestamp(1700000000 + h, i)
            v = Vote(type=canonical.PRECOMMIT_TYPE, height=h, round=0,
                     block_id=bid, timestamp=ts,
                     validator_address=val.address,
                     validator_index=i)
            v.signature = priv.sign(v.sign_bytes(chain_id))
            votes.append(v)
        vs = VoteSet(chain_id, h, 0, canonical.PRECOMMIT_TYPE, vset)
        t0 = _now()
        for v in votes:
            vs.add_vote(v)
        tally_ms.append((_now() - t0) * 1000)
        commit = vs.make_extended_commit().to_commit()
        t0 = _now()
        verify_commit(chain_id, vset, bid, h, commit)
        commit_ms.append((_now() - t0) * 1000)
    return {"config": 4, "metric": "replay_per_height_ms",
            "validators": n_vals, "heights": heights,
            "tally_ms_p50": round(sorted(tally_ms)[len(tally_ms) // 2],
                                  2),
            "commit_verify_ms_p50": round(
                sorted(commit_ms)[len(commit_ms) // 2], 2)}


def config5_mixed_stress(n_vals=1000, n_bls=64) -> dict:
    """Reference: BASELINE config #5 — mixed-key commit verify (batch
    gate must disengage) + bls12381 aggregate verification."""
    from ..crypto import bls12381, ed25519, secp256k1
    from ..types.block_id import BlockID
    from ..types.part_set import PartSetHeader
    from ..types.validation import verify_commit

    chain_id = "stress-bench"
    privs = []
    for i in range(n_vals):
        if i % 3 == 0:
            privs.append(secp256k1.gen_priv_key())
        elif i % 7 == 0:
            privs.append(bls12381.gen_priv_key_from_secret(
                b"bench-%d" % i))
        else:
            privs.append(ed25519.gen_priv_key())
    vset, privs = _make_valset(privs)
    assert not vset.all_keys_have_same_type()
    bid = BlockID(hash=b"\x55" * 32,
                  part_set_header=PartSetHeader(1, b"\x66" * 32))
    commit = _signed_commit(chain_id, vset, privs, 9, bid)
    t0 = _now()
    verify_commit(chain_id, vset, bid, 9, commit)
    mixed_ms = (_now() - t0) * 1000

    # bls aggregate: n_bls distinct messages, one aggregate signature
    bls_privs = [bls12381.gen_priv_key_from_secret(b"agg-%d" % i)
                 for i in range(n_bls)]
    msgs = [b"block-%d" % i for i in range(n_bls)]
    agg = bls12381.aggregate_signatures(
        [p.sign(m) for p, m in zip(bls_privs, msgs)])
    pks = [p.pub_key() for p in bls_privs]
    t0 = _now()
    ok = bls12381.aggregate_verify(pks, msgs, agg)
    bls_ms = (_now() - t0) * 1000
    assert ok
    return {"config": 5, "metric": "mixed_stress",
            "validators": n_vals, "bls_aggregate_size": n_bls,
            "mixed_commit_verify_ms": round(mixed_ms, 1),
            "bls_aggregate_verify_ms": round(bls_ms, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="BASELINE benchmark configs #2, #4, #5")
    ap.add_argument("--config", type=int, default=0,
                    choices=[0, 2, 4, 5],
                    help="run a single config (2-5); 0 = all. "
                         "Config #1 (live testnet) is tools/"
                         "manifest.py + `cometbft_tpu.cmd load`.")
    ap.add_argument("--full", action="store_true",
                    help="BASELINE sizes (1k light valset, 10k batch)")
    args = ap.parse_args(argv)
    runs = {
        2: lambda: config2_batch_verify(
            (64, 1024, 10_000) if args.full else (64, 256)),
        4: lambda: config4_replay_tally(150, 10 if args.full else 3),
        5: lambda: config5_mixed_stress(
            10_000 if args.full else 200,
            256 if args.full else 16),
    }
    for n, fn in runs.items():
        if args.config in (0, n):
            print(json.dumps(fn()))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
