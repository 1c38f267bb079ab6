"""The plain reference of configuration ``wal-150``: what playing a
consensus WAL back must do with every record, in plain Python.

Independent of the consensus state machine (consensus/state.py), of
the vote sets (types/vote_set.py, consensus/height_vote_set.py), of the
verified-signature memo and of the BatchVerifier seam: a vote is a
plain tuple, a signature's verdict comes one vote at a time from the
per-signature CPU verifier (the one reference/golden.py holds the
seam's masks to), the tally is a dict of power per block id, and the
rule is the few dozen lines of ``replay``:

  - records are taken in WAL order by one mutator;
  - a vote of the height in hand is accepted when its validator is the
    one its index names, has not voted that type before, and the
    signature verifies; a wrong signature is refused as
    ``INVALID_SIGNATURE`` and never counted;
  - the height commits the block that MORE than 2/3 of the power has
    precommitted, in that round, at that vote: the precommits accepted
    by then are the seen commit, and the next height is in hand;
  - a precommit of the height before is a late one: accepted the same
    way into the last commit until the ``timeout`` record that leaves
    NewHeight, dropped without a verdict after it;
  - everything else about a height (proposal, parts, steps) decides
    nothing a WAL of round 0 can show.

It reads the WAL through the program's frame reader (``WAL.iter_group``:
length, CRC, JSON) and takes a vote's signed bytes from the program's
``Vote`` (as reference/skipping.plain takes a commit's): the reference
is of the RULE.  What it does not model: rounds above 0, nil votes,
vote extensions, conflicting votes (the fabricated WAL has none).

``Witness`` is what a run shows of the system for the comparison
(``differences``): the votes it published as accepted, the votes it
refused and why, and what it stored.  Run as a script this file is a
reference worker: heights ``first..last`` of a WAL, so that the timed
sizes (some half a million signatures, 110 us each one by one) are
compared in seconds, not minutes.
"""
from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":      # run as a reference worker
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

PREVOTE, PRECOMMIT, LATE = "prevote", "precommit", "late_precommit"
INVALID_SIGNATURE = "failed to verify vote: invalid vote signature"
_PREVOTE_TYPE, _PRECOMMIT_TYPE = 1, 2


@dataclass
class Height:
    """What the reference holds a replayed height to."""
    height: int
    accepted: set = field(default_factory=set)      # (kind, index)
    refused: dict = field(default_factory=dict)     # (kind, index): why
    block: bytes = b""              # hash of the block committed
    round: int = -1
    at_quorum: frozenset = frozenset()  # precommit indices at +2/3
    after_late: frozenset = frozenset()     # ... and the late ones
    votes: int = 0      # vote records between the marker below and its own
    late: int = 0       # those of them that were precommits of height - 1


def validators_of(vset) -> list:
    """A program ValidatorSet as plain data: (address, pubkey, power)."""
    return [(v.address, v.pub_key.bytes(), v.voting_power)
            for v in vset.validators]


def plain_vote(chain_id: str, record: dict) -> tuple:
    """A WAL vote record as plain data: (type, height, round, block
    hash, index, address, signed bytes, signature)."""
    from cometbft_tpu.consensus.messages import dejsonify
    from cometbft_tpu.types.vote import Vote
    v = Vote.from_proto(dejsonify(record["vote"]))
    return (v.type, v.height, v.round, v.block_id.hash,
            v.validator_index, v.validator_address,
            v.sign_bytes(chain_id), v.signature)


def replay(chain_id: str, validators: list, records, first: int = 1,
           last: int = 0) -> dict[int, Height]:
    """The heights ``first..last`` (0: to the end) of the WAL whose
    records ``records`` yields, each held to the rule above."""
    from cometbft_tpu.crypto import ed25519

    total = sum(power for _, _, power in validators)
    keys = [ed25519.Ed25519PubKey(pub) for _, pub, _ in validators]
    out: dict[int, Height] = {}
    height = first
    cur = out.setdefault(height, Height(height))
    tally: dict = {}            # (type, round, block hash) -> power
    late_open = False           # NewHeight: late precommits welcome
    started = first == 1

    def verdict(vote) -> str | None:
        _, _, _, _, index, address, msg, sig = vote
        if not 0 <= index < len(validators) or \
                validators[index][0] != address:
            return "validator index does not name the address"
        if not keys[index].verify_signature(msg, sig):
            return INVALID_SIGNATURE
        return None

    for record in records:
        t = record.get("type")
        if not started:
            # a worker's range begins behind the marker below it
            started = t == "end_height" and \
                record.get("height") == first - 1
            late_open = started
            continue
        if t == "timeout":
            if record.get("height") == height:
                late_open = False
                if last and height > last:
                    break
            continue
        if t == "end_height":
            if record.get("height") != height - 1:
                raise RuntimeError(
                    f"the WAL marks the end of height "
                    f"{record.get('height')} where the reference has "
                    f"committed up to {height - 1}")
            continue
        if t != "vote":
            continue
        vote = plain_vote(chain_id, record)
        type_, vheight, round_, block, index = vote[:5]
        cur.votes += 1
        if vheight == height - 1 and type_ == _PRECOMMIT_TYPE:
            cur.late += 1
            prev = out.get(height - 1)
            if prev is None or not late_open or \
                    (PRECOMMIT, index) in prev.accepted or \
                    (LATE, index) in prev.accepted:
                continue
            why = verdict(vote)
            if why is not None:
                prev.refused[(LATE, index)] = why
                continue
            prev.accepted.add((LATE, index))
            prev.after_late = prev.after_late | {index}
            continue
        if vheight != height:
            continue
        kind = PREVOTE if type_ == _PREVOTE_TYPE else PRECOMMIT
        if (kind, index) in cur.accepted:
            continue
        why = verdict(vote)
        if why is not None:
            cur.refused[(kind, index)] = why
            continue
        cur.accepted.add((kind, index))
        key = (type_, round_, block)
        tally[key] = tally.get(key, 0) + validators[index][2]
        if kind == PRECOMMIT and block and tally[key] * 3 > total * 2:
            cur.block, cur.round = block, round_
            cur.at_quorum = frozenset(
                i for k, i in cur.accepted if k == PRECOMMIT)
            cur.after_late = cur.at_quorum
            height += 1
            cur = out.setdefault(height, Height(height))
            tally = {}
            late_open = True
    if not cur.votes and not cur.block:
        del out[height]         # the WAL ended on a marker
    return out


def run(chain_id: str, validators: list, wal_path: str, first: int = 1,
        last: int = 0) -> dict[int, Height]:
    from cometbft_tpu.consensus.wal import WAL
    return replay(chain_id, validators, WAL.iter_group(wal_path),
                  first, last)


# -- the same, over worker processes -----------------------------------------

def run_parallel(chain_id: str, validators: list, wal_path: str,
                 last: int, workers: int, work_dir: str
                 ) -> dict[int, Height]:
    """``run`` for heights 1..``last`` split over ``workers`` child
    processes (they never import JAX); 0 workers: here, in process.
    A worker takes the late precommits of its last height from the
    records behind it, so every height's result is whole."""
    if workers <= 0 or last < 2 * max(workers, 1):
        return {h: r for h, r in run(chain_id, validators, wal_path,
                                     1, last).items() if h <= last}
    step = -(-last // workers)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               COMETBFT_TPU_CRYPTO_BACKEND="cpu")
    procs = []
    for k, first in enumerate(range(1, last + 1, step)):
        out_path = os.path.join(work_dir, f"reference-{k}.pickle")
        args = dict(chain_id=chain_id, wal_path=wal_path, first=first,
                    last=min(first + step - 1, last),
                    validators=[(a.hex(), p.hex(), w)
                                for a, p, w in validators])
        procs.append((out_path, subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             json.dumps(args), out_path], env=env,
            stdout=subprocess.DEVNULL)))
    out: dict[int, Height] = {}
    try:
        for out_path, proc in procs:
            if proc.wait() != 0:
                raise RuntimeError(
                    f"a reference worker exited with {proc.returncode}")
            with open(out_path, "rb") as f:
                out.update({h: Height(**d)
                            for h, d in pickle.load(f).items()})
            os.unlink(out_path)
    finally:
        for _, proc in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
    return out


def _worker_main(argv: list[str]) -> int:
    import dataclasses
    import logging
    logging.disable(logging.CRITICAL)
    kw, out_path = json.loads(argv[1]), argv[2]
    validators = [(bytes.fromhex(a), bytes.fromhex(p), w)
                  for a, p, w in kw.pop("validators")]
    got = run(kw["chain_id"], validators, kw["wal_path"], kw["first"],
              kw["last"])
    with open(out_path + ".tmp", "wb") as f:
        pickle.dump({h: dataclasses.asdict(r) for h, r in got.items()
                     if kw["first"] <= h <= kw["last"]}, f,
                    protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(out_path + ".tmp", out_path)
    return 0


# -- what a run shows of the system ------------------------------------------

class Witness:
    """Stands where a playback's event bus and logger stand: the votes
    the state machine published as accepted (an RPC subscriber's view;
    a precommit below the height in hand is a late one), and the votes
    it refused, with its own words for why."""

    def __init__(self):
        self.height = 0             # the height the machine has in hand
        self.accepted: dict[int, set] = {}
        self.refused: dict[int, dict] = {}
        self.errors: list[str] = []     # whatever else it called an error

    # the event bus
    def publish_new_round_step(self, summary: dict) -> None:
        self.height = summary["height"]

    def publish_vote(self, vote) -> None:
        kind = PREVOTE if vote.type == _PREVOTE_TYPE else \
            LATE if vote.height < self.height else PRECOMMIT
        self.accepted.setdefault(vote.height, set()).add(
            (kind, vote.validator_index))

    def __getattr__(self, name):
        if name.startswith("publish"):
            return lambda *a, **k: None
        raise AttributeError(name)

    # the logger
    def error(self, msg: str, **kv) -> None:
        if msg == "failed to add vote" and "height" in kv:
            kind = PREVOTE if kv["type"] == _PREVOTE_TYPE else \
                LATE if kv["height"] < self.height else PRECOMMIT
            self.refused.setdefault(kv["height"], {})[
                (kind, kv["index"])] = kv["err"]
        else:
            self.errors.append(f"{msg} {kv}")

    def debug(self, msg: str, **kv) -> None:
        pass

    info = warn = debug


def differences(want: dict[int, Height], witness: Witness, block_store,
                heights) -> list[str]:
    """How the system's replay of ``heights`` differs from the
    reference's; empty when it does not."""
    from cometbft_tpu.types.vote import BLOCK_ID_FLAG_COMMIT
    bad = []
    for h in heights:
        ref = want.get(h)
        if ref is None:
            bad.append(f"height {h}: the reference did not commit it")
            continue
        got = witness.accepted.get(h, set())
        if got != ref.accepted:
            bad.append(
                f"height {h}: accepted votes differ: "
                f"{sorted(got ^ ref.accepted)[:4]}")
        if witness.refused.get(h, {}) != ref.refused:
            bad.append(
                f"height {h}: refused {witness.refused.get(h, {})}, "
                f"the reference {ref.refused}")
        seen = block_store.load_seen_commit(h)
        if seen is None:
            bad.append(f"height {h}: no seen commit stored")
            continue
        signed = frozenset(
            i for i, cs in enumerate(seen.signatures)
            if cs.block_id_flag == BLOCK_ID_FLAG_COMMIT)
        if seen.block_id.hash != ref.block or seen.round != ref.round \
                or signed != ref.at_quorum:
            bad.append(
                f"height {h}: the seen commit is not the reference's "
                f"at +2/3 ({len(signed)} precommits for "
                f"{len(ref.at_quorum)})")
        late = frozenset(i for k, i in got if k == LATE)
        if signed | late != ref.after_late:
            bad.append(f"height {h}: the last commit after the late "
                       f"precommits differs")
    return bad


if __name__ == "__main__":
    sys.exit(_worker_main(sys.argv))
