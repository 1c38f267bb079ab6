"""The plain reference of configuration ``vote-ext-60``: what playing a
consensus WAL with vote extensions back must do with every record, in
plain Python.

Independent of the consensus state machine, the vote sets, the
verified-signature memo, the BatchVerifier seam and the ``oracle`` app:
a vote is a plain tuple, each of its (up to) three signatures is
verified one at a time by the per-signature CPU verifier, the app's
verdict on a payload comes from THIS module's own protobuf decoder, and
the rule is reference/wal_replay.py's with upstream's order of checks
for a non-nil precommit (``consensus/state.go`` addVote, then
``VoteSet.AddVote``):

  - a precommit of the height in hand: its extension signature, then
    its non-replay-protected extension signature (refused as
    EXT_SIGNATURE, NON_RP_SIGNATURE), then the app's verdict on the
    payload (REJECTED), then its vote signature (INVALID_SIGNATURE);
  - a late precommit (of the height before, until the ``timeout`` that
    leaves NewHeight): its vote signature, then both extension
    signatures, each refused with ``failed to verify vote: `` before
    the words above; the app is not asked;
  - a prevote: its vote signature;
  - the height commits at the precommit that brings MORE than 2/3 of
    the power: the precommits accepted by then, with their extensions,
    are the seen extended commit.

It also counts what the program's serial path must show: for each
read-ahead (the records from one end-height marker to the next) the
extension signatures its batch holds (``ext_lanes``); for the votes OF
each height the extension signature checks the rule makes
(``ext_checks``: two in addVote and two more in VoteSet.AddVote for a
precommit of the height in hand, two for a late one, each a memo
answer, fewer where one fails; a forged one is answered twice, by the
confirmation of the lane its batch refused and by the memo), how many
of them are those confirmations on the CPU (``ext_serial``), and the
same for vote signatures (``vote_serial``).  And the oracle's medians
of a block's injected commit info (``medians``), from the same decoder.

Run as a script this file is a reference worker, as wal_replay.py is.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import subprocess
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":      # run as a reference worker
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.reference import wal_replay  # noqa: E402

PREVOTE, PRECOMMIT, LATE = wal_replay.PREVOTE, wal_replay.PRECOMMIT, \
    wal_replay.LATE
INVALID_SIGNATURE = wal_replay.INVALID_SIGNATURE
EXT_SIGNATURE = "invalid vote extension signature"
NON_RP_SIGNATURE = "invalid non-RP vote extension signature"
REJECTED = "invalid vote extension"
LATE_PREFIX = "failed to verify vote: "
MAX_EXTENSION_BYTES = 4096
_PREVOTE_TYPE, _PRECOMMIT_TYPE = 1, 2
_COMMIT_FLAG = 2


@dataclass
class Height(wal_replay.Height):
    """wal_replay.Height and what extensions add to it."""
    seen_ext: dict = field(default_factory=dict)   # index: sha256(ext)
    ext_lanes: int = 0      # of the read-ahead this height is in hand for
    ext_checks: int = 0
    ext_serial: int = 0
    vote_serial: int = 0


# -- this module's own protobuf reader ------------------------------------------

def _varint(raw: bytes, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = raw[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7
        if shift > 63:
            raise ValueError("varint too long")


def fields(raw: bytes) -> list[tuple[int, object]]:
    """(field number, int or bytes) of a message: varints and
    length-delimited fields only."""
    out, pos = [], 0
    try:
        while pos < len(raw):
            key, pos = _varint(raw, pos)
            num, wire = key >> 3, key & 7
            if wire == 0:
                value, pos = _varint(raw, pos)
            elif wire == 2:
                size, pos = _varint(raw, pos)
                value = bytes(raw[pos:pos + size])
                if len(value) != size:
                    raise ValueError("truncated field")
                pos += size
            else:
                raise ValueError(f"wire type {wire}")
            out.append((num, value))
    except IndexError as e:
        raise ValueError("truncated message") from e
    return out


def decode_prices(raw: bytes, pairs: int) -> dict[int, int]:
    """An ``OracleVoteExtension`` as the app must judge it: ValueError
    unless it is under the cap, and every field is a map entry of a
    known pair id, once, and an 8-byte price that is not zero."""
    if len(raw) > MAX_EXTENSION_BYTES:
        raise ValueError("over the cap")
    out: dict[int, int] = {}
    for num, entry in fields(raw):
        if num != 1 or not isinstance(entry, bytes):
            raise ValueError("not a map entry")
        got = dict(fields(entry))
        pair, price = got.get(1), got.get(2)
        if not isinstance(pair, int) or not isinstance(price, bytes) \
                or len(price) != 8:
            raise ValueError("malformed entry")
        value = struct.unpack(">Q", price)[0]
        if pair >= pairs or pair in out or not value:
            raise ValueError("unknown or repeated pair, or no price")
        out[pair] = value
    return out


def commit_info(raw: bytes) -> list[tuple]:
    """An ``ExtendedCommitInfo``'s votes: (address, power, block id
    flag, extension, extension signature)."""
    votes = []
    for num, vote in fields(raw):
        if num != 2:
            continue
        v = dict(fields(vote))
        val = dict(fields(v.get(1, b"")))
        votes.append((val.get(1, b""), val.get(3, 0), v.get(5, 0),
                      v.get(3, b""), v.get(4, b"")))
    return votes


def medians(raw: bytes, pairs: int) -> dict[int, int]:
    """The oracle's medians of an injected commit info: per pair, over
    the committing votes whose payload decodes, the middle price (of an
    even count, the floor of the mean of the two middle ones)."""
    got = []
    for _, _, flag, ext, _ in commit_info(raw):
        if flag == _COMMIT_FLAG:
            try:
                got.append(decode_prices(ext, pairs))
            except ValueError:
                continue
    out = {}
    for p in range(pairs):
        s = sorted(g[p] for g in got if p in g)
        if s:
            mid = len(s) // 2
            out[p] = s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) // 2
    return out


# -- the rule ---------------------------------------------------------------------

def plain_vote(chain_id: str, record: dict) -> tuple:
    """A WAL vote record as plain data: (type, height, round, block
    hash, index, address, signed bytes, signature, extension, extension
    signed bytes, extension signature, non-RP extension, its
    signature)."""
    from cometbft_tpu.consensus.messages import dejsonify
    from cometbft_tpu.types.vote import Vote
    v = Vote.from_proto(dejsonify(record["vote"]))
    return (v.type, v.height, v.round, v.block_id.hash,
            v.validator_index, v.validator_address,
            v.sign_bytes(chain_id), v.signature, v.extension,
            v.extension_sign_bytes(chain_id), v.extension_signature,
            v.non_rp_extension, v.non_rp_extension_signature)


def replay(chain_id: str, validators: list, records, pairs: int,
           first: int = 1, last: int = 0) -> dict[int, Height]:
    """The heights ``first..last`` (0: to the end) of the WAL whose
    records ``records`` yields, each held to the rule above."""
    from cometbft_tpu.crypto import ed25519

    total = sum(power for _, _, power in validators)
    keys = [ed25519.Ed25519PubKey(pub) for _, pub, _ in validators]
    out: dict[int, Height] = {}
    height = first
    cur = out.setdefault(height, Height(height))
    tally: dict = {}
    exts: dict = {}         # index: sha256(extension) of the height in hand
    late_open = False
    started = first == 1

    def ok(index, msg, sig) -> bool:
        return keys[index].verify_signature(msg, sig)

    def named(index, address) -> bool:
        return 0 <= index < len(validators) and \
            validators[index][0] == address

    for record in records:
        t = record.get("type")
        if not started:
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
        if t != "vote":
            continue
        (type_, vheight, round_, block, index, address, msg, sig, ext,
         ext_msg, ext_sig, nrp, nrp_sig) = plain_vote(chain_id, record)
        cur.votes += 1
        extended = type_ == _PRECOMMIT_TYPE and bool(block) and \
            bool(ext_sig) and bool(nrp_sig)
        if extended and named(index, address) and \
                vheight in (height, height - 1):
            cur.ext_lanes += 2
        if vheight == height - 1 and type_ == _PRECOMMIT_TYPE:
            cur.late += 1
            prev = out.get(height - 1)
            if prev is None or not late_open or \
                    (PRECOMMIT, index) in prev.accepted or \
                    (LATE, index) in prev.accepted:
                continue
            if not named(index, address):
                prev.refused[(LATE, index)] = \
                    "validator index does not name the address"
                continue
            # VoteSet.AddVote: the vote, then both extensions
            why = None
            if not ok(index, msg, sig):
                why = INVALID_SIGNATURE
                prev.vote_serial += 1
            elif not ok(index, ext_msg, ext_sig):
                why = LATE_PREFIX + EXT_SIGNATURE
                prev.ext_checks += 2
                prev.ext_serial += 1
            elif not ok(index, nrp, nrp_sig):
                why = LATE_PREFIX + NON_RP_SIGNATURE
                prev.ext_checks += 3
                prev.ext_serial += 1
            else:
                prev.ext_checks += 2
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
        if not named(index, address):
            cur.refused[(kind, index)] = \
                "validator index does not name the address"
            continue
        why = None
        if kind == PRECOMMIT and block:
            # addVote: the extension, then the app, then VoteSet.AddVote
            if not ok(index, ext_msg, ext_sig):
                why = EXT_SIGNATURE
                cur.ext_checks += 2
                cur.ext_serial += 1
            elif not ok(index, nrp, nrp_sig):
                why = NON_RP_SIGNATURE
                cur.ext_checks += 3
                cur.ext_serial += 1
            else:
                cur.ext_checks += 2
                try:
                    decode_prices(ext, pairs)
                except ValueError:
                    why = REJECTED
        if why is None and not ok(index, msg, sig):
            why = INVALID_SIGNATURE
            cur.vote_serial += 1
        elif why is None and kind == PRECOMMIT and block:
            cur.ext_checks += 2        # VoteSet.AddVote: both again
        if why is not None:
            cur.refused[(kind, index)] = why
            continue
        cur.accepted.add((kind, index))
        if kind == PRECOMMIT:
            exts[index] = hashlib.sha256(ext).digest()
        key = (type_, round_, block)
        tally[key] = tally.get(key, 0) + validators[index][2]
        if kind == PRECOMMIT and block and tally[key] * 3 > total * 2:
            cur.block, cur.round = block, round_
            cur.at_quorum = frozenset(
                i for k, i in cur.accepted if k == PRECOMMIT)
            cur.after_late = cur.at_quorum
            cur.seen_ext = {i: exts[i] for i in cur.at_quorum}
            height += 1
            cur = out.setdefault(height, Height(height))
            tally, exts = {}, {}
            late_open = True
    if not cur.votes and not cur.block:
        del out[height]
    return out


def run(chain_id: str, validators: list, wal_path: str, pairs: int,
        first: int = 1, last: int = 0) -> dict[int, Height]:
    from cometbft_tpu.consensus.wal import WAL
    return replay(chain_id, validators, WAL.iter_group(wal_path), pairs,
                  first, last)


def run_parallel(chain_id: str, validators: list, wal_path: str,
                 pairs: int, last: int, workers: int, work_dir: str
                 ) -> dict[int, Height]:
    """``run`` for heights 1..``last`` over ``workers`` child processes
    (they never import JAX); 0 workers: here, in process."""
    if workers <= 0 or last < 2 * max(workers, 1):
        return {h: r for h, r in run(chain_id, validators, wal_path,
                                     pairs, 1, last).items() if h <= last}
    step = -(-last // workers)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               COMETBFT_TPU_CRYPTO_BACKEND="cpu")
    procs = []
    for k, first in enumerate(range(1, last + 1, step)):
        out_path = os.path.join(work_dir, f"reference-ext-{k}.pickle")
        args = dict(chain_id=chain_id, wal_path=wal_path, pairs=pairs,
                    first=first, last=min(first + step - 1, last),
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
    got = run(kw["chain_id"], validators, kw["wal_path"], kw["pairs"],
              kw["first"], kw["last"])
    with open(out_path + ".tmp", "wb") as f:
        # plain dicts: this file runs as __main__ here, and a pickled
        # Height would name a class the parent cannot find
        pickle.dump({h: dataclasses.asdict(r) for h, r in got.items()
                     if kw["first"] <= h <= kw["last"]}, f,
                    protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(out_path + ".tmp", out_path)
    return 0


# -- what a run shows of the system --------------------------------------------

def differences(want: dict[int, Height], witness, block_store,
                heights) -> list[str]:
    """wal_replay.differences, and the stored seen EXTENDED commit: the
    precommits the reference has at +2/3, each with its extension."""
    bad = wal_replay.differences(want, witness, block_store, heights)
    for h in heights:
        ref = want.get(h)
        if ref is None:
            continue
        ec = block_store.load_block_ext_commit(h)
        if ec is None:
            bad.append(f"height {h}: no seen extended commit stored")
            continue
        got = {i: hashlib.sha256(s.extension).digest()
               for i, s in enumerate(ec.extended_signatures)
               if s.block_id_flag == _COMMIT_FLAG}
        if got != ref.seen_ext:
            bad.append(f"height {h}: the seen extended commit is not the "
                       f"reference's at +2/3 ({len(got)} extensions for "
                       f"{len(ref.seen_ext)})")
    return bad


if __name__ == "__main__":
    sys.exit(_worker_main(sys.argv))
