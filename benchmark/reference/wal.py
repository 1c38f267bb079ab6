"""The WAL fabricator: the consensus WAL a full node OUTSIDE the
validator set would have written while following a seeded chain at
round 0, made without running consensus.

The chain is reference/chain.py's (one seeded kvstore tx a block, every
commit signed by every validator), so a height's precommits ARE the
signatures of the next block's LastCommit.  Per height H the node's WAL
holds, in this order (what ``consensus/state.py`` writes for the inputs
a live node receives, ``round_state`` records aside; a node with
upstream's ``timeout_commit``, not 0, which waits NewHeight out and
writes the timeout that ends it even when every precommit is in):

  the late precommits of H-1   those that arrived after H-1 had +2/3
  timeout                      the one that leaves NewHeight
  proposal, block parts        the proposer's, as its peers relay them
  prevotes                     one a validator, in a seeded arrival order
  precommits                   in a seeded arrival order, up to the one
                               that completes +2/3 of the power
  end_height H                 the marker ``_finalize_commit`` writes

Every vote has its own timestamp.  One height in ``forged_one_in``
holds ONE vote whose signature has a seeded bit flipped
(fixtures.flip_bit), by turns a prevote, a precommit before the marker
(+2/3 then takes one arrival more) and a late precommit: what a
byzantine peer relays; the validator's honest vote is then not in the
WAL.  The records go through the program's own writer
(``consensus/wal.WAL``, its size limit raised so that no file of the
group is pruned) and its own ``to_wal`` forms.

Run as a script it is the fabricating child: it never imports JAX, and
leaves beside the WAL a pickle of the chain's record (block hash and
app hash a height) and of what it forged.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pickle
import random
import subprocess
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":      # run as the fabricating child
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.reference import chain as chainlib  # noqa: E402
from benchmark.reference import fixtures  # noqa: E402

PREVOTE, PRECOMMIT, LATE = "prevote", "precommit", "late_precommit"
KINDS = (PREVOTE, PRECOMMIT, LATE)
# a prevote is cast this long after the block's time, a precommit a
# second after it (fixtures.signed_commit: BASE_TIME_S + height)
PREVOTE_AFTER_NS = 300_000_000
TOTAL_SIZE_LIMIT = 1 << 40


@dataclass
class Fabricated:
    """The chain's record and what was forged: ``forged[h]`` is
    ``(kind, validator index)`` for a vote OF height h."""
    chain_id: str
    heights: int
    wal_path: str
    block_hash: dict[int, bytes] = field(default_factory=dict)
    app_hash: dict[int, bytes] = field(default_factory=dict)
    forged: dict[int, tuple] = field(default_factory=dict)


def quorum(n_validators: int) -> int:
    """Votes of equal power that hold MORE than 2/3 of it."""
    return n_validators * 2 // 3 + 1


def forged_kind(seed: int, height: int, one_in: int):
    """What is forged at ``height``: None, or one of KINDS by turns."""
    if not one_in or height % one_in != seed % one_in:
        return None
    return KINDS[(height // one_in) % len(KINDS)]


def height_votes(chain, seed: int, forged_one_in: int, h: int, bid):
    """The votes of height ``h`` for block ``bid`` as the WAL holds
    them: (prevotes in arrival order, precommits before the marker,
    precommits after it, what was forged or None)."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.vote import Vote

    n = chain.vset.size()
    need = quorum(n)
    rng = random.Random(f"arrival-{seed}-{h}")
    commit = chain.block_store.load_seen_commit(h)
    precommits = [commit.get_vote(i) for i in range(n)]
    make = canonical.vote_sign_bytes_template(
        chain.chain_id, canonical.PREVOTE_TYPE, h, 0, bid)
    cast = chain.block_store.load_block_meta(h).header.time.add_ns(
        PREVOTE_AFTER_NS)
    prevotes = []
    for i, (val, priv) in enumerate(zip(chain.vset.validators,
                                        chain.privs)):
        ts = Timestamp(cast.seconds, cast.nanos + i)
        prevotes.append(Vote(
            type=canonical.PREVOTE_TYPE, height=h, round=0,
            block_id=bid, timestamp=ts, validator_address=val.address,
            validator_index=i, signature=priv.sign(make(ts))))
    pv_order = rng.sample(range(n), n)
    pc_order = rng.sample(range(n), n)
    kind = forged_kind(seed, h, forged_one_in)
    # +2/3 of the power has precommitted at the ``before``-th arrival:
    # one later where a forged one is among them
    before = need + (kind == PRECOMMIT)
    forged = None
    if kind == PREVOTE:
        forged = (kind, pv_order[rng.randrange(n)])
        prevotes[forged[1]] = _forge(rng, prevotes[forged[1]])
    elif kind == PRECOMMIT:
        forged = (kind, pc_order[rng.randrange(need)])
    elif kind == LATE:
        forged = (kind, pc_order[rng.randrange(before, n)])
    if kind in (PRECOMMIT, LATE):
        precommits[forged[1]] = _forge(rng, precommits[forged[1]])
    return ([prevotes[i] for i in pv_order],
            [precommits[i] for i in pc_order[:before]],
            [precommits[i] for i in pc_order[before:]], forged)


def write_wal(chain, wal_path: str, seed: int, forged_one_in: int,
              first: int = 1, last: int = 0) -> dict[int, tuple]:
    """Write the WAL of ``chain``'s heights ``first..last`` (0: the
    chain's last); returns what was forged."""
    from cometbft_tpu.consensus.messages import (
        BlockPartMessage, ProposalMessage, VoteMessage,
    )
    from cometbft_tpu.consensus.round_state import STEP_NEW_HEIGHT
    from cometbft_tpu.consensus.wal import WAL
    from cometbft_tpu.types.proposal import Proposal

    by_addr = {v.address: p for v, p in zip(chain.vset.validators,
                                            chain.privs)}
    wal = WAL(wal_path, total_size_limit=TOTAL_SIZE_LIMIT)
    forged: dict[int, tuple] = {}
    late: list = []
    if first > 1:
        meta = chain.block_store.load_block_meta(first - 1)
        late = height_votes(chain, seed, forged_one_in, first - 1,
                            meta.block_id)[2]
    for h in range(first, (last or chain.height) + 1):
        block = chain.block_store.load_block(h)
        parts = block.make_part_set()
        bid = chain.block_store.load_block_meta(h).block_id
        prevotes, precommits, after, made = height_votes(
            chain, seed, forged_one_in, h, bid)
        if made is not None:
            forged[h] = made
        for vote in late:
            wal.write(VoteMessage(vote).to_wal())
        wal.write({"type": "timeout", "height": h, "round": 0,
                   "step": STEP_NEW_HEIGHT})
        proposal = Proposal(height=h, round=0, pol_round=-1,
                            block_id=bid, timestamp=block.header.time)
        proposal.signature = by_addr[block.header.proposer_address] \
            .sign(proposal.sign_bytes(chain.chain_id))
        wal.write(ProposalMessage(proposal).to_wal())
        for i in range(parts.total):
            wal.write(BlockPartMessage(h, 0, parts.get_part(i)).to_wal())
        for vote in prevotes + precommits:
            wal.write(VoteMessage(vote).to_wal())
        wal.write({"type": "end_height", "height": h})
        late = after
    wal.close()
    return forged


# -- the WAL's heights over forked writers ------------------------------------
# Two signatures a validator a height and the records' JSON are most of
# fabrication and independent from height to height once the chain is
# made: ``workers`` forked processes (they share the chain's stores by
# inheritance; never from a process that has touched JAX) each write a
# run of heights into a group of their own, and the groups' files are
# renamed, in order, into one.

_FORK_JOB = None


def _write_run(k: int) -> dict[int, tuple]:
    chain, wal_path, seed, one_in, runs = _FORK_JOB
    return write_wal(chain, f"{wal_path}.run{k}/wal", seed, one_in,
                     *runs[k])


def write_wal_forked(chain, wal_path: str, seed: int,
                     forged_one_in: int, workers: int
                     ) -> dict[int, tuple]:
    import multiprocessing
    from cometbft_tpu.consensus.wal import WAL

    global _FORK_JOB
    step = -(-chain.height // workers)
    runs = [(a, min(a + step - 1, chain.height))
            for a in range(1, chain.height + 1, step)]
    _FORK_JOB = (chain, wal_path, seed, forged_one_in, runs)
    with multiprocessing.get_context("fork").Pool(len(runs)) as pool:
        parts = pool.map(_write_run, range(len(runs)))
    _FORK_JOB = None
    files = [f for k in range(len(runs))
             for f in WAL.group_files(f"{wal_path}.run{k}/wal")]
    for i, f in enumerate(files[:-1]):
        os.replace(f, f"{wal_path}.{i:03d}")
    os.replace(files[-1], wal_path)
    for k in range(len(runs)):
        os.rmdir(f"{wal_path}.run{k}")
    return {h: made for part in parts for h, made in part.items()}


def _forge(rng: random.Random, vote):
    return dataclasses.replace(vote, signature=fixtures.flip_bit(
        rng, vote.signature, 0, 63))


async def fabricate(wal_path: str, chain_id: str, seed: int,
                    n_validators: int, power: int, heights: int,
                    tx_bytes: int, forged_one_in: int,
                    workers: int = 1) -> Fabricated:
    chain = await chainlib.fabricate(
        chain_id, seed, n_validators, power, heights, 1, tx_bytes)
    if workers > 1 and heights >= 2 * workers:
        forged = write_wal_forked(chain, wal_path, seed, forged_one_in,
                                  workers)
    else:
        forged = write_wal(chain, wal_path, seed, forged_one_in)
    return Fabricated(chain_id, heights, wal_path, chain.block_hash,
                      chain.app_hash, forged)


def genesis(chain_id: str, seed: int, n_validators: int, power: int):
    """(GenesisDoc, validator set) of the chain the WAL follows."""
    doc, _, vset, _ = chainlib._genesis_state(chain_id, seed,
                                              n_validators, power)
    return doc, vset


# -- fabrication in a child process ------------------------------------------
# As reference/chain.py's: signing (two signatures a validator a
# height) and writing are host Python, made anew in every run, and hide
# under the parent's set-up of the kernel's shapes.

def start_child(out_path: str, **kw) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               COMETBFT_TPU_CRYPTO_BACKEND="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), json.dumps(kw),
         out_path], env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)


def load_child(proc: subprocess.Popen, out_path: str) -> Fabricated:
    if proc.wait() != 0:
        raise RuntimeError(
            f"the WAL fabricator exited with {proc.returncode}")
    with open(out_path, "rb") as f:
        made = Fabricated(**pickle.load(f))
    os.unlink(out_path)
    return made


def _child_main(argv: list[str]) -> int:
    import asyncio
    import logging
    logging.disable(logging.CRITICAL)
    kw, out_path = json.loads(argv[1]), argv[2]
    made = asyncio.run(fabricate(**kw))
    with open(out_path + ".tmp", "wb") as f:
        pickle.dump(dataclasses.asdict(made), f,
                    protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv))
