"""The WAL fabricator of configuration ``vote-ext-60``: the consensus WAL
a full node OUTSIDE the validator set would have written while
following a seeded chain with vote extensions on (the shape of dYdX
Chain: every non-nil precommit carries Slinky's oracle prices), made
without running consensus.

The chain: vote extensions enabled from height 1; block H holds, first,
the proposer's ``ExtendedCommitInfo`` of H-1 (every validator's
precommit with its extension and both extension signatures: what the
``oracle`` app's PrepareProposal injects) and then one seeded 1 kB
kvstore tx; it is executed through the program's BlockExecutor on the
program's ``oracle`` app (abci/oracle.py), so headers, app hashes (the
median prices included) and LastCommits are real, and every commit is
signed by every validator (reference/fixtures.signed_commit).  The
payloads and the injected commit info are written by THIS module's own
protobuf encoder, not the app's.

Per height H the node's WAL holds, as reference/wal.py's does:

  the late precommits of H-1   with their extensions
  timeout                      the one that leaves NewHeight
  proposal, block parts        two parts: the ~100 kB injected tx
  prevotes                     one a validator, in a seeded arrival order
  precommits                   with extensions, in a seeded arrival
                               order, up to the one that completes +2/3
  end_height H

A validator's extension is ``price_pairs`` prices (pair id -> 8-byte
big-endian price) from the seed, the height and its address; the
non-replay-protected extension is empty and signed.  One height in
``forged_one_in`` holds ONE forged vote, the kinds by turns (KINDS): a
prevote's signature, an on-time precommit's vote signature, its
extension signature, its non-replay-protected extension signature, a
late precommit's extension signature (a seeded bit flipped), and an
on-time precommit whose payload names an unknown pair, validly signed,
which the app's VerifyVoteExtension rejects.  An on-time forgery makes
+2/3 take one arrival more.  What a byzantine peer relays: the
validator's honest vote is not in the WAL, and the chain's commits hold
it.

Run as a script it is the fabricating child (reference/wal.py's
``load_child`` reads what it leaves): it never imports JAX.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import random
import struct
import subprocess
import sys

if __name__ == "__main__":      # run as the fabricating child
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.reference import chain as chainlib  # noqa: E402
from benchmark.reference import fixtures  # noqa: E402
from benchmark.reference import wal as walfab  # noqa: E402

PREVOTE, PRECOMMIT, EXT, NON_RP, LATE_EXT, PAYLOAD = (
    "prevote", "precommit", "extension", "non_rp_extension",
    "late_extension", "payload")
KINDS = (PREVOTE, PRECOMMIT, EXT, NON_RP, LATE_EXT, PAYLOAD)
# forgeries of an on-time precommit: +2/3 takes one arrival more
ON_TIME = (PRECOMMIT, EXT, NON_RP, PAYLOAD)
COMMIT_FLAG = 2
_PRICE = struct.Struct(">Q")


# -- the payload and the injected commit info, this module's own encoder ------

def _uvarint(u: int) -> bytes:
    out = bytearray()
    while True:
        b = u & 0x7F
        u >>= 7
        out.append(b | 0x80 if u else b)
        if not u:
            return bytes(out)


def _field(num: int, payload: bytes) -> bytes:
    """A length-delimited field (wire type 2)."""
    return _uvarint(num << 3 | 2) + _uvarint(len(payload)) + payload


def prices(seed: int, height: int, address: bytes, pairs: int
           ) -> dict[int, int]:
    """What ``address`` reports at ``height``: pair p near
    (p + 1) x 10^8, spread by the p-th 4 bytes of the SHAKE-256 stream
    of (seed, height, address)."""
    stream = hashlib.shake_256(b"oracle|%d|%d|%s" % (
        seed, height, address.hex().encode())).digest(4 * pairs)
    return {p: (p + 1) * 100_000_000 + int.from_bytes(
        stream[4 * p:4 * p + 4], "big") % 1_000_000 for p in range(pairs)}


_ENTRY_HEADS: dict[int, bytes] = {}


def encode_prices(prices: dict[int, int]) -> bytes:
    """Slinky's ``OracleVoteExtension``: ``map<uint64, bytes> prices =
    1``, an entry a pair (key 1, value 2), pair ids ascending."""
    out = []
    for p, v in sorted(prices.items()):
        head = _ENTRY_HEADS.get(p)
        if head is None:
            key = b"\x08" + _uvarint(p)
            head = _ENTRY_HEADS[p] = _uvarint(1 << 3 | 2) + \
                _uvarint(len(key) + 10) + key + b"\x12\x08"
        out.append(head + _PRICE.pack(v))
    return b"".join(out)


def encode_commit_info(round_: int, votes: list) -> bytes:
    """``cometbft.abci.v2.ExtendedCommitInfo``; a vote is (address,
    power, block id flag, extension, extension signature, non-RP
    extension, non-RP extension signature)."""
    out = bytearray()
    if round_:
        out += _uvarint(1 << 3) + _uvarint(round_)
    for addr, power, flag, ext, ext_sig, nrp, nrp_sig in votes:
        val = (_field(1, addr) if addr else b"") + \
            (_uvarint(3 << 3) + _uvarint(power) if power else b"")
        v = _field(1, val)
        for num, b in ((3, ext), (4, ext_sig)):
            if b:
                v += _field(num, b)
        if flag:
            v += _uvarint(5 << 3) + _uvarint(flag)
        for num, b in ((6, nrp), (7, nrp_sig)):
            if b:
                v += _field(num, b)
        out += _field(2, v)
    return bytes(out)


# -- the chain -----------------------------------------------------------------

def genesis(chain_id: str, seed: int, n_validators: int, power: int):
    """(GenesisDoc, validator set, privs in the set's order): the
    wal-150 chain's genesis with vote extensions enabled from height 1."""
    from cometbft_tpu.types.params import ConsensusParams, FeatureParams
    doc, _, vset, privs = chainlib._genesis_state(chain_id, seed,
                                                  n_validators, power)
    doc.consensus_params = ConsensusParams(
        feature=FeatureParams(vote_extensions_enable_height=1))
    return doc, vset, privs


async def fresh_node(doc, app):
    """Stores, connections and the state after InitChain over ``app``,
    as the ``replay`` command's handshake leaves them: (conns, state
    store, block store, state)."""
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.state import make_genesis_state
    from cometbft_tpu.state.store import Store
    from cometbft_tpu.store import BlockStore

    conns = AppConns(app)
    state_store, block_store = Store(MemDB()), BlockStore(MemDB())
    state = make_genesis_state(doc)
    res = await conns.consensus.init_chain(abci.InitChainRequest(
        time=doc.genesis_time, chain_id=doc.chain_id,
        initial_height=doc.initial_height,
        consensus_params=doc.consensus_params,
        validators=[abci.ValidatorUpdate(
            power=v.voting_power, pub_key_type=v.pub_key.type(),
            pub_key_bytes=v.pub_key.bytes())
            for v in state.validators.validators]))
    state.app_hash = res.app_hash
    state_store.save(state)
    return conns, state_store, block_store, state


def extended_precommits(chain_id: str, vset, privs, commit, seed: int,
                        pairs: int, non_rp_sigs: list) -> list:
    """The precommits of ``commit`` as their validators cast them,
    extensions and both extension signatures included."""
    from cometbft_tpu.types import canonical
    out = []
    for i, priv in enumerate(privs):
        vote = commit.get_vote(i)
        vote.extension = encode_prices(prices(
            seed, commit.height, vset.validators[i].address, pairs))
        vote.extension_signature = priv.sign(
            canonical.vote_extension_sign_bytes(
                chain_id, commit.height, commit.round, vote.extension))
        vote.non_rp_extension_signature = non_rp_sigs[i]
        out.append(vote)
    return out


def injected_tx(vset, votes) -> bytes:
    return encode_commit_info(votes[0].round, [
        (val.address, val.voting_power, COMMIT_FLAG, v.extension,
         v.extension_signature, v.non_rp_extension,
         v.non_rp_extension_signature)
        for val, v in zip(vset.validators, votes)])


@dataclasses.dataclass
class ExtChain:
    """reference/chain.Chain and what the WAL's writers need beside it."""
    chain: object
    seed: int
    pairs: int
    non_rp_sigs: list

    def precommits(self, h: int) -> list:
        c = self.chain
        return extended_precommits(
            c.chain_id, c.vset, c.privs, c.block_store.load_seen_commit(h),
            self.seed, self.pairs, self.non_rp_sigs)


async def make_chain(chain_id: str, seed: int, n_validators: int,
                     power: int, heights: int, tx_bytes: int,
                     pairs: int) -> ExtChain:
    from cometbft_tpu.abci.oracle import OracleApplication
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.commit import Commit

    doc, vset, privs = genesis(chain_id, seed, n_validators, power)
    app = OracleApplication()
    conns, state_store, block_store, state = await fresh_node(doc, app)
    executor = BlockExecutor(state_store, conns.consensus,
                             block_store=block_store)
    chain = chainlib.Chain(chain_id, doc, vset, privs, state_store,
                           block_store, app, state)
    made = ExtChain(chain, seed, pairs, [p.sign(b"") for p in privs])
    last_commit, last_votes = Commit(), None
    for h in range(1, heights + 1):
        txs = [fixtures.seeded_tx(seed, h, 0, tx_bytes)]
        if last_votes is not None:
            txs.insert(0, injected_tx(vset, last_votes))
        block = state.make_block(h, txs, last_commit, [],
                                 state.validators.get_proposer().address)
        parts = block.make_part_set()
        bid = BlockID(hash=block.hash(), part_set_header=parts.header())
        commit = fixtures.signed_commit(chain_id, vset, privs, h, bid)
        block_store.save_block(block, parts, commit)
        state = await executor.apply_verified_block(state, bid, block)
        chain.block_hash[h] = block.hash()
        chain.app_hash[h] = state.app_hash
        last_commit = commit
        last_votes = extended_precommits(chain_id, vset, privs, commit,
                                         seed, pairs, made.non_rp_sigs)
    chain.state = state
    return made


# -- the WAL ---------------------------------------------------------------------

def forged_kind(seed: int, height: int, one_in: int):
    """What is forged at ``height``: None, or one of KINDS by turns."""
    if not one_in or height % one_in != seed % one_in:
        return None
    return KINDS[(height // one_in) % len(KINDS)]


def _flip(rng: random.Random, b: bytes) -> bytes:
    return fixtures.flip_bit(rng, b, 0, 63)


def height_votes(made: ExtChain, forged_one_in: int, h: int, bid):
    """The votes of height ``h`` as the WAL holds them: (prevotes in
    arrival order, precommits before the marker, precommits after it,
    what was forged or None)."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.vote import Vote

    chain, seed = made.chain, made.seed
    n = chain.vset.size()
    need = walfab.quorum(n)
    rng = random.Random(f"arrival-ext-{seed}-{h}")
    precommits = made.precommits(h)
    make = canonical.vote_sign_bytes_template(
        chain.chain_id, canonical.PREVOTE_TYPE, h, 0, bid)
    cast = chain.block_store.load_block_meta(h).header.time.add_ns(
        walfab.PREVOTE_AFTER_NS)
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
    before = need + (kind in ON_TIME)
    forged = None
    if kind == PREVOTE:
        at = pv_order[rng.randrange(n)]
        prevotes[at] = dataclasses.replace(
            prevotes[at], signature=_flip(rng, prevotes[at].signature))
        forged = (kind, at)
    elif kind is not None:
        at = pc_order[rng.randrange(before, n) if kind == LATE_EXT
                      else rng.randrange(need)]
        v = precommits[at]
        if kind == PRECOMMIT:
            v = dataclasses.replace(v, signature=_flip(rng, v.signature))
        elif kind in (EXT, LATE_EXT):
            v = dataclasses.replace(v, extension_signature=_flip(
                rng, v.extension_signature))
        elif kind == NON_RP:
            v = dataclasses.replace(v, non_rp_extension_signature=_flip(
                rng, v.non_rp_extension_signature))
        else:   # PAYLOAD: a pair the app does not know, validly signed
            got = prices(seed, h, v.validator_address, made.pairs)
            got[made.pairs + rng.randrange(1000)] = got.pop(
                rng.randrange(made.pairs))
            ext = encode_prices(got)
            v = dataclasses.replace(
                v, extension=ext,
                extension_signature=chain.privs[at].sign(
                    canonical.vote_extension_sign_bytes(
                        chain.chain_id, h, 0, ext)))
        precommits[at] = v
        forged = (kind, at)
    return ([prevotes[i] for i in pv_order],
            [precommits[i] for i in pc_order[:before]],
            [precommits[i] for i in pc_order[before:]], forged)


def write_wal(made: ExtChain, wal_path: str, forged_one_in: int,
              first: int = 1, last: int = 0) -> dict[int, tuple]:
    """Write the WAL of heights ``first..last`` (0: the chain's last);
    returns what was forged."""
    from cometbft_tpu.consensus.messages import (
        BlockPartMessage, ProposalMessage, VoteMessage,
    )
    from cometbft_tpu.consensus.round_state import STEP_NEW_HEIGHT
    from cometbft_tpu.consensus.wal import WAL
    from cometbft_tpu.types.proposal import Proposal

    chain = made.chain
    by_addr = {v.address: p for v, p in zip(chain.vset.validators,
                                            chain.privs)}
    wal = WAL(wal_path, total_size_limit=walfab.TOTAL_SIZE_LIMIT)
    forged: dict[int, tuple] = {}
    late: list = []
    if first > 1:
        meta = chain.block_store.load_block_meta(first - 1)
        late = height_votes(made, forged_one_in, first - 1,
                            meta.block_id)[2]
    for h in range(first, (last or chain.height) + 1):
        block = chain.block_store.load_block(h)
        parts = block.make_part_set()
        bid = chain.block_store.load_block_meta(h).block_id
        prevotes, precommits, after, what = height_votes(
            made, forged_one_in, h, bid)
        if what is not None:
            forged[h] = what
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


_FORK_JOB = None


def _write_run(k: int) -> dict[int, tuple]:
    made, wal_path, one_in, runs = _FORK_JOB
    return write_wal(made, f"{wal_path}.run{k}/wal", one_in, *runs[k])


def write_wal_forked(made: ExtChain, wal_path: str, forged_one_in: int,
                     workers: int) -> dict[int, tuple]:
    """``write_wal`` over ``workers`` forked writers, each a run of
    heights into a group of its own, renamed in order into one (as
    reference/wal.py's)."""
    import multiprocessing
    from cometbft_tpu.consensus.wal import WAL

    global _FORK_JOB
    top = made.chain.height
    step = -(-top // workers)
    runs = [(a, min(a + step - 1, top)) for a in range(1, top + 1, step)]
    _FORK_JOB = (made, wal_path, forged_one_in, runs)
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
    return {h: what for part in parts for h, what in part.items()}


async def fabricate(wal_path: str, chain_id: str, seed: int,
                    n_validators: int, power: int, heights: int,
                    tx_bytes: int, forged_one_in: int, price_pairs: int,
                    workers: int = 1) -> walfab.Fabricated:
    made = await make_chain(chain_id, seed, n_validators, power, heights,
                            tx_bytes, price_pairs)
    if workers > 1 and heights >= 2 * workers:
        forged = write_wal_forked(made, wal_path, forged_one_in, workers)
    else:
        forged = write_wal(made, wal_path, forged_one_in)
    return walfab.Fabricated(chain_id, heights, wal_path,
                             made.chain.block_hash, made.chain.app_hash,
                             forged)


def chunk_of(kind: str, height: int) -> int:
    """The read-ahead (named by the height in hand) that holds the
    forged vote OF ``height``: a late precommit arrives behind its
    height's marker."""
    return height + (kind == LATE_EXT)


# -- fabrication in a child process ---------------------------------------------

def start_child(out_path: str, **kw) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               COMETBFT_TPU_CRYPTO_BACKEND="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), json.dumps(kw),
         out_path], env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)


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
