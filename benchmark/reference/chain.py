"""The chain fabricator: a seeded chain signed by a whole validator
set, made without running consensus.

Blocks of seeded kvstore txs are made from the running state and
executed through the program's BlockExecutor on the kvstore app, so
headers, app hashes, results hashes and LastCommits are real; every
commit is signed by every validator (reference/fixtures.signed_commit).
The chain's own record — block hash and app hash per height — is what
a synced node is compared with; the plain reference of the app's
semantics (a key holds the last value a tx wrote to it) is derived
from the seed by the driver's check, independent of the state tree.
"""
from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":      # run as the fabricating child
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.reference import fixtures  # noqa: E402


@dataclass
class Chain:
    chain_id: str
    doc: object                     # GenesisDoc
    vset: object
    privs: list
    state_store: object
    block_store: object
    app: object
    state: object                   # state after the last height
    block_hash: dict[int, bytes] = field(default_factory=dict)
    app_hash: dict[int, bytes] = field(default_factory=dict)
    dbs: dict = field(default_factory=dict)     # the stores' databases

    @property
    def height(self) -> int:
        return self.block_store.height


def genesis(chain_id: str, privs, power: int):
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.timestamp import Timestamp
    return GenesisDoc(
        chain_id=chain_id,
        genesis_time=Timestamp(fixtures.BASE_TIME_S, 0),
        validators=[GenesisValidator(address=b"", pub_key=p.pub_key(),
                                     power=power) for p in privs])


def _genesis_state(chain_id: str, seed: int, n_validators: int,
                   power: int):
    """(doc, genesis state, validator set, privs in the set's order)."""
    from cometbft_tpu.state import make_genesis_state
    privs = fixtures.seeded_privs(n_validators, seed)
    doc = genesis(chain_id, privs, power)
    state = make_genesis_state(doc)
    by_addr = {p.pub_key().address(): p for p in privs}
    vset = state.validators
    return doc, state, vset, [by_addr[v.address]
                              for v in vset.validators]


async def fabricate(chain_id: str, seed: int, n_validators: int,
                    power: int, heights: int, txs_per_block: int,
                    tx_bytes: int) -> Chain:
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.store import Store
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.commit import Commit

    doc, state, vset, privs = _genesis_state(chain_id, seed,
                                             n_validators, power)
    app = KVStoreApplication()
    conns = AppConns(app)
    dbs = {"blocks": MemDB(), "state": MemDB()}
    state_store, block_store = Store(dbs["state"]), \
        BlockStore(dbs["blocks"])
    state_store.save(state)
    await conns.consensus.init_chain(
        abci.InitChainRequest(chain_id=chain_id))
    executor = BlockExecutor(state_store, conns.consensus,
                             block_store=block_store)
    chain = Chain(chain_id, doc, vset, privs, state_store, block_store,
                  app, state, dbs=dbs)
    last_commit = Commit()
    for h in range(1, heights + 1):
        txs = [fixtures.seeded_tx(seed, h, i, tx_bytes)
               for i in range(txs_per_block)]
        block = state.make_block(
            h, txs, last_commit, [],
            state.validators.get_proposer().address)
        parts = block.make_part_set()
        bid = BlockID(hash=block.hash(), part_set_header=parts.header())
        # the set is fixed, so validators at h are the genesis set
        commit = fixtures.signed_commit(chain_id, vset, privs, h, bid)
        block_store.save_block(block, parts, commit)
        state = await executor.apply_verified_block(state, bid, block)
        chain.block_hash[h] = block.hash()
        chain.app_hash[h] = state.app_hash
        last_commit = commit
    chain.state = state
    return chain


# -- fabrication in a child process ------------------------------------------
# Data is made anew in every run and counts as set-up, and fabrication
# is host Python that grows with the square of the chain: every block
# is executed through the program's kvstore, whose root is taken over
# ALL leaves at every height, 16 new keys a height, so a height costs
# 18 ms at the start and 45 ms at height 1,500 (about 0.016 h +
# 1.0e-5 h^2 seconds for h heights on the chip's host: 45 s for 1,500,
# 73 for 2,000; PERF.md, PR 33).  A child process (it never imports JAX, so it never
# asks for the chip) makes the chain while the parent sets the kernel's
# shapes up, and hands the two stores over as key/value pairs.  The
# parent makes the first heights itself meanwhile (``fabricate`` with
# fewer ``heights``: the chain is a function of the seed, so they are
# the child's first), for what it verifies before the child is done.

def start_child(out_path: str, **kw) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               COMETBFT_TPU_CRYPTO_BACKEND="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), json.dumps(kw),
         out_path], env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)


def load_child(proc: subprocess.Popen, out_path: str, **kw) -> Chain:
    """Wait for the child and rebuild its chain in this process."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.state.store import Store
    from cometbft_tpu.store import BlockStore

    if proc.wait() != 0:
        raise RuntimeError(
            f"the chain fabricator exited with {proc.returncode}")
    with open(out_path, "rb") as f:
        made = pickle.load(f)
    os.unlink(out_path)
    dbs = {}
    for name in ("blocks", "state"):
        dbs[name] = MemDB()
        for k, v in made[name]:
            dbs[name].set(k, v)
    doc, _, vset, privs = _genesis_state(
        kw["chain_id"], kw["seed"], kw["n_validators"], kw["power"])
    state_store = Store(dbs["state"])
    return Chain(kw["chain_id"], doc, vset, privs, state_store,
                 BlockStore(dbs["blocks"]), KVStoreApplication(),
                 state_store.load(), made["block_hash"],
                 made["app_hash"], dbs)


def _child_main(argv: list[str]) -> int:
    import asyncio
    import logging
    logging.disable(logging.CRITICAL)
    kw, out_path = json.loads(argv[1]), argv[2]
    chain = asyncio.run(fabricate(**kw))
    with open(out_path + ".tmp", "wb") as f:
        pickle.dump({
            "blocks": list(chain.dbs["blocks"].iterator()),
            "state": list(chain.dbs["state"].iterator()),
            "block_hash": chain.block_hash,
            "app_hash": chain.app_hash}, f,
            protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv))
