"""Traffic kind ``catchup``: a fresh node block-syncs a fabricated,
seeded chain from one in-process source peer through the program's
BlocksyncReactor over localhost p2p; closed loop (the node asks for
the next blocks as soon as it can).

Set-up fabricates the chain from the seed (reference/chain.py) in a
child process and, meanwhile, in this one: the program's own warm-up of
the device path, with the chain's first ``PREFIX_HEIGHTS`` made here
from the same seed beside it, then pre-warm, the verifications the
sync makes of those heights.  Only then does it wait for the child, and
holds the child's chain to the prefix, block hash and app hash at every
height, so that what pre-warm verified is the chain the node syncs.  It
serves the chain from a BlocksyncReactor(active=False) on its own
Switch, and starts a fresh node (fresh app, stores and state) syncing
from it as a persistent peer.  Warm-up is the sync itself, until the
warm-up rule is quiet; the window then counts the heights the node
saved, by the timestamps the benchmark's own BlockStore puts on each
save.  The chain must outlast warm-up plus window: if the node comes
within ``chain_margin`` heights of its end, the run fails instead of
reporting a short window, and names the rate the chain would have held
(``ceiling``).

One height inside warm-up (``forged_height``) is served the first time
with a LastCommit forged below the 2/3 mark: the node must refuse it,
drop the peer, take the honest block on the persistent peer's
reconnect, and end on the honest chain.

Parameters (cell file, then configuration): validators, power,
txs_per_block, tx_bytes, chain_heights, chain_margin, forged_height,
prewarm_ops, warmup_ops, kv_check_keys.
"""
from __future__ import annotations

import asyncio
import gc
import os
import time
from dataclasses import dataclass, field

from benchmark.lib.session import Outcome
from benchmark.reference import chain as chainlib
from benchmark.reference import fixtures

CHAIN_ID = "bench-catchup"
# the heights pre-warm may take to go quiet: it was quiet after 46 in
# every run of PR 32, and fails past these
PREFIX_HEIGHTS = 96


class ServingStore:
    """The source peer's block store: the fabricated chain's, except
    that ``forge_at`` is served with one LastCommit signature forged
    the first time it is asked for."""

    def __init__(self, store, forge_at: int, forge_index: int, rng):
        self._store = store
        self.forge_at = forge_at
        self.forge_index = forge_index
        self._rng = rng
        self.served: dict[int, int] = {}

    def __getattr__(self, name):
        return getattr(self._store, name)

    def load_block(self, height: int):
        block = self._store.load_block(height)
        n = self.served.get(height, 0)
        self.served[height] = n + 1
        if block is not None and height == self.forge_at and n == 0:
            cs = block.last_commit.signatures[self.forge_index]
            cs.signature = fixtures.flip_bit(self._rng, cs.signature,
                                             0, 32)
        return block


def stamped_store():
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.store import BlockStore

    class StampedBlockStore(BlockStore):
        """The syncing node's store, timestamping each save."""

        def __init__(self, db):
            super().__init__(db)
            self.save_ns: list[int] = []

        def save_block(self, block, parts, seen_commit):
            super().save_block(block, parts, seen_commit)
            self.save_ns.append(time.monotonic_ns())

    return StampedBlockStore(MemDB())


@dataclass
class State:
    chain: object
    serving: ServingStore
    src_switch: object
    dst_switch: object
    dst_reactor: object
    dst_store: object
    dst_state_store: object
    dst_app: object
    margin: int
    seconds: float
    window_from: int | None = None      # the node's height when the
                                        # window opened
    setup_spans: list = field(default_factory=list)
    registries: tuple = ()
    done: asyncio.Event = field(default_factory=asyncio.Event)


async def set_up(ctx) -> State:
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.blocksync import BlocksyncReactor
    from cometbft_tpu.crypto import _native_loader
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.node.node import warm_device_path
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.p2p.switch import Switch
    from cometbft_tpu.state import make_genesis_state
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.store import Store

    n = int(ctx.param("validators"))
    if _native_loader.load(allow_build=True) is None:
        raise RuntimeError("native host prep did not build")
    ctx.lap("native")
    # the chain is made by a child while this process sets shapes up
    chain_kw = dict(
        chain_id=CHAIN_ID, seed=ctx.seed, n_validators=n,
        power=int(ctx.param("power")),
        heights=int(ctx.param("chain_heights")),
        txs_per_block=int(ctx.param("txs_per_block")),
        tx_bytes=int(ctx.param("tx_bytes")))
    chain_file = os.path.join(ctx.work_dir, f"chain-{ctx.seed}.pickle")
    child = chainlib.start_child(chain_file, **chain_kw)
    try:
        # what a node does before it verifies anything (node.py start),
        # in a thread; beside it, on the loop, the chain's first heights
        # from the same seed (no commit is verified in making them:
        # nothing goes to the device)
        prefix_kw = dict(chain_kw, heights=min(PREFIX_HEIGHTS,
                                               chain_kw["heights"]))
        if crypto_batch.get_backend() == "tpu":
            _, prefix = await asyncio.gather(
                asyncio.to_thread(warm_device_path, n),
                chainlib.fabricate(**prefix_kw))
        else:
            prefix = await chainlib.fabricate(**prefix_kw)
        ctx.lap("warm_device_path")

        # pre-warm: the verifications the sync makes of the chain's
        # first heights, made directly and in a thread of their own,
        # until the warm-up rule is quiet.  The program sets a kernel
        # shape up inside the call that first needs it, and that costs
        # three to four times as much from the reactor's deep stack on
        # the event loop (where it also stalls the loop until the peer
        # is dropped for a timeout) as from a shallow one (PERF.md,
        # PR 22).  It runs over the prefix, so the child has the second
        # cold shape's set-up to hide under as well as the first's.
        pre = ctx.warmup_gate()
        pre.min_ops = int(ctx.param("prewarm_ops"))
        await asyncio.to_thread(prewarm, prefix, pre)
        ctx.lap("prewarm")
        chain = await asyncio.to_thread(chainlib.load_child, child,
                                        chain_file, **chain_kw)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    must_extend(prefix, chain)
    del prefix
    gc.freeze()         # the chain is the benchmark's, not the program's
    ctx.lap("chain")

    # source peer: serves blocks only
    mark = chain.vset.total_voting_power() * 2 // 3 \
        // chain.vset.validators[0].voting_power + 1
    rng = ctx.rng("forge")
    # the block AFTER forged_height carries its LastCommit
    serving = ServingStore(chain.block_store,
                           int(ctx.param("forged_height")) + 1,
                           rng.randrange(mark), rng)
    src_switch = Switch(NodeKey.generate(), CHAIN_ID,
                        listen_addr="127.0.0.1:0")
    src_exec = BlockExecutor(
        chain.state_store, AppConns(chain.app).consensus,
        block_store=serving)
    src_switch.add_reactor(BlocksyncReactor(
        chain.state, src_exec, serving, active=False))
    await src_switch.start()

    # the node under test: fresh app, stores and state
    dst_app = KVStoreApplication()
    dst_conns = AppConns(dst_app)
    dst_ss, dst_bs = Store(MemDB()), stamped_store()
    dst_state = make_genesis_state(chain.doc)
    dst_ss.save(dst_state)
    await dst_conns.consensus.init_chain(
        abci.InitChainRequest(chain_id=CHAIN_ID))
    dst_exec = BlockExecutor(dst_ss, dst_conns.consensus,
                             block_store=dst_bs)
    state = State(chain=chain, serving=serving, src_switch=src_switch,
                  dst_switch=None, dst_reactor=None, dst_store=dst_bs,
                  dst_state_store=dst_ss, dst_app=dst_app,
                  margin=int(ctx.param("chain_margin")),
                  seconds=ctx.seconds)

    async def on_caught_up(st, height):
        state.done.set()

    dst_switch = Switch(NodeKey.generate(), CHAIN_ID,
                        listen_addr="127.0.0.1:0")
    dst_reactor = BlocksyncReactor(dst_state, dst_exec, dst_bs,
                                   active=True,
                                   on_caught_up=on_caught_up)
    dst_switch.add_reactor(dst_reactor)
    await dst_switch.start()
    await dst_reactor.start_sync()
    dst_switch.dial_peers_async([src_switch.listen_addr],
                                persistent=True)
    state.dst_switch, state.dst_reactor = dst_switch, dst_reactor
    ctx.lap("net")

    # warm-up: the cell's own traffic — the sync itself
    gate = ctx.warmup_gate()
    seen = 0
    while not gate.done():
        await asyncio.sleep(0.05)
        now = len(dst_bs.save_ns)
        gate.op_done(now - seen)
        seen = now
        _must_have_chain_left(state)
    if serving.served.get(serving.forge_at, 0) < 2:
        raise RuntimeError(
            f"warm-up ended at height {dst_bs.height} before the "
            f"forged block {serving.forge_at} was refused and asked "
            f"for again")
    state.setup_spans = pre.setup_spans + gate.setup_spans
    ctx.lap("warmup")
    print(f"[catchup] pre-warm {pre.ops} heights (last change at "
          f"{pre.last_change_op}); warm after {gate.ops} heights (last change at "
          f"{gate.last_change_op}); buckets seen "
          f"{sorted(gate.buckets)}; forged block {serving.forge_at} "
          f"served {serving.served[serving.forge_at]} times",
          flush=True)
    return state


def prewarm(chain, gate) -> None:
    """verify_commit_light and verify_commit over ``chain``'s heights,
    as the sync makes them, until ``gate`` is quiet."""
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.validation import (
        verify_commit, verify_commit_light,
    )
    store, ids = chain.block_store, {}
    for h in range(1, chain.height - 1):
        block, nxt = store.load_block(h), store.load_block(h + 1)
        ids[h] = BlockID(
            hash=block.hash(),
            part_set_header=block.make_part_set().header())
        verify_commit_light(CHAIN_ID, chain.vset, ids[h], h,
                            nxt.last_commit)
        if h > 1:
            verify_commit(CHAIN_ID, chain.vset, ids[h - 1], h - 1,
                          block.last_commit)
        gate.op_done()
        if gate.done():
            return
    raise RuntimeError("pre-warm never went quiet")


def must_extend(prefix, chain) -> None:
    """The child's chain begins with the heights pre-warm verified."""
    for h in range(1, prefix.height + 1):
        if chain.block_hash.get(h) != prefix.block_hash[h] or \
                chain.app_hash.get(h) != prefix.app_hash[h]:
            raise RuntimeError(
                f"the fabricated chain differs from the prefix "
                f"pre-warm verified at height {h}: the fabricator is "
                f"not a function of the seed")


def ceiling(chain_heights: int, margin: int, window_from: int,
            seconds: float) -> float:
    """The fastest sync, in heights/s, that a chain of ``chain_heights``
    outlasts when the window opens with the node at ``window_from``."""
    return (chain_heights - margin - window_from) / seconds


def _must_have_chain_left(state: State) -> None:
    left = state.chain.height - state.dst_store.height
    if left < state.margin or state.done.is_set():
        raise RuntimeError(
            f"the chain ran out: node at height "
            f"{state.dst_store.height} of {state.chain.height} "
            f"(margin {state.margin}); {_holds(state)}; lengthen "
            f"chain_heights")


def _holds(state: State) -> str:
    if state.window_from is None:
        return "the window had not opened"
    rate = ceiling(state.chain.height, state.margin, state.window_from,
                   state.seconds)
    return (f"a {state.seconds:g} s window opened at height "
            f"{state.window_from} holds at most {rate:.1f} heights/s")


async def run(ctx, state: State, window) -> dict:
    state.window_from = state.dst_store.height
    await asyncio.sleep(max(0.0, window.end - time.monotonic()))
    _must_have_chain_left(state)
    # the window is over: stop asking, so that nothing competes with
    # the checks (and the trace's write-out) for the loop
    await state.dst_reactor.stop_sync()
    t0, t1 = int(window.start * 1e9), int(window.end * 1e9)
    saves = [t for t in state.dst_store.save_ns if t0 <= t < t1]
    first = next((i for i, t in enumerate(state.dst_store.save_ns)
                  if t >= t0), len(state.dst_store.save_ns))
    return {"save_ns": saves, "first_height": first + 1,
            "heights": len(saves)}


def end_to_end(ctx, state: State, samples: dict) -> dict:
    return {"sync_heights_per_s": samples["heights"] / ctx.seconds}


async def check(ctx, state: State, samples: dict) -> Outcome:
    from cometbft_tpu.abci import types as abci

    problems = []
    chain, store = state.chain, state.dst_store
    top = store.height
    bad = []
    for h in range(1, top + 1):
        meta = store.load_block_meta(h)
        if meta is None or meta.block_id.hash != chain.block_hash[h]:
            bad.append(h)
        elif h > 1 and meta.header.app_hash != chain.app_hash[h - 1]:
            bad.append(h)
    final = state.dst_state_store.load()
    if final.last_block_height != top or \
            final.app_hash != chain.app_hash[top]:
        problems.append(
            f"state after height {top}: app hash differs from the "
            f"chain's")
    if bad:
        problems.append(f"{len(bad)} synced heights differ from the "
                        f"chain, first {bad[:4]}")
    # the plain reference of the app: a key holds the last value
    # written to it by a synced block
    written = {}
    for h in range(1, top + 1):
        for i in range(int(ctx.param("txs_per_block"))):
            tx = fixtures.seeded_tx(ctx.seed, h, i,
                                    int(ctx.param("tx_bytes")))
            key, _, value = tx.partition(b"=")
            written[key] = value
    rng = ctx.rng("kv-check")
    keys = rng.sample(sorted(written),
                      min(int(ctx.param("kv_check_keys")),
                          len(written)))
    for key in keys:
        got = await state.dst_app.query(abci.QueryRequest(data=key))
        if got.value != written[key]:
            problems.append(f"key {key!r} does not read back")
            break
    f = state.serving.forge_at
    if state.serving.served.get(f, 0) < 2 or top < f:
        problems.append(f"forged block {f} was not refused and asked "
                        f"for again")
    in_window = samples["heights"]
    wrong = sum(1 for h in bad if samples["first_height"] <= h
                < samples["first_height"] + in_window)
    print(f"[catchup] synced to {top} of {chain.height} "
          f"({_holds(state)}); window "
          f"heights {samples['first_height']}.."
          f"{samples['first_height'] + in_window - 1}; {len(bad)} "
          f"differ; {len(keys)} keys read back; forged block {f} "
          f"served {state.serving.served.get(f, 0)} times", flush=True)
    return Outcome(attempted=in_window, failed=wrong,
                   problems=problems)


async def tear_down(ctx, state: State) -> None:
    await state.dst_reactor.stop_sync()
    await state.dst_switch.stop()
    await state.src_switch.stop()
