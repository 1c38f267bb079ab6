"""Traffic kind ``replay``: a full node outside the validator set plays
a fabricated, seeded consensus WAL back through the program's
``consensus/replay.playback`` (the ``replay`` command's function: a
``ConsensusState`` in replay mode over fresh stores and a fresh kvstore,
fed every record in WAL order); closed loop (the next record as soon as
the last is handled).

Set-up fabricates the WAL from the seed (reference/wal.py) in a child
process and, meanwhile, in this one: the program's own warm-up of the
device path, with the WAL's first ``prefix_heights`` made here from the
same seed beside it, then pre-warm, a playback of that prefix on stores
of its own until the warm-up rule is quiet (the shapes the tuner inserts
for a height's two batches are set up inside the calls that first need
them).  Only then does it wait for the child, and holds the child's
chain to the prefix, block hash and app hash at every height.  Warm-up
is the playback itself, started as one task that runs on through the
window; the window counts the heights whose ``_finalize_commit``
returned inside it, by the timestamps the witness puts on each new
height.  The WAL must outlast warm-up plus window: if the playback comes
within ``wal_margin`` heights of its end, the run fails instead of
reporting a short window, and names the rate the WAL would have held.

``check`` holds every replayed height to the chain (block hash, app
hash, the tx's key) and to reference/wal_replay.py at the timed sizes:
every vote's verdict (the forged ones refused with the reference's
reason, their heights still committed), the seen commit at +2/3, the
last commit after the late precommits, the number of signatures every
``vote_preverify`` held; and the serial verifications of the window to
the forged votes' confirmations and nothing else.  Integers, sets and
bits: no tolerance.

Parameters (cell file, then configuration): validators, power,
tx_bytes, forged_one_in, wal_heights, wal_margin, prefix_heights,
prewarm_ops, warmup_ops, kv_check_keys, fabricator_workers,
reference_workers.
"""
from __future__ import annotations

import asyncio
import gc
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from benchmark.lib import probes
from benchmark.lib.session import Outcome
from benchmark.reference import fixtures
from benchmark.reference import wal as walfab
from benchmark.reference import wal_replay as reference

CHAIN_ID = "bench-wal"
EXIT_NO_PROGRAM = 3     # run.py's: the program is not in this checkout


class StampedWitness(reference.Witness):
    """The witness, timestamping each new height: ``_finalize_commit``
    announces the next height's first step as its last act."""

    def __init__(self):
        super().__init__()
        self.advanced_ns: list[int] = []

    def publish_new_round_step(self, summary: dict) -> None:
        if summary["height"] > self.height and self.height:
            self.advanced_ns.append(time.monotonic_ns())
        self.height = summary["height"]


@dataclass
class Node:
    """What a playback runs over, and what it shows."""
    witness: StampedWitness
    block_store: object
    state_store: object
    app: object
    conns: object
    state: object

    @property
    def height(self) -> int:
        return self.block_store.height


@dataclass
class State:
    made: walfab.Fabricated
    vset: object
    node: Node
    task: asyncio.Task
    margin: int
    seconds: float
    window_from: int | None = None
    setup_spans: list = field(default_factory=list)


async def fresh_node(doc) -> Node:
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.state import make_genesis_state
    from cometbft_tpu.state.store import Store
    from cometbft_tpu.store import BlockStore

    app = KVStoreApplication()
    conns = AppConns(app)
    state_store, block_store = Store(MemDB()), BlockStore(MemDB())
    state = make_genesis_state(doc)
    state_store.save(state)
    await conns.consensus.init_chain(
        abci.InitChainRequest(chain_id=CHAIN_ID))
    return Node(StampedWitness(), block_store, state_store, app, conns,
                state)


def play(node: Node, wal_path: str):
    from cometbft_tpu.config import ConsensusConfig
    from cometbft_tpu.consensus.replay import playback
    return playback(ConsensusConfig(), node.state, node.state_store,
                    node.block_store, node.conns, wal_path,
                    event_bus=node.witness, logger=node.witness)


async def set_up(ctx) -> State:
    try:
        from cometbft_tpu.consensus.replay import playback  # noqa: F401
    except ImportError:
        print("benchmark: this checkout's consensus/replay.py has no "
              f"playback of a whole WAL: {ctx.cell.name} cannot run here",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_PROGRAM)
    from cometbft_tpu.crypto import _native_loader
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.node.node import warm_device_path

    n = int(ctx.param("validators"))
    heights = int(ctx.param("wal_heights"))
    if _native_loader.load(allow_build=True) is None:
        raise RuntimeError("native host prep did not build")
    ctx.lap("native")
    # the WAL is made by a child while this process sets shapes up
    wal_dir = os.path.join(ctx.work_dir, f"wal-{ctx.seed}")
    prefix_dir = os.path.join(ctx.work_dir, f"prefix-{ctx.seed}")
    for d in (wal_dir, prefix_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    wal_kw = dict(
        wal_path=os.path.join(wal_dir, "wal"), chain_id=CHAIN_ID,
        seed=ctx.seed, n_validators=n, power=int(ctx.param("power")),
        heights=heights, tx_bytes=int(ctx.param("tx_bytes")),
        forged_one_in=int(ctx.param("forged_one_in")),
        workers=int(ctx.param("fabricator_workers")))
    made_file = os.path.join(ctx.work_dir, f"made-{ctx.seed}.pickle")
    child = walfab.start_child(made_file, **wal_kw)
    try:
        # what a node does before it verifies anything (node.py start),
        # in a thread; beside it, on the loop, the WAL's first heights
        # from the same seed (nothing goes to the device in making them)
        prefix_kw = dict(
            wal_kw, wal_path=os.path.join(prefix_dir, "wal"),
            heights=min(int(ctx.param("prefix_heights")), heights),
            workers=1)      # in this process, which holds the chip
        if crypto_batch.get_backend() == "tpu":
            _, prefix = await asyncio.gather(
                asyncio.to_thread(warm_device_path, n),
                walfab.fabricate(**prefix_kw))
        else:
            prefix = await walfab.fabricate(**prefix_kw)
        ctx.lap("warm_device_path")

        # pre-warm: the prefix played back on stores of its own until
        # the warm-up rule is quiet, so that the child has the set-up
        # of the shapes a height's two batches end at to hide under
        doc, vset = walfab.genesis(CHAIN_ID, ctx.seed, n,
                                   int(ctx.param("power")))
        pre = ctx.warmup_gate()
        pre.min_ops = int(ctx.param("prewarm_ops"))
        pre_node = await fresh_node(doc)
        task = asyncio.get_running_loop().create_task(
            play(pre_node, prefix.wal_path))
        await _warm(task, pre_node, pre)
        if not pre.done():
            raise RuntimeError(
                f"pre-warm never went quiet over the prefix's "
                f"{prefix.heights} heights (last change at "
                f"{pre.last_change_op})")
        await _cancel(task)
        del pre_node
        shutil.rmtree(prefix_dir, ignore_errors=True)
        ctx.lap("prewarm")
        made = await asyncio.to_thread(walfab.load_child, child,
                                       made_file)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    for h in range(1, prefix.heights + 1):
        if made.block_hash.get(h) != prefix.block_hash[h] or \
                made.app_hash.get(h) != prefix.app_hash[h]:
            raise RuntimeError(
                f"the fabricated chain differs from the prefix "
                f"pre-warm played back at height {h}: the fabricator "
                f"is not a function of the seed")
    gc.freeze()         # the record is the benchmark's, not the program's
    ctx.lap("wal")

    # warm-up: the cell's own traffic, the playback itself, which runs
    # on through the window
    node = await fresh_node(doc)
    state = State(made=made, vset=vset, node=node, task=None,
                  margin=int(ctx.param("wal_margin")),
                  seconds=ctx.seconds)
    state.task = asyncio.get_running_loop().create_task(
        play(node, made.wal_path))
    gate = ctx.warmup_gate()
    await _warm(state.task, node, gate, state)
    if not gate.done():
        raise RuntimeError("the playback ended inside warm-up")
    state.setup_spans = pre.setup_spans + gate.setup_spans
    ctx.lap("warmup")
    forged = sorted(h for h in made.forged if h <= node.height)
    print(f"[replay] pre-warm {pre.ops} heights (last change at "
          f"{pre.last_change_op}); warm after {gate.ops} heights (last "
          f"change at {gate.last_change_op}); buckets seen "
          f"{sorted(gate.buckets | pre.buckets)}; forged so far at "
          f"{forged}; WAL {_wal_bytes(made.wal_path) >> 20} MiB",
          flush=True)
    # what warm-up left behind (traced and lowered kernel shapes are a
    # few million objects): out of every later collection
    gc.collect()
    gc.freeze()
    return state


async def _warm(task, node: Node, gate, state=None) -> None:
    """Feed ``gate`` the heights ``task``'s playback commits until it
    is quiet or the playback is over."""
    seen = 0
    while not gate.done() and not task.done():
        await asyncio.sleep(0.05)
        now = len(node.witness.advanced_ns)
        gate.op_done(now - seen)
        seen = now
        if state is not None:
            _must_have_wal_left(state)
    if task.done() and not task.cancelled() and task.exception():
        raise task.exception()


async def _cancel(task) -> None:
    task.cancel()
    try:
        await task
    except asyncio.CancelledError:
        pass


def _wal_bytes(wal_path: str) -> int:
    from cometbft_tpu.consensus.wal import WAL
    return sum(os.path.getsize(f) for f in WAL.group_files(wal_path))


def ceiling(wal_heights: int, margin: int, window_from: int,
            seconds: float) -> float:
    """The fastest playback, in heights/s, that a WAL of
    ``wal_heights`` outlasts when the window opens at ``window_from``."""
    return (wal_heights - margin - window_from) / seconds


def _must_have_wal_left(state: State) -> None:
    left = state.made.heights - state.node.height
    if left < state.margin or state.task.done():
        raise RuntimeError(
            f"the WAL ran out: playback at height {state.node.height} "
            f"of {state.made.heights} (margin {state.margin}); "
            f"{_holds(state)}; lengthen wal_heights")


def _holds(state: State) -> str:
    if state.window_from is None:
        return "the window had not opened"
    rate = ceiling(state.made.heights, state.margin, state.window_from,
                   state.seconds)
    return (f"a {state.seconds:g} s window opened at height "
            f"{state.window_from} holds at most {rate:.1f} heights/s")


async def run(ctx, state: State, window) -> dict:
    from cometbft_tpu.types import vote as vote_mod
    state.window_from = state.node.height
    in_hand = state.node.witness.height
    _, serial0 = vote_mod.verify_counts()
    await asyncio.sleep(max(0.0, window.end - time.monotonic()))
    _must_have_wal_left(state)
    _, serial1 = vote_mod.verify_counts()
    # the window is over: stop, so that nothing competes with the
    # checks (and the trace's write-out) for the loop
    last_in_hand = state.node.witness.height
    await _cancel(state.task)
    t0, t1 = int(window.start * 1e9), int(window.end * 1e9)
    stamps = state.node.witness.advanced_ns
    return {"heights": sum(1 for t in stamps if t0 <= t < t1),
            "first_in_hand": in_hand, "last_in_hand": last_in_hand,
            "serial_verifies": serial1 - serial0}


def end_to_end(ctx, state: State, samples: dict) -> dict:
    return {"sync_heights_per_s": samples["heights"] / ctx.seconds}


def chunk_of(kind: str, height: int) -> int:
    """The read-ahead (named by the height in hand) that holds a vote
    OF ``height``: a late precommit arrives behind its height's marker."""
    return height + (kind == walfab.LATE)


async def check(ctx, state: State, samples: dict) -> Outcome:
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.libs import tracing

    problems = []
    made, node = state.made, state.node
    store, top = node.block_store, node.height
    bad = []
    for h in range(1, top + 1):
        meta = store.load_block_meta(h)
        if meta is None or meta.block_id.hash != made.block_hash[h]:
            bad.append(h)
        elif h > 1 and meta.header.app_hash != made.app_hash[h - 1]:
            bad.append(h)
    final = node.state_store.load()
    if final.last_block_height != top or \
            final.app_hash != made.app_hash[top]:
        problems.append(f"state after height {top}: app hash differs "
                        f"from the chain's")
    if bad:
        problems.append(f"{len(bad)} replayed heights differ from the "
                        f"chain, first {bad[:4]}")

    # the plain reference, at the timed sizes; the late precommits of
    # the last height may not have been handled when the window closed
    t0 = time.monotonic()
    want = await asyncio.to_thread(
        reference.run_parallel, CHAIN_ID,
        reference.validators_of(state.vset), made.wal_path, top,
        int(ctx.param("reference_workers")), ctx.work_dir)
    reference_s = time.monotonic() - t0
    differ = reference.differences(want, node.witness, store,
                                   range(1, top))
    problems += differ[:4]
    if node.witness.errors:
        problems.append(f"the playback logged {len(node.witness.errors)} "
                        f"errors, first {node.witness.errors[0]}")
    refused = sum(len(r) for h, r in node.witness.refused.items()
                  if h < top)
    if refused != sum(1 for h in made.forged if h < top):
        problems.append(f"{refused} votes refused below height {top}, "
                        f"not every forged one and only those")

    # a vote verified one by one on the CPU is a batch that left the
    # device path: over the window, the forged votes' confirmations
    # and nothing else (a read-ahead in hand when the window opened or
    # closed may lie on either side)
    first, last = samples["first_in_hand"], samples["last_in_hand"]
    chunks = [chunk_of(kind, h) for h, (kind, _) in made.forged.items()]
    sure = sum(1 for c in chunks if first < c < last)
    maybe = sum(1 for c in chunks if c in (first, last))
    if not sure <= samples["serial_verifies"] <= sure + maybe:
        problems.append(
            f"{samples['serial_verifies']} serial verifications in "
            f"the window for {sure} to {sure + maybe} forged votes")

    # every pre-verification held what the reference counts between
    # two markers
    held = {ev["height"]: probes.attr(ev, "entries")
            for ev in tracing.snapshot(category=tracing.CONSENSUS)
            if ev["name"] == "vote_preverify"}
    short = [h for h, n in held.items()
             if h in want and h <= top and n != want[h].votes]
    if short or not held:
        problems.append(
            f"{len(short)} of {len(held)} pre-verifications did not "
            f"hold their read-ahead's votes, first {short[:4]}")

    # the plain reference of the app: a key holds the value its tx wrote
    rng = ctx.rng("kv-check")
    for h in rng.sample(range(1, top + 1),
                        min(int(ctx.param("kv_check_keys")), top)):
        tx = fixtures.seeded_tx(ctx.seed, h, 0, int(ctx.param("tx_bytes")))
        key, _, value = tx.partition(b"=")
        got = await node.app.query(abci.QueryRequest(data=key))
        if got.value != value:
            problems.append(f"key {key!r} does not read back")
            break
    in_window = samples["heights"]
    wrong = sum(1 for h in bad if first <= h < first + in_window)
    print(f"[replay] played back to {top} of {made.heights} "
          f"({_holds(state)}); window heights {first}.."
          f"{first + in_window - 1}; {len(bad)} differ from the chain, "
          f"{len(differ)} from the reference ({reference_s:.1f} s, "
          f"{sum(len(r.accepted) for r in want.values())} votes "
          f"accepted, {refused} refused); "
          f"{samples['serial_verifies']} serial verifications in the "
          f"window for {sure}..{sure + maybe} forged; "
          f"{len(held)} pre-verifications held to their read-aheads",
          flush=True)
    return Outcome(attempted=in_window, failed=wrong + len(differ),
                   problems=problems)


async def tear_down(ctx, state: State) -> None:
    await _cancel(state.task)
    shutil.rmtree(os.path.dirname(state.made.wal_path),
                  ignore_errors=True)
