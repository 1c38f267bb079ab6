"""Traffic driver ``replay_ext``: traffic/replay.py's playback, over a
chain with vote extensions on (configuration ``vote-ext-60``, the
shape of dYdX Chain): a full node outside the validator set plays a
fabricated, seeded WAL back through the program's
``consensus/replay.playback`` over fresh stores and a fresh ``oracle``
app (abci/oracle.py), closed loop.

Per height the WAL holds the late precommits of the height before (with
their extensions), the ``timeout``, the proposal and its two block
parts (the ~100 kB injected ``ExtendedCommitInfo`` of the height before,
then one 1 kB tx), a prevote a validator and the precommits up to +2/3,
each with ~1.8 kB of oracle prices and both extension signatures
(reference/wal_ext.py, which also forges one vote in
``forged_one_in`` heights, six kinds by turns).  Set-up, warm-up, the
window and the WAL's run-out are traffic/replay.py's.

``check`` holds every replayed height to the chain (block hash, app
hash: the oracle's medians are in it) and to
reference/wal_ext_replay.py at the timed sizes: every vote's verdict and
both extension verdicts of every non-nil precommit (a forged one refused
with the serial path's own words, its height still committed), the seen
commit and the seen EXTENDED commit at +2/3, the last commit after the
late precommits, the signatures and the extension signatures every
``vote_preverify`` held; the oracle's medians of sampled heights read
back from the app against the reference's decoder; and the serial
verifications of the window, of vote and of extension signatures, to the
forged ones' confirmations and nothing else.  Integers, sets and bits:
no tolerance.

Parameters (cell file, then configuration): validators, power,
tx_bytes, price_pairs, forged_one_in, wal_heights, wal_margin,
prefix_heights, prewarm_ops, warmup_ops, kv_check_keys,
fabricator_workers, reference_workers.
"""
from __future__ import annotations

import asyncio
import gc
import os
import shutil
import sys
import time

from benchmark.lib import probes
from benchmark.lib.session import Outcome
from benchmark.reference import fixtures
from benchmark.reference import wal as walfab
from benchmark.reference import wal_ext as fab
from benchmark.reference import wal_ext_replay as reference
from benchmark.reference import wal_replay
from benchmark.traffic import replay as base

CHAIN_ID = "bench-wal-ext"
State = base.State


async def fresh_node(doc) -> base.Node:
    from cometbft_tpu.abci.oracle import OracleApplication
    app = OracleApplication()
    conns, state_store, block_store, state = await fab.fresh_node(doc, app)
    return base.Node(base.StampedWitness(), block_store, state_store, app,
                     conns, state)


async def set_up(ctx) -> State:
    try:
        from cometbft_tpu.abci.oracle import OracleApplication  # noqa: F401
    except ImportError:
        print("benchmark: this checkout has no oracle app "
              f"(cometbft_tpu/abci/oracle.py): {ctx.cell.name} cannot "
              f"run here", file=sys.stderr)
        raise SystemExit(base.EXIT_NO_PROGRAM)
    from cometbft_tpu.crypto import _native_loader
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.node.node import warm_device_path

    n = int(ctx.param("validators"))
    heights = int(ctx.param("wal_heights"))
    pairs = int(ctx.param("price_pairs"))
    if _native_loader.load(allow_build=True) is None:
        raise RuntimeError("native host prep did not build")
    ctx.lap("native")
    wal_dir = os.path.join(ctx.work_dir, f"wal-{ctx.seed}")
    prefix_dir = os.path.join(ctx.work_dir, f"prefix-{ctx.seed}")
    for d in (wal_dir, prefix_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    wal_kw = dict(
        wal_path=os.path.join(wal_dir, "wal"), chain_id=CHAIN_ID,
        seed=ctx.seed, n_validators=n, power=int(ctx.param("power")),
        heights=heights, tx_bytes=int(ctx.param("tx_bytes")),
        forged_one_in=int(ctx.param("forged_one_in")), price_pairs=pairs,
        workers=int(ctx.param("fabricator_workers")))
    made_file = os.path.join(ctx.work_dir, f"made-{ctx.seed}.pickle")
    child = fab.start_child(made_file, **wal_kw)
    try:
        prefix_kw = dict(
            wal_kw, wal_path=os.path.join(prefix_dir, "wal"),
            heights=min(int(ctx.param("prefix_heights")), heights),
            workers=1)
        if crypto_batch.get_backend() == "tpu":
            _, prefix = await asyncio.gather(
                asyncio.to_thread(warm_device_path, n),
                fab.fabricate(**prefix_kw))
        else:
            prefix = await fab.fabricate(**prefix_kw)
        ctx.lap("warm_device_path")

        doc, vset, _ = fab.genesis(CHAIN_ID, ctx.seed, n,
                                   int(ctx.param("power")))
        pre = ctx.warmup_gate()
        pre.min_ops = int(ctx.param("prewarm_ops"))
        pre_node = await fresh_node(doc)
        task = asyncio.get_running_loop().create_task(
            base.play(pre_node, prefix.wal_path))
        await base._warm(task, pre_node, pre)
        if not pre.done():
            raise RuntimeError(
                f"pre-warm never went quiet over the prefix's "
                f"{prefix.heights} heights (last change at "
                f"{pre.last_change_op})")
        await base._cancel(task)
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
    gc.freeze()
    ctx.lap("wal")

    node = await fresh_node(doc)
    state = State(made=made, vset=vset, node=node, task=None,
                  margin=int(ctx.param("wal_margin")),
                  seconds=ctx.seconds)
    state.task = asyncio.get_running_loop().create_task(
        base.play(node, made.wal_path))
    gate = ctx.warmup_gate()
    await base._warm(state.task, node, gate, state)
    if not gate.done():
        raise RuntimeError("the playback ended inside warm-up")
    state.setup_spans = pre.setup_spans + gate.setup_spans
    ctx.lap("warmup")
    forged = sorted(h for h in made.forged if h <= node.height)
    print(f"[replay_ext] pre-warm {pre.ops} heights (last change at "
          f"{pre.last_change_op}); warm after {gate.ops} heights (last "
          f"change at {gate.last_change_op}); buckets seen "
          f"{sorted(gate.buckets | pre.buckets)}; forged so far at "
          f"{forged}; WAL {base._wal_bytes(made.wal_path) >> 20} MiB",
          flush=True)
    gc.collect()
    gc.freeze()
    return state


async def run(ctx, state: State, window) -> dict:
    from cometbft_tpu.types import vote as vote_mod
    state.window_from = state.node.height
    in_hand = state.node.witness.height
    _, serial0 = vote_mod.verify_counts()
    ext0 = vote_mod.extension_verify_counts()
    await asyncio.sleep(max(0.0, window.end - time.monotonic()))
    base._must_have_wal_left(state)
    _, serial1 = vote_mod.verify_counts()
    ext1 = vote_mod.extension_verify_counts()
    last_in_hand = state.node.witness.height
    await base._cancel(state.task)
    t0, t1 = int(window.start * 1e9), int(window.end * 1e9)
    stamps = state.node.witness.advanced_ns
    return {"heights": sum(1 for t in stamps if t0 <= t < t1),
            "first_in_hand": in_hand, "last_in_hand": last_in_hand,
            "serial_verifies": serial1 - serial0,
            "ext_checks": sum(ext1) - sum(ext0),
            "ext_serial": ext1[1] - ext0[1]}


end_to_end = base.end_to_end


def _window_serial(forged: dict, kinds: tuple, first: int, last: int
                   ) -> tuple[int, int]:
    """(forged votes of ``kinds`` whose read-ahead lies wholly inside
    the window, those of a read-ahead it cuts)."""
    chunks = [fab.chunk_of(kind, h) for h, (kind, _) in forged.items()
              if kind in kinds]
    return (sum(1 for c in chunks if first < c < last),
            sum(1 for c in chunks if c in (first, last)))


async def check(ctx, state: State, samples: dict) -> Outcome:
    from cometbft_tpu.abci import types as abci
    from cometbft_tpu.abci.oracle import oracle_key
    from cometbft_tpu.libs import tracing

    problems = []
    made, node = state.made, state.node
    store, top = node.block_store, node.height
    pairs = int(ctx.param("price_pairs"))
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

    t0 = time.monotonic()
    want = await asyncio.to_thread(
        reference.run_parallel, CHAIN_ID,
        wal_replay.validators_of(state.vset), made.wal_path, pairs, top,
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

    # a signature verified one by one on the CPU is a batch that left
    # the device path: over the window, the forged ones' confirmations
    # and nothing else, of vote and of extension signatures apart
    first, last = samples["first_in_hand"], samples["last_in_hand"]
    for name, kinds, got in (
            ("vote", (fab.PREVOTE, fab.PRECOMMIT),
             samples["serial_verifies"]),
            ("extension", (fab.EXT, fab.NON_RP, fab.LATE_EXT),
             samples["ext_serial"])):
        sure, maybe = _window_serial(made.forged, kinds, first, last)
        if not sure <= got <= sure + maybe:
            problems.append(
                f"{got} serial {name} verifications in the window for "
                f"{sure} to {sure + maybe} forged {name} signatures")

    # every pre-verification held what the reference counts between two
    # markers: every signature, and of them the extension signatures
    held = {ev["height"]: (probes.attr(ev, "entries"),
                           probes.attr(ev, "ext"))
            for ev in tracing.snapshot(category=tracing.CONSENSUS)
            if ev["name"] == "vote_preverify"}
    short = [h for h, (n, ext) in held.items()
             if h in want and h <= top and
             (n, ext) != (want[h].votes + want[h].ext_lanes,
                          want[h].ext_lanes)]
    if short or not held:
        problems.append(
            f"{len(short)} of {len(held)} pre-verifications did not "
            f"hold their read-ahead's signatures, first {short[:4]}")

    # the app against the reference's decoder: a sampled height's
    # medians of its injected commit info, and its tx's key
    rng = ctx.rng("kv-check")
    wrong_prices = []
    for h in rng.sample(range(2, top + 1),
                        min(int(ctx.param("kv_check_keys")), top - 1)):
        txs = store.load_block(h).data.txs
        for pair, price in reference.medians(txs[0], pairs).items():
            got = await node.app.query(abci.QueryRequest(
                data=oracle_key(pair), height=h))
            if got.value != b"%d" % price:
                wrong_prices.append((h, pair))
        tx = fixtures.seeded_tx(ctx.seed, h, 0, int(ctx.param("tx_bytes")))
        key, _, value = tx.partition(b"=")
        got = await node.app.query(abci.QueryRequest(data=key))
        if got.value != value:
            problems.append(f"key {key!r} does not read back")
            break
    if wrong_prices:
        problems.append(f"{len(wrong_prices)} oracle prices differ from "
                        f"the reference's medians, first "
                        f"{wrong_prices[:4]}")

    in_window = samples["heights"]
    wrong = sum(1 for h in bad if first <= h < first + in_window)
    inside = [want[h] for h in range(first + 1, last) if h in want]
    checks = sum(r.ext_checks for r in inside)
    print(f"[replay_ext] played back to {top} of {made.heights} "
          f"({base._holds(state)}); window heights {first}.."
          f"{first + in_window - 1}; {len(bad)} differ from the chain, "
          f"{len(differ)} from the reference ({reference_s:.1f} s, "
          f"{sum(len(r.accepted) for r in want.values())} votes "
          f"accepted, {refused} refused); serial verifications in the "
          f"window: {samples['serial_verifies']} vote, "
          f"{samples['ext_serial']} of {samples['ext_checks']} "
          f"extension (the reference's heights wholly inside: "
          f"{sum(r.ext_serial for r in inside)} of {checks}, "
          f"{100.0 * sum(r.ext_serial for r in inside) / max(checks, 1):.4f}"
          f" %); {len(held)} pre-verifications held to their "
          f"read-aheads", flush=True)
    return Outcome(attempted=in_window, failed=wrong + len(differ),
                   problems=problems)


tear_down = base.tear_down
