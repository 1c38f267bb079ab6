"""Traffic kind ``load``: open-loop txs at a fixed rate to one
validator's RPC of a live in-process net; a tx's commit is observed on
a node other than the one it was submitted to.

Set-up boots the configuration's net in this process (the one that
holds the chip) the way tools/manifest.py does — manifest.setup() for
homes, keys, genesis and peer wiring, then in-process Nodes on
localhost sockets, with the consensus timeouts put back to the
program's defaults — subscribes to NewBlock on the observer, and runs
the cell's own traffic until the warm-up rule is quiet.  Each tx is
``key=value`` of ``tx_bytes`` bytes with a key of its own, sent by
broadcast_tx_sync in a task of its own at its due time, and timed from
the instant it was DUE to the instant the observer published the block
that holds it.  A tx due in the window that was not accepted, or was
accepted and not seen committed by the end of the window plus
``grace_s``, counts in ``failed``.

Parameters (cell file, then configuration): validators, full_nodes,
tx_bytes, rate_tx_per_s, warmup_ops, grace_s, readback_txs,
start_timeout_s.
"""
from __future__ import annotations

import asyncio
import base64
import os
import shutil
import time
from dataclasses import dataclass, field

from benchmark.lib import schedule, stats
from benchmark.lib.session import Outcome
from benchmark.reference import fixtures


@dataclass
class Sent:
    due: float
    late_s: float
    tx: bytes
    accepted: bool = False
    error: str = ""
    seen: float = 0.0           # observer's clock, 0 = not seen


@dataclass
class State:
    nodes: dict
    entry: str                  # node taking the txs
    observer: str               # node the commits are seen on
    sub: object
    observer_task: object
    by_tx: dict = field(default_factory=dict)       # tx -> Sent
    blocks: list = field(default_factory=list)      # (ns, height, ntxs)
    setup_spans: list = field(default_factory=list)
    registries: tuple = ()
    phase: int = 0

    def endpoint(self, name: str) -> str:
        return f"http://{self.nodes[name]._rpc_server.listen_addr}"


async def _observe(state: State) -> None:
    """Stamp every block the observer publishes, and every tx in it."""
    from cometbft_tpu.libs.pubsub import PubSubError
    try:
        while True:
            msg = await state.sub.next()
            now = time.monotonic()
            block = msg.data.payload["block"]
            state.blocks.append((int(now * 1e9), block.header.height,
                                 len(block.data.txs)))
            for tx in block.data.txs:
                sent = state.by_tx.get(bytes(tx))
                if sent is not None and not sent.seen:
                    sent.seen = now
    except (PubSubError, asyncio.CancelledError):
        return


async def _offer(ctx, state: State, start: float, seconds: float,
                 rate: float) -> list[Sent]:
    """Offer ``rate`` tx/s for ``seconds`` from ``start``, open loop;
    returns once every send has been answered."""
    from cometbft_tpu.rpc.client import HTTPClient

    state.phase += 1
    phase = state.phase
    size = int(ctx.param("tx_bytes"))
    cli = HTTPClient(state.endpoint(state.entry), timeout=10.0)
    sent: list[Sent] = []
    tasks: set = set()

    async def send_one(s: Sent) -> None:
        try:
            r = await cli.broadcast_tx_sync(s.tx)
            s.accepted = int(r.get("code", 0)) == 0
            if not s.accepted:
                s.error = f"code {r.get('code')}: {r.get('log', '')}"
        except Exception as e:  # noqa: BLE001 — counted as failed
            s.error = f"{type(e).__name__}: {e}"

    async for i, due, late in schedule.paced(start, 1.0 / rate,
                                             seconds):
        s = Sent(due=due, late_s=late,
                 tx=fixtures.seeded_tx(ctx.seed, phase, i, size))
        state.by_tx[s.tx] = s
        sent.append(s)
        t = asyncio.get_running_loop().create_task(send_one(s))
        tasks.add(t)
        t.add_done_callback(tasks.discard)
    if tasks:
        await asyncio.wait(set(tasks), timeout=12.0)
    for t in list(tasks):
        t.cancel()
    return sent


async def _drain(sent: list[Sent], grace_s: float) -> None:
    """Wait until every accepted tx was seen, at most ``grace_s``."""
    deadline = time.monotonic() + grace_s
    while time.monotonic() < deadline:
        if all(s.seen for s in sent if s.accepted):
            return
        await asyncio.sleep(0.02)


async def set_up(ctx) -> State:
    from cometbft_tpu.config import Config
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.tools import manifest as mf
    from cometbft_tpu.types import events

    m = mf.Manifest(chain_id=f"bench-load-{ctx.seed}",
                    load_tx_size=int(ctx.param("tx_bytes")))
    for i in range(int(ctx.param("validators"))):
        m.nodes[f"validator{i:02d}"] = mf.ManifestNode()
    for i in range(int(ctx.param("full_nodes"))):
        m.nodes[f"full{i:02d}"] = mf.ManifestNode(mode="full")
    homes = os.path.join(ctx.work_dir, "homes")
    shutil.rmtree(homes, ignore_errors=True)
    cfgs, relays = mf.setup(m, homes)
    if relays:
        raise RuntimeError("this driver runs no latency relays")
    defaults = Config().consensus
    nodes = {}
    for name, cfg in cfgs.items():
        # manifest.setup() shortens timeout_commit for its tests; the
        # deployment runs the program's default consensus timeouts
        cfg.consensus.timeout_commit_ns = defaults.timeout_commit_ns
        nodes[name] = Node(cfg)
    ctx.configure_tracing()     # every Node re-created the recorder
    for node in nodes.values():
        await node.start()      # warms the device path, as a node does
    ctx.lap("boot")

    names = list(nodes)
    state = State(nodes=nodes, entry=names[0], observer=names[-1],
                  sub=None, observer_task=None)
    obs = nodes[state.observer]
    state.registries = (obs.metrics_registry,)
    state.sub = obs.event_bus.subscribe(
        "bench-load", events.EVENT_QUERY_NEW_BLOCK,
        out_capacity=100_000)
    state.observer_task = asyncio.get_running_loop().create_task(
        _observe(state))
    deadline = time.monotonic() + float(ctx.param("start_timeout_s"))
    while min(n.height for n in nodes.values()) < 2:
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"net did not start: heights "
                f"{[n.height for n in nodes.values()]}")
        await asyncio.sleep(0.05)
    ctx.lap("first_blocks")

    # warm-up: the cell's own traffic, a second at a time
    rate = float(ctx.param("rate_tx_per_s"))
    gate = ctx.warmup_gate()
    while not gate.done():
        sent = await _offer(ctx, state, time.monotonic(), 1.0, rate)
        await _drain(sent, float(ctx.param("grace_s")))
        lost = [s for s in sent if not s.seen]
        if lost:
            raise RuntimeError(
                f"warm-up: {len(lost)} of {len(sent)} txs not "
                f"committed ({lost[0].error or 'accepted, never seen'})")
        gate.op_done(len(sent))
    state.setup_spans = gate.setup_spans
    ctx.lap("warmup")
    print(f"[load] warm after {gate.ops} txs; net at height "
          f"{obs.height}; buckets seen {sorted(gate.buckets)}",
          flush=True)
    return state


async def run(ctx, state: State, window) -> dict:
    rate = float(ctx.param("rate_tx_per_s"))
    sent = await _offer(ctx, state, window.start, window.seconds, rate)
    await _drain(sent, float(ctx.param("grace_s")))
    t0, t1 = int(window.start * 1e9), int(window.end * 1e9)
    blocks = [b for b in state.blocks if t0 <= b[0] < t1]
    return {
        "sent": sent,
        "lat_ms": [(s.seen - s.due) * 1e3 for s in sent if s.seen],
        "late_ms": [s.late_s * 1e3 for s in sent],
        "block_ns": [b[0] for b in blocks],
        "block_txs": [b[2] for b in blocks],
    }


def end_to_end(ctx, state: State, samples: dict) -> dict:
    return {"tx_commit_p50_ms": stats.median(samples["lat_ms"]),
            "tx_commit_p95_ms": stats.percentile(samples["lat_ms"], 95)}


async def check(ctx, state: State, samples: dict) -> Outcome:
    from cometbft_tpu.rpc.client import HTTPClient

    problems = []
    sent = samples["sent"]
    refused = [s for s in sent if not s.accepted]
    unseen = [s for s in sent if s.accepted and not s.seen]
    # cross-node invariants: identical block ids and app hashes at
    # every common height (tools/manifest.py's, on the live stores)
    nodes = state.nodes
    ref = nodes[state.entry]
    common = min(n.height for n in nodes.values())
    for h in range(1, common + 1):
        want = ref.block_store.load_block_meta(h)
        for name, n in nodes.items():
            got = n.block_store.load_block_meta(h)
            if got is None or want is None:
                continue
            if got.block_id.hash != want.block_id.hash or \
                    got.header.app_hash != want.header.app_hash:
                problems.append(f"{name}@{h}: block or app hash "
                                f"differs from {state.entry}'s")
    # acknowledged writes read back from a node that did not take them
    rng = ctx.rng("readback")
    acked = [s for s in sent if s.seen]
    readers = [name for name in nodes if name != state.entry]
    for s in rng.sample(acked, min(int(ctx.param("readback_txs")),
                                   len(acked))):
        key, _, value = s.tx.partition(b"=")
        reader = rng.choice(readers)
        q = await HTTPClient(state.endpoint(reader)).abci_query("", key)
        if base64.b64decode(q["response"]["value"] or "") != value:
            problems.append(f"tx {key!r} acknowledged by "
                            f"{state.entry} does not read back from "
                            f"{reader}")
            break
    due = schedule.count_due(1.0 / float(ctx.param("rate_tx_per_s")),
                             ctx.seconds)
    never_sent = due - len(sent)
    lat = samples["lat_ms"]
    print(f"[load] {due} txs due, {never_sent} never sent, "
          f"{len(refused)} not accepted "
          f"({refused[0].error if refused else ''}), {len(unseen)} "
          f"accepted and never seen; {len(samples['block_ns'])} blocks "
          f"in the window; {stats.beyond(lat, 95)} samples beyond p95; "
          f"latency max {max(lat) if lat else None} ms; heights "
          f"{[n.height for n in nodes.values()]}", flush=True)
    return Outcome(attempted=due,
                   failed=never_sent + len(refused) + len(unseen),
                   problems=problems)


async def tear_down(ctx, state: State) -> None:
    state.observer_task.cancel()
    await asyncio.gather(state.observer_task, return_exceptions=True)
    for n in state.nodes.values():
        try:
            await n.stop()
        except Exception:  # noqa: BLE001 — teardown goes on
            pass
    shutil.rmtree(os.path.join(ctx.work_dir, "homes"),
                  ignore_errors=True)
