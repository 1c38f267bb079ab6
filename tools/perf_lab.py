#!/usr/bin/env python
"""Perf lab: pinned microbenchmark suite with regression gating.

Every ROADMAP perf-backlog item must land "with before/after
breakdowns" — this is the harness that produces them.  The suite pins
the hot primitives the node's latency decomposes into (the same
decomposition the flight recorder attributes per height):

  * ``batch_verify_cpu_pad*``  — CPU ed25519 batch verification at the
    kernel pad-bucket batch shapes (crypto/pipeline.py BASE_BUCKETS);
  * ``merkle_root_1024``       — the block-hash primitive;
  * ``vote_sign_bytes``        — canonical vote encoding (every sign
    and every verify path builds these bytes);
  * ``signature_cache_hit``    — the verification fast path;
  * ``metrics_observe``        — histogram+labeled-counter cost per
    observation (the metrics-v2 overhead budget);
  * ``tracing_disabled_span``  — the flight-recorder disabled path
    (tier-1 separately guards < 1µs);
  * ``tracing_overhead``       — the ENABLED path: a peer-attributed
    arrival instant with the clock-anchor refresh firing every event
    (the fleet-observatory per-receive cost ceiling);
  * ``p2p_loopback_send``      — MConnection framing/scheduling cost
    per message over an in-memory pipe (no sockets, no crypto);
  * ``multiproof_build`` / ``multiproof_verify`` /
    ``proofs_verify_256`` — lightserve compact multiproofs: build and
    verify 256 of 1024 leaves vs the same leaves as 256 individual
    Proofs (the committed numbers demonstrate the >= 4x size / >= 3x
    verify win; tests/test_lightserve.py pins the claim against this
    baseline);
  * ``rpc_cache_hit``          — lightserve response-cache lookup
    (the path thousands of light clients ride per request);
  * ``statetree_commit`` / ``statetree_proof_build`` /
    ``statetree_proof_verify`` — the committed state tree behind the
    kvstore's app_hash (docs/state_tree.md): a 1k-key write+commit,
    and building/verifying a 256-key proof envelope (224 existence +
    32 non-inclusion arms under one multiproof);
  * ``bftlint_selfcheck``      — the full-package bftlint run that
    gates tier-1 (tests/test_bftlint.py), including the ISSUE 20
    whole-package call graph + effect summaries (built once per run,
    shared by every checker); a pathological checker (an accidental
    O(n^2) walk) or a diverging fixed point must not blow the tier-1
    budget, so this is pinned < ~8s via an explicit tolerance.

Modes:
  run                 run the suite, print a JSON report
  check               run + diff against the committed baseline;
                      exit 1 when any benchmark regresses beyond its
                      tolerance (per-benchmark ``tolerance`` in the
                      baseline, else ``default_tolerance``)
  rebaseline          run + rewrite the baseline file

``--fast`` runs the tier-1 subset (seconds, not minutes); the full
suite is what perf PRs attach before/after reports from.  The gate
compares per-op ``min_ms`` (the most noise-robust statistic on a
shared CI box; p50/mean ride along in reports for humans) with
generous multiplier tolerances — it catches order-of-magnitude
regressions (an accidental O(n^2), a dropped cache), not 10% drift.

Usage for a perf PR: ``python tools/perf_lab.py run > before.json``,
apply the change, run again, put both numbers in the PR description,
and ``rebaseline`` if the improvement should become the new floor.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

DEFAULT_BASELINE = os.path.join(_REPO_ROOT, "perf_baseline.json")
SCHEMA = 1
DEFAULT_TOLERANCE = 6.0

# comparison-arm statistics carried into the committed baseline so
# claim tests (e.g. tests/test_verify_pipeline.py's pipelined >=
# 1.25x monolithic / stall >= 5x gates) can check them statically
CLAIM_KEYS = ("monolithic_min_ms", "sync_stall_ms",
              "speedup_vs_monolithic", "stall_drop",
              "host_prep_ms", "kernel_execute_ms")


# ---------------------------------------------------------------------
# measurement core

def measure(fn, reps: int, inner: int = 1,
            setup=None, warmup: int = 1) -> dict:
    """Time ``fn`` (called with the value returned by ``setup``, if
    any) ``reps`` times, ``inner`` calls per rep; returns per-op
    millisecond stats.  ``warmup`` leading reps are discarded — on a
    throttled shared box the first iterations of a native-heavy loop
    run several times slower than steady state (cold caches, branch
    predictors, CPU frequency ramp)."""
    arg = setup() if setup is not None else None
    call = (lambda: fn(arg)) if setup is not None else fn
    durations = []
    for rep in range(reps + warmup):
        t0 = time.perf_counter()
        for _ in range(inner):
            call()
        dt = (time.perf_counter() - t0) / inner
        if rep >= warmup:
            durations.append(dt)
    durations.sort()
    return {
        "p50_ms": round(statistics.median(durations) * 1e3, 6),
        "min_ms": round(durations[0] * 1e3, 6),
        "mean_ms": round(statistics.fmean(durations) * 1e3, 6),
        "reps": reps,
        "inner": inner,
    }


# ---------------------------------------------------------------------
# benchmarks.  Each entry: name -> (fn(fast: bool) -> stats dict,
# in_fast_subset).  tests/test_perf_lab.py monkeypatches this table to
# prove the regression gate trips.

def _make_sigs(n: int):
    from cometbft_tpu.crypto import ed25519
    sk = ed25519.gen_priv_key()
    pk = sk.pub_key()
    msgs = [b"perf-lab-msg-%d" % i for i in range(n)]
    return [(pk, m, sk.sign(m)) for pk, m in
            ((pk, m) for m in msgs)]


def bench_batch_verify_cpu(batch: int, reps: int):
    from cometbft_tpu.crypto import ed25519

    def setup():
        return _make_sigs(batch)

    def run(items):
        bv = ed25519.CpuBatchVerifier()
        for pk, m, s in items:
            bv.add(pk, m, s)
        ok, _ = bv.verify()
        if not ok:
            raise RuntimeError("benchmark signatures failed to verify")

    stats = measure(run, reps=reps, setup=setup, warmup=4)
    stats["batch"] = batch
    return stats


def bench_batch_verify_pad64(fast: bool):
    return bench_batch_verify_cpu(batch=64, reps=4 if fast else 6)


def bench_batch_verify_pad1024(fast: bool):
    # 256 signatures dispatch at the 1024 pad bucket
    return bench_batch_verify_cpu(batch=256, reps=3)


def bench_merkle_root(fast: bool):
    from cometbft_tpu.crypto.merkle import hash_from_byte_slices
    leaves = [(b"%08d" % i) * 32 for i in range(1024)]
    return measure(lambda: hash_from_byte_slices(leaves),
                   reps=10 if fast else 30, inner=3)


def bench_vote_sign_bytes(fast: bool):
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp
    bid = BlockID(hash=b"\xab" * 32,
                  part_set_header=PartSetHeader(total=1,
                                                hash=b"\xcd" * 32))
    ts = Timestamp(1700000000, 123456789)
    return measure(
        lambda: canonical.vote_sign_bytes(
            "perf-lab-chain", canonical.PRECOMMIT_TYPE, 12345, 2,
            bid, ts),
        reps=5 if fast else 15, inner=500)


def bench_signature_cache_hit(fast: bool):
    from cometbft_tpu.types.signature_cache import (
        SignatureCache, SignatureCacheValue,
    )
    cache = SignatureCache(capacity=4096)
    sigs = [os.urandom(64) for _ in range(512)]
    for s in sigs:
        cache.add(s, SignatureCacheValue(s[:20], s[:32]))

    def run():
        for s in sigs:
            if cache.get(s) is None:
                raise RuntimeError("expected a cache hit")

    stats = measure(run, reps=5 if fast else 15, inner=4)
    # per-op: each run() call does len(sigs) lookups
    for k in ("p50_ms", "min_ms", "mean_ms"):
        stats[k] = round(stats[k] / len(sigs), 6)
    return stats


def bench_metrics_observe(fast: bool):
    from cometbft_tpu.libs.metrics import Registry
    reg = Registry()
    hist = reg.histogram("perf", "lat", "perf-lab latency histogram",
                         labels=("backend",))
    ctr = reg.counter("perf", "ops", "perf-lab labeled counter",
                      labels=("kind",))

    def run():
        hist.with_labels("cpu").observe(0.0123)
        ctr.with_labels("bench").add()

    return measure(run, reps=5 if fast else 15, inner=5000)


def bench_tracing_disabled_span(fast: bool):
    from cometbft_tpu.libs import tracing
    old = tracing.set_recorder(tracing.Recorder(enabled=False))
    try:
        def run():
            with tracing.span(tracing.CRYPTO, "bench"):
                pass
        return measure(run, reps=5 if fast else 15, inner=5000)
    finally:
        tracing.set_recorder(old)


def bench_tracing_overhead(fast: bool):
    """Enabled-path flight-recorder cost: one peer-attributed arrival
    instant (the fleet-observatory hot path on every p2p/consensus
    receive) with the passive clock-anchor refresh armed to fire on
    every event — the worst case including the wall-clock sample."""
    from cometbft_tpu.libs import tracing
    rec = tracing.Recorder(buffer_size=4096, anchor_interval_s=1e-9)
    old = tracing.set_recorder(rec)
    try:
        def run():
            tracing.instant(tracing.P2P, "recv", height=7,
                            peer="perfpeer1234", chan=32, bytes=512)
        return measure(run, reps=5 if fast else 15, inner=5000)
    finally:
        tracing.set_recorder(old)


def bench_p2p_loopback_send(fast: bool):
    import asyncio

    from cometbft_tpu.p2p.conn import ChannelDescriptor, MConnection

    n_msgs = 100 if fast else 400
    payload = b"\x5a" * 1024

    class _Pipe:
        def __init__(self):
            self._q: asyncio.Queue = asyncio.Queue()
            self.peer: "_Pipe" = None          # type: ignore

        async def write_msg(self, data: bytes) -> None:
            await self.peer._q.put(bytes(data))

        async def read_msg(self) -> bytes:
            return await self._q.get()

        def close(self) -> None:
            pass

    async def run_once() -> float:
        a, b = _Pipe(), _Pipe()
        a.peer, b.peer = b, a
        got = asyncio.Event()
        count = 0

        async def on_recv(chan, msg):
            nonlocal count
            count += 1
            if count >= n_msgs:
                got.set()

        async def nop_recv(chan, msg):
            pass

        descs = [ChannelDescriptor(id=0x30,
                                   send_queue_capacity=n_msgs + 8)]
        # rate 0 = unlimited: measure framing + scheduling, not the
        # token bucket
        tx = MConnection(a, descs, nop_recv, lambda e: None,
                         send_rate=0, recv_rate=0, peer_id="tx")
        rx = MConnection(b, descs, on_recv, lambda e: None,
                         send_rate=0, recv_rate=0, peer_id="rx")
        tx.start()
        rx.start()
        try:
            t0 = time.perf_counter()
            for _ in range(n_msgs):
                await tx.send_blocking(0x30, payload)
            await asyncio.wait_for(got.wait(), 30)
            return (time.perf_counter() - t0) / n_msgs
        finally:
            tx.close()
            rx.close()

    reps = 3 if fast else 5
    durations = sorted(asyncio.run(run_once())
                       for _ in range(reps + 1))[: reps]
    return {
        "p50_ms": round(statistics.median(durations) * 1e3, 6),
        "min_ms": round(durations[0] * 1e3, 6),
        "mean_ms": round(statistics.fmean(durations) * 1e3, 6),
        "reps": reps,
        "inner": n_msgs,
    }


# lightserve: multiproof build/verify and the RPC response-cache hit
# path (docs/light_proofs.md).  Fixed geometry — 1024-leaf tree, 256
# seeded-random keys — so the committed numbers demonstrate the
# compactness claims: tests/test_lightserve.py statically checks the
# baseline shows multiproof_verify >= 3x faster than
# proofs_verify_256, and the tight tolerance on multiproof_verify
# makes a regression that would void the claim fail `check`.

_MULTIPROOF_LEAVES = 1024
_MULTIPROOF_KEYS = 256


def _multiproof_fixture():
    import random
    items = [b"perf-leaf-%05d" % i for i in range(_MULTIPROOF_LEAVES)]
    sel = sorted(random.Random(7).sample(
        range(_MULTIPROOF_LEAVES), _MULTIPROOF_KEYS))
    return items, sel


def bench_multiproof_build(fast: bool):
    from cometbft_tpu.crypto import merkle
    items, sel = _multiproof_fixture()
    stats = measure(lambda: merkle.multiproof_from_byte_slices(
        items, sel), reps=5 if fast else 15, inner=3)
    # 1/16-key builds ride along for the scaling picture (ungated)
    for k in (1, 16):
        sub = measure(lambda: merkle.multiproof_from_byte_slices(
            items, sel[:k]), reps=3, inner=3)
        stats[f"keys{k}_min_ms"] = sub["min_ms"]
    stats["keys"] = _MULTIPROOF_KEYS
    return stats


def bench_multiproof_verify(fast: bool):
    import json as _json

    from cometbft_tpu.crypto import merkle
    items, sel = _multiproof_fixture()
    root, mp = merkle.multiproof_from_byte_slices(items, sel)
    leaves = [items[i] for i in sel]
    stats = measure(lambda: mp.verify(root, leaves),
                    reps=5 if fast else 15, inner=3, warmup=2)
    # serialized-size comparison vs 256 individual Proofs (the
    # deterministic half of the compactness claim; also asserted in
    # tests/test_lightserve.py)
    _, proofs = merkle.proofs_from_byte_slices(items)
    stats["bytes"] = len(_json.dumps(mp.to_dict()))
    stats["per_key_bytes"] = sum(
        len(_json.dumps(proofs[i].to_dict())) for i in sel)
    stats["size_ratio"] = round(
        stats["per_key_bytes"] / stats["bytes"], 2)
    stats["keys"] = _MULTIPROOF_KEYS
    return stats


def bench_proofs_verify_256(fast: bool):
    """The per-key comparison: verifying the same 256 leaves with 256
    individual Proof objects."""
    from cometbft_tpu.crypto import merkle
    items, sel = _multiproof_fixture()
    root, proofs = merkle.proofs_from_byte_slices(items)

    def run():
        for i in sel:
            proofs[i].verify(root, items[i])

    stats = measure(run, reps=5 if fast else 15, inner=3, warmup=2)
    stats["keys"] = _MULTIPROOF_KEYS
    return stats


def bench_rpc_cache_hit(fast: bool):
    from cometbft_tpu.lightserve.cache import ResponseCache
    cache = ResponseCache(max_bytes=1 << 24)
    payload = {"block": {"data": "x" * 512}}
    for h in range(1, 513):
        cache.put("block", h, (), payload, latest_height=1024)

    def run():
        for h in range(1, 513):
            if cache.get("block", h) is None:
                raise RuntimeError("expected a cache hit")

    stats = measure(run, reps=5 if fast else 15, inner=4)
    # per-op: each run() does 512 lookups
    for k in ("p50_ms", "min_ms", "mean_ms"):
        stats[k] = round(stats[k] / 512, 6)
    return stats


# statetree: the committed state tree that IS the kvstore's app_hash
# (docs/state_tree.md).  Pinned geometry: 1024 committed keys, and a
# 256-key request batch of which 32 are absent — so the verify number
# includes the non-inclusion adjacency arms, not just membership.

_STATETREE_KEYS = 1024
_STATETREE_REQ_PRESENT = 224
_STATETREE_REQ_ABSENT = 32


def _statetree_fixture():
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.statetree import StateTree
    t = StateTree(MemDB())
    for i in range(_STATETREE_KEYS):
        t.set(b"st-key-%05d" % (2 * i), b"st-val-%d" % i)
    root = t.commit(1)
    # even keys exist; odd keys fall in the gaps between them
    req = [b"st-key-%05d" % (2 * i)
           for i in range(_STATETREE_REQ_PRESENT)] + \
          [b"st-key-%05d" % (2 * i + 1)
           for i in range(_STATETREE_REQ_ABSENT)]
    return t, req, root


def bench_statetree_commit(fast: bool):
    """1k-key write + version commit — the per-block ceiling for a
    block that rewrites every key of a 1k-key app (the ISSUE 17
    gate shape)."""
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.statetree import StateTree

    def setup():
        t = StateTree(MemDB())
        for i in range(_STATETREE_KEYS):
            t.set(b"st-key-%05d" % (2 * i), b"v0")
        t.commit(1)
        return {"tree": t, "version": 1}

    def run(state):
        state["version"] += 1
        v = state["version"]
        t = state["tree"]
        for i in range(_STATETREE_KEYS):
            t.set(b"st-key-%05d" % (2 * i), b"v%d" % v)
        t.commit(v)

    stats = measure(run, reps=5 if fast else 15, setup=setup,
                    warmup=1)
    stats["keys"] = _STATETREE_KEYS
    return stats


def bench_statetree_proof_build(fast: bool):
    t, req, _ = _statetree_fixture()
    stats = measure(lambda: t.prove(req, 1),
                    reps=5 if fast else 15, inner=3, warmup=1)
    stats["keys"] = len(req)
    stats["absent_keys"] = _STATETREE_REQ_ABSENT
    return stats


def bench_statetree_proof_verify(fast: bool):
    from cometbft_tpu.statetree import verify_proof_envelope
    t, req, root = _statetree_fixture()
    env = t.prove(req, 1)
    present = [(b"st-key-%05d" % (2 * i), b"st-val-%d" % i)
               for i in range(_STATETREE_REQ_PRESENT)]
    absent = req[_STATETREE_REQ_PRESENT:]
    stats = measure(
        lambda: verify_proof_envelope(env, present=present,
                                      absent=absent,
                                      expected_root=root),
        reps=5 if fast else 15, inner=3, warmup=2)
    stats["keys"] = len(req)
    stats["absent_keys"] = _STATETREE_REQ_ABSENT
    return stats


def bench_mempool_incremental_recheck(fast: bool):
    """ISSUE 10: a 512-tx pool absorbing a commit that touched 16
    keys.  Gates the incremental ``update()`` pass (remove + slice +
    batched recheck); the full-pool recheck of the same commit rides
    along as ``full_min_ms`` — the before/after of the 10 tx/s wall
    (QA_r05's collapse was recheck-bound: every commit re-ran CheckTx
    for thousands of pooled txs)."""
    import asyncio

    from cometbft_tpu.abci import types as abci_t
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import (
        DEFAULT_LANES, KVStoreApplication, tx_recheck_keys,
    )
    from cometbft_tpu.config import MempoolConfig
    from cometbft_tpu.mempool import CListMempool

    n_pool, n_touch = 512, 16

    async def run_once(incremental: bool) -> float:
        app = KVStoreApplication()
        conns = AppConns(app)
        mp = CListMempool(
            MempoolConfig(size=2 * n_pool,
                          recheck_incremental=incremental),
            conns.mempool, lanes=DEFAULT_LANES,
            default_lane="default")
        for i in range(n_pool):
            await mp.check_tx(b"pk%04dx=v" % i)
        committed = [b"pk%04dx=z" % i for i in range(n_touch)]
        results = [abci_t.ExecTxResult(
            code=abci_t.CODE_TYPE_OK,
            recheck_keys=tx_recheck_keys(t)) for t in committed]
        t0 = time.perf_counter()
        await mp.update(1, committed, results)
        return time.perf_counter() - t0

    reps = 3 if fast else 6
    inc = sorted(asyncio.run(run_once(True))
                 for _ in range(reps + 1))[:reps]
    full = sorted(asyncio.run(run_once(False))
                  for _ in range(max(2, reps - 1) + 1))[
                      :max(2, reps - 1)]
    return {
        "p50_ms": round(statistics.median(inc) * 1e3, 6),
        "min_ms": round(inc[0] * 1e3, 6),
        "mean_ms": round(statistics.fmean(inc) * 1e3, 6),
        "full_min_ms": round(full[0] * 1e3, 6),
        "pool": n_pool,
        "touched": n_touch,
        "reps": reps,
        "inner": 1,
    }


def bench_height_pipeline_overlap(fast: bool):
    """ISSUE 10: wall-clock for a wired 2-validator in-process net to
    commit 4 heights with a 10 ms-FinalizeBlock app and a loaded
    mempool.  Gates the pipelined path (commit/propose overlap +
    incremental recheck); the serial path (pipeline_commit=False,
    full recheck) rides along as ``serial_min_ms``."""
    import asyncio

    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import (
        DEFAULT_LANES, KVStoreApplication,
    )
    from cometbft_tpu.config import MempoolConfig
    from cometbft_tpu.config import test_config as _test_config
    from cometbft_tpu.consensus.messages import (
        BlockPartMessage, ProposalMessage, VoteMessage,
    )
    from cometbft_tpu.consensus.state import ConsensusState
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.db import MemDB
    from cometbft_tpu.mempool import CListMempool
    from cometbft_tpu.state import make_genesis_state
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.store import Store
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.types.genesis import (
        GenesisDoc, GenesisValidator,
    )
    from cometbft_tpu.types.priv_validator import new_mock_pv
    from cometbft_tpu.types.timestamp import Timestamp

    gossip = (ProposalMessage, BlockPartMessage, VoteMessage)
    heights = 4

    async def run_once(pipeline: bool) -> float:
        crypto_batch.set_backend("cpu")
        pvs = [new_mock_pv() for _ in range(2)]
        doc = GenesisDoc(
            chain_id="perf-pipeline",
            genesis_time=Timestamp(1700000000, 0),
            validators=[GenesisValidator(
                address=b"", pub_key=pv.get_pub_key(), power=10)
                for pv in pvs])
        # small blocks so the preloaded pool stays occupied across
        # every height — the serial path then pays its full-pool
        # recheck inside the commit critical path each block, which
        # is exactly the cost the pipeline + incremental recheck
        # take off it
        doc.consensus_params.block.max_bytes = 2048
        doc.consensus_params.evidence.max_bytes = 1024
        nodes, pools = [], []
        for pv in pvs:
            state = make_genesis_state(doc)
            app = KVStoreApplication()
            app.abci_delays = {"finalize_block": 0.01}
            conns = AppConns(app)
            ss, bs = Store(MemDB()), BlockStore(MemDB())
            ss.save(state)
            mp = CListMempool(
                MempoolConfig(size=4096,
                              recheck_incremental=pipeline),
                conns.mempool, lanes=DEFAULT_LANES,
                default_lane="default")
            ex = BlockExecutor(ss, conns.consensus, mempool=mp,
                               block_store=bs)
            cfg = _test_config().consensus
            cfg.pipeline_commit = pipeline
            nodes.append(ConsensusState(cfg, state, ex, bs,
                                        priv_validator=pv))
            pools.append(mp)
        for i, cs in enumerate(nodes):
            def mk(idx):
                def hook(msg):
                    if isinstance(msg, gossip):
                        for j, other in enumerate(nodes):
                            if j != idx:
                                other.send_peer(msg, f"n{idx}")
                return hook
            cs.broadcast_hooks.append(mk(i))
        for mp in pools:
            for i in range(768):
                await mp.check_tx(b"ld%04dx=v" % i)
        t0 = time.perf_counter()
        for cs in nodes:
            await cs.start()
        try:
            while min(cs.block_store.height for cs in nodes) \
                    < heights:
                if time.perf_counter() - t0 > 60:
                    raise RuntimeError("pipeline bench net stuck")
                await asyncio.sleep(0.005)
            return time.perf_counter() - t0
        finally:
            for cs in nodes:
                await cs.stop()
            crypto_batch.set_backend("auto")

    reps = 2 if fast else 4
    piped = sorted(asyncio.run(run_once(True))
                   for _ in range(reps + 1))[:reps]
    serial = sorted(asyncio.run(run_once(False))
                    for _ in range(2 + 1))[:2]
    return {
        "p50_ms": round(statistics.median(piped) * 1e3, 6),
        "min_ms": round(piped[0] * 1e3, 6),
        "mean_ms": round(statistics.fmean(piped) * 1e3, 6),
        "serial_min_ms": round(serial[0] * 1e3, 6),
        "heights": heights,
        "reps": reps,
        "inner": 1,
    }


def bench_gossip_reconcile_roundtrip(fast: bool):
    """ISSUE 12: one reconciliation round at a 5k-tx pool — build the
    short-id summary for a 256-tx advert batch, encode + decode the
    TxHave, and diff it against a receiver pool missing 32 of the
    txs (the receiver-side cost every advert pays).  The short-id
    hashing of the full 5k pool rides along as ``pool_hash_min_ms``
    (the per-salt map build, amortized across adverts)."""
    from cometbft_tpu.mempool.messages import (
        TxHaveMessage, decode_mempool, encode_mempool, short_ids,
    )
    from cometbft_tpu.types.tx import tx_key

    n_pool, n_advert, n_missing = 5000, 256, 32
    keys = [tx_key(b"sum%05d=" % i + b"v" * 248)
            for i in range(n_pool)]
    salt = b"perf-salt"
    # receiver's short map: the pool minus the missing txs
    have = dict(zip(short_ids(salt, keys[n_missing:]),
                    keys[n_missing:]))
    advert_keys = keys[:n_advert]

    def run():
        sids = short_ids(salt, advert_keys)
        raw = encode_mempool(TxHaveMessage(salt=salt, ids=sids))
        msg = decode_mempool(raw)
        wants = [sid for sid in msg.ids if sid not in have]
        if len(wants) != n_missing:
            raise RuntimeError(f"diff found {len(wants)} missing")

    stats = measure(run, reps=5 if fast else 15, inner=5, warmup=2)
    sub = measure(lambda: short_ids(salt, keys), reps=3, inner=1,
                  warmup=1)
    stats["pool_hash_min_ms"] = sub["min_ms"]
    stats["pool"] = n_pool
    stats["advert"] = n_advert
    return stats


def bench_compact_block_reconstruct(fast: bool):
    """ISSUE 12: rebuild a 900-tx / 256 KiB proposal from the mempool
    given its compact form (skeleton + tx hashes) — resolve, splice,
    re-encode, re-split, verify the part-set header.  The full-part
    path this replaces shipped ~233 KB per peer; the compact form is
    ~29 KB (``compact_bytes``/``full_bytes`` ride along)."""
    from cometbft_tpu.consensus.messages import (
        make_compact_block, reconstruct_block_bytes,
    )
    from cometbft_tpu.types.block import Block, Data, Header
    from cometbft_tpu.types.part_set import PartSet
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.tx import tx_key

    txs = [(b"cb%04d=" % i) + b"v" * 249 for i in range(900)]
    block = Block(header=Header(chain_id="perf", height=7,
                                time=Timestamp(1700000000, 0),
                                proposer_address=b"p" * 20),
                  data=Data(txs=list(txs)))
    block.fill_header()
    parts = block.make_part_set()
    msg = make_compact_block(7, 0, block, parts.header())
    pool = {tx_key(tx): tx for tx in txs}

    def run():
        resolved = [pool[h] for h in msg.tx_hashes]
        rebuilt = PartSet.from_data(
            reconstruct_block_bytes(msg.skeleton, resolved))
        if rebuilt.header() != parts.header():
            raise RuntimeError("part-set header mismatch")

    stats = measure(run, reps=5 if fast else 15, inner=2, warmup=2)
    stats["txs"] = len(txs)
    stats["compact_bytes"] = len(msg.skeleton) + \
        32 * len(msg.tx_hashes)
    stats["full_bytes"] = parts.byte_size
    return stats


def bench_bftlint_selfcheck(fast: bool):
    from tools.bftlint import lint_paths
    from tools.bftlint.checkers import ALL_CHECKERS
    pkg = os.path.join(_REPO_ROOT, "cometbft_tpu")

    def run():
        result = lint_paths([pkg], ALL_CHECKERS)
        if result.parse_errors:
            raise RuntimeError(
                f"bftlint parse errors: {result.parse_errors}")

    return measure(run, reps=2 if fast else 4, warmup=1)


def _pipeline_workload(n: int = 10000):
    """n (pub, msg, sig) triples with DISTINCT keys — the shape of a
    10k-validator commit burst (the seeded generator chip_smoke.py
    and bench.py use)."""
    from cometbft_tpu.tools import benchmarks
    return benchmarks.seeded_sig_items(n, seed=0)


def _cpu_bv(items, monolithic: bool):
    from cometbft_tpu.crypto import ed25519
    bv = ed25519.CpuBatchVerifier(monolithic=monolithic)
    for pub, msg, sig in items:
        bv.add(ed25519.Ed25519PubKey(pub), msg, sig)
    return bv


def bench_ed25519_pipelined_dispatch(fast: bool):
    """ISSUE 14 tentpole gate: the tiled+overlapped verification
    pipeline (native tile kernel: packed blobs, staged pubkey
    decompression, signed-digit MSM with cached-form bucket adds,
    fe_sqr decompression — KERNEL_NOTES round 6) at the 10k-signature
    commit-burst shape, vs the pre-pipeline monolithic dispatch
    riding along as ``monolithic_min_ms``.  The committed baseline
    pins pipelined >= 1.25x faster (tests/test_verify_pipeline.py
    statically checks the claim); the host_prep/kernel_execute
    histogram split rides along as evidence the phases are
    separately instrumented (``host_prep_ms``/``kernel_execute_ms``).
    """
    from cometbft_tpu.crypto import pipeline as cpipe
    from cometbft_tpu.libs import metrics as libmetrics

    items = _pipeline_workload()
    piped = _cpu_bv(items, monolithic=False)
    mono = _cpu_bv(items, monolithic=True)

    hist = cpipe.dispatch_histogram()
    tile = str(cpipe.MSM_TILE)
    prep = hist.with_labels("host_prep", "native", tile, "1")
    execu = hist.with_labels("kernel_execute", "native", tile, "1")
    prep0, exec0 = prep._sum, execu._sum

    def run_piped():
        ok, _ = piped.verify()
        if not ok:
            raise RuntimeError("workload must verify")

    def run_mono():
        ok, _ = mono.verify()
        if not ok:
            raise RuntimeError("workload must verify")

    stats = measure(run_piped, reps=3 if fast else 5, warmup=1)
    mono_stats = measure(run_mono, reps=2 if fast else 4, warmup=1)
    stats["monolithic_min_ms"] = mono_stats["min_ms"]
    stats["speedup_vs_monolithic"] = round(
        mono_stats["min_ms"] / stats["min_ms"], 3)
    stats["host_prep_ms"] = round((prep._sum - prep0) * 1e3, 3)
    stats["kernel_execute_ms"] = round((execu._sum - exec0) * 1e3, 3)
    stats["sigs"] = len(items)
    return stats


def bench_verify_event_loop_stall(fast: bool):
    """ISSUE 14 gate: maximum event-loop stall while a 10k-signature
    burst verifies.  The async arm awaits ``verify_async()`` (the
    whole tiled pipeline on the verification staging worker;
    GIL-free kernels), the sync arm calls ``verify()`` on the loop —
    the pre-pipeline behavior, riding along as ``sync_stall_ms``.
    A ticker coroutine measures the largest gap between 1 ms ticks;
    the committed baseline pins the async stall >= 5x smaller
    (tests/test_verify_pipeline.py checks the claim statically)."""
    import asyncio

    items = _pipeline_workload()

    async def run_arm(use_async: bool) -> float:
        bv = _cpu_bv(items, monolithic=not use_async)
        max_gap = 0.0
        done = asyncio.Event()

        async def ticker():
            nonlocal max_gap
            last = time.perf_counter()
            while not done.is_set():
                await asyncio.sleep(0.001)
                now = time.perf_counter()
                if now - last > max_gap:
                    max_gap = now - last
                last = now

        t = asyncio.ensure_future(ticker())
        await asyncio.sleep(0.05)       # ticker cadence settles
        max_gap = 0.0
        if use_async:
            ok, _ = await bv.verify_async()
        else:
            ok, _ = bv.verify()
        if not ok:
            raise RuntimeError("workload must verify")
        done.set()
        await t
        return max_gap

    reps = 3 if fast else 5
    asyncio.run(run_arm(True))          # warm (kernel, cache, worker)
    gaps = sorted(asyncio.run(run_arm(True)) for _ in range(reps))
    sync_gaps = sorted(asyncio.run(run_arm(False))
                       for _ in range(2))
    return {
        "p50_ms": round(gaps[len(gaps) // 2] * 1e3, 6),
        "min_ms": round(gaps[0] * 1e3, 6),
        "mean_ms": round(sum(gaps) / len(gaps) * 1e3, 6),
        "sync_stall_ms": round(sync_gaps[0] * 1e3, 6),
        "stall_drop": round(sync_gaps[0] / gaps[0], 2)
        if gaps[0] > 0 else 0.0,
        "sigs": len(items),
        "reps": reps,
        "inner": 1,
    }


# name -> (fn, in_fast_subset)
def _agg_commit_fixture(n: int):
    """An n-validator BLS valset + verified-shape aggregate commit.

    Tiny secret scalars keep fixture construction fast at 10k
    validators; verification cost is independent of scalar size (the
    pairing and the G1 point sum see full-width field elements)."""
    from cometbft_tpu.crypto import bls12381 as bls
    from cometbft_tpu.crypto import _bls12381_math as m
    from cometbft_tpu.libs.bits import BitArray
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.commit import AggregateCommit
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp
    from cometbft_tpu.types.validator_set import (
        Validator, ValidatorSet,
    )

    bid = BlockID(hash=b"\x0b" * 32,
                  part_set_header=PartSetHeader(1, b"\x0c" * 32))
    height = 9
    sks = list(range(2, n + 2))
    vals_list = []
    pk_by_addr = {}
    for sk in sks:
        pk = bls.Bls12381PubKey._from_point_unchecked(
            m.pt_mul(m.G1_OPS, m.G1_GEN, sk))
        vals_list.append(Validator(address=pk.address(), pub_key=pk,
                                   voting_power=10))
        pk_by_addr[pk.address()] = sk
    vals = ValidatorSet(vals_list)
    sb = canonical.vote_sign_bytes(
        "perf-chain", canonical.PRECOMMIT_TYPE, height, 0, bid,
        Timestamp.zero())
    # aggregate signature = [sum sk]H(m): one G2 mul instead of n
    # signs + n adds — same point the real aggregation produces
    agg_sk = sum(pk_by_addr[v.address] for v in vals.validators) \
        % m.R_ORDER
    hm = m.hash_to_g2(sb, bls.DST)
    agg_sig = m.g2_compress(m.pt_mul(m.G2_OPS, hm, agg_sk))
    signers = BitArray(n)
    for i in range(n):
        signers.set_index(i, True)
    commit = AggregateCommit(height=height, round=0, block_id=bid,
                             signers=signers, signature=agg_sig)
    vals.hash()   # memoize: the valset hash is not what we measure
    return vals, commit, bid, height


def bench_bls_aggregate_commit_verify(n: int, reps: int,
                                      warm: bool):
    """O(1) aggregate-commit verification (docs/aggregate_commits.md):
    cold pays the G1 pubkey point-sum + one pairing; warm hits the
    aggregate-pubkey cache and pays the pairing alone.  The ISSUE 13
    acceptance gate lives at the 10k shape."""
    from cometbft_tpu.crypto import bls12381 as bls
    from cometbft_tpu.types import validation

    def setup():
        return _agg_commit_fixture(n)

    def run(fixture):
        vals, commit, bid, height = fixture
        if not warm:
            bls._AGG_PK_CACHE = None     # force the G1 point-sum
        validation.verify_commit_light("perf-chain", vals, bid,
                                       height, commit)

    if warm:
        fixture = _agg_commit_fixture(n)
        run(fixture)                     # prime the pubkey cache
        stats = measure(lambda _: run(fixture), reps=reps,
                        setup=lambda: None, warmup=1)
    else:
        stats = measure(run, reps=reps, setup=setup, warmup=1)
    stats["validators"] = n
    stats["warm_pubkey_cache"] = warm
    return stats


def bench_bls_agg_verify_100_cold(fast: bool):
    return bench_bls_aggregate_commit_verify(
        100, reps=4 if fast else 6, warm=False)


def bench_bls_agg_verify_1k_cold(fast: bool):
    return bench_bls_aggregate_commit_verify(1000, reps=4, warm=False)


def bench_bls_agg_verify_10k_cold(fast: bool):
    return bench_bls_aggregate_commit_verify(10000, reps=4,
                                             warm=False)


def bench_bls_agg_verify_10k_warm(fast: bool):
    return bench_bls_aggregate_commit_verify(10000, reps=4, warm=True)


BENCHMARKS = {
    "batch_verify_cpu_pad64": (bench_batch_verify_pad64, True),
    "batch_verify_cpu_pad1024": (bench_batch_verify_pad1024, False),
    "merkle_root_1024": (bench_merkle_root, True),
    "vote_sign_bytes": (bench_vote_sign_bytes, True),
    "signature_cache_hit": (bench_signature_cache_hit, True),
    "metrics_observe": (bench_metrics_observe, True),
    "tracing_disabled_span": (bench_tracing_disabled_span, True),
    "tracing_overhead": (bench_tracing_overhead, True),
    "p2p_loopback_send": (bench_p2p_loopback_send, True),
    "multiproof_build": (bench_multiproof_build, True),
    "multiproof_verify": (bench_multiproof_verify, True),
    "proofs_verify_256": (bench_proofs_verify_256, True),
    "rpc_cache_hit": (bench_rpc_cache_hit, True),
    "statetree_commit": (bench_statetree_commit, True),
    "statetree_proof_build": (bench_statetree_proof_build, True),
    "statetree_proof_verify": (bench_statetree_proof_verify, True),
    "mempool_incremental_recheck": (
        bench_mempool_incremental_recheck, True),
    "height_pipeline_overlap": (bench_height_pipeline_overlap, True),
    "gossip_reconcile_roundtrip": (
        bench_gossip_reconcile_roundtrip, True),
    "compact_block_reconstruct": (
        bench_compact_block_reconstruct, True),
    "bftlint_selfcheck": (bench_bftlint_selfcheck, True),
    "ed25519_pipelined_dispatch": (
        bench_ed25519_pipelined_dispatch, True),
    "verify_event_loop_stall": (
        bench_verify_event_loop_stall, True),
    "bls_aggregate_commit_verify_100_cold": (
        bench_bls_agg_verify_100_cold, True),
    "bls_aggregate_commit_verify_1k_cold": (
        bench_bls_agg_verify_1k_cold, False),
    "bls_aggregate_commit_verify_10k_cold": (
        bench_bls_agg_verify_10k_cold, False),
    "bls_aggregate_commit_verify_10k_warm": (
        bench_bls_agg_verify_10k_warm, False),
}


# ---------------------------------------------------------------------
# modes

def run_suite(fast: bool = False, only=None) -> dict:
    results = {}
    for name, (fn, in_fast) in BENCHMARKS.items():
        if only and name not in only:
            continue
        if fast and not in_fast:
            continue
        results[name] = fn(fast)
    return {
        "schema": SCHEMA,
        "mode": "fast" if fast else "full",
        **({"only": sorted(only)} if only else {}),
        "env": {
            "python": sys.version.split()[0],
            "platform": sys.platform,
            "cpus": os.cpu_count(),
        },
        "benchmarks": results,
    }


def load_baseline(path: str) -> dict:
    with open(path) as f:
        base = json.load(f)
    if base.get("schema") != SCHEMA:
        raise ValueError(
            f"baseline schema {base.get('schema')} != {SCHEMA}; "
            f"rerun `perf_lab.py rebaseline`")
    return base


def check_report(report: dict, baseline: dict) -> tuple[bool, list]:
    """Diff a run report against the baseline.  Returns (ok, lines).
    A benchmark regresses when its current min_ms exceeds the
    baseline min_ms times its tolerance; a benchmark in the baseline
    but missing from the (non-fast-filtered) report fails too."""
    default_tol = float(baseline.get("default_tolerance",
                                     DEFAULT_TOLERANCE))
    base_benches = baseline.get("benchmarks", {})
    ok = True
    lines = []
    for name, stats in sorted(report["benchmarks"].items()):
        base = base_benches.get(name)
        if base is None:
            lines.append(f"NEW   {name}: min {stats['min_ms']}ms "
                         f"(not in baseline — rebaseline to gate it)")
            continue
        tol = float(base.get("tolerance", default_tol))
        limit = base["min_ms"] * tol
        cur = stats["min_ms"]
        ratio = cur / base["min_ms"] if base["min_ms"] > 0 else 0.0
        verdict = "ok   " if cur <= limit else "REGRESSED"
        if cur > limit:
            ok = False
        lines.append(
            f"{verdict} {name}: min {cur}ms vs baseline "
            f"{base['min_ms']}ms (x{ratio:.2f}, limit x{tol:g})")
    wanted = {n for n, (fn, in_fast) in BENCHMARKS.items()
              if report["mode"] == "full" or in_fast}
    if report.get("only"):
        # an explicit --only subset only gates what it ran
        wanted &= set(report["only"])
    for name in sorted(set(base_benches) & wanted
                       - set(report["benchmarks"])):
        ok = False
        lines.append(f"MISSING {name}: in baseline but did not run")
    return ok, lines


def rebaseline(report: dict, path: str,
               default_tolerance: float = DEFAULT_TOLERANCE) -> dict:
    prev_tols = {}
    if os.path.exists(path):
        try:
            prev = load_baseline(path)
            prev_tols = {n: b["tolerance"]
                         for n, b in prev.get("benchmarks", {}).items()
                         if "tolerance" in b}
        except Exception:
            pass
    base = {
        "schema": SCHEMA,
        "default_tolerance": default_tolerance,
        "generated_by": "tools/perf_lab.py rebaseline",
        "env": report["env"],
        "benchmarks": {
            name: {"min_ms": stats["min_ms"],
                   "p50_ms": stats["p50_ms"],
                   **{k: stats[k] for k in CLAIM_KEYS if k in stats},
                   **({"tolerance": prev_tols[name]}
                      if name in prev_tols else {})}
            for name, stats in sorted(report["benchmarks"].items())
        },
    }
    with open(path, "w") as f:
        json.dump(base, f, indent=2, sort_keys=True)
        f.write("\n")
    return base


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("run", "check", "rebaseline"),
                    nargs="?", default="run")
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 subset (seconds, not minutes)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--out", default="",
                    help="also write the JSON report here")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark subset")
    args = ap.parse_args(argv)

    only = {s.strip() for s in args.only.split(",") if s.strip()} \
        or None
    report = run_suite(fast=args.fast, only=only)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")

    if args.mode == "run":
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0
    if args.mode == "rebaseline":
        base = rebaseline(report, args.baseline)
        print(f"baseline written: {args.baseline} "
              f"({len(base['benchmarks'])} benchmarks)")
        return 0
    # check
    baseline = load_baseline(args.baseline)
    ok, lines = check_report(report, baseline)
    print("\n".join(lines))
    print("PASS" if ok else "FAIL: perf regression beyond tolerance")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
