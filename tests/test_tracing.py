"""Flight recorder (libs/tracing.py): rings, dumps, report, RPC, and
the live-testnet per-height timeline acceptance check.

Covers:
  * span/instant recording, strict monotonic ordering, height and
    category filters, ring-buffer bounding;
  * the disabled path as a true no-op (<1µs per span call — the
    always-on budget);
  * causality: id/parent/tid on every event, parent and height
    inherited through nested spans and asyncio.to_thread but not from
    a parent that has closed; the span tree of one block-synced
    height; per-tile kernel_execute spans of a tiled dispatch;
  * crash dumps: supervisor give-up and the nemesis safety-assertion
    failure leave parseable JSON records (the nemesis one names the
    conflicting-commit heights), rendered by tools/trace_report.py;
  * the interpreter's own time (category ``runtime``): gc_pause spans
    and the collector's totals, gc_us on a flagged span, what
    the hook and the flag cost;
  * the /trace RPC handler;
  * the bounded signature cache (LRU cap + hit/evict counters);
  * live 4-validator net: /trace?height=H returns consensus step
    spans, a batch-verify dispatch span, and p2p send/recv events,
    strictly ordered.
"""
import asyncio
import gc
import importlib.util
import json
import os
import time

import pytest

from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.supervisor import RestartPolicy, Supervisor
from cometbft_tpu.libs.tracing import Recorder
from cometbft_tpu.types.signature_cache import (
    SignatureCache, SignatureCacheValue,
)

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(_ROOT, "tools",
                                     "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture
def recorder(tmp_path):
    """Fresh process-global recorder pointed at tmp; restores the old
    one afterwards."""
    old = tracing.set_recorder(
        Recorder(buffer_size=65536, dump_dir=str(tmp_path)))
    yield tracing.recorder()
    tracing.set_recorder(old)


class TestRecorder:
    def test_spans_and_instants_strictly_ordered(self, recorder):
        with tracing.span(tracing.CONSENSUS, "step:Propose",
                          height=5, round=0):
            tracing.instant(tracing.P2P, "recv", height=5, bytes=100)
        tracing.instant(tracing.CONSENSUS, "commit", height=5)
        evs = tracing.snapshot()
        # spans sort by their START time: the span opened before the
        # instant fired inside it
        assert [e["name"] for e in evs] == \
            ["step:Propose", "recv", "commit"]
        ts = [e["ts_ns"] for e in evs]
        assert ts == sorted(ts)
        span_ev = next(e for e in evs if e["name"] == "step:Propose")
        assert span_ev["dur_ns"] > 0
        assert span_ev["attrs"]["round"] == 0

    def test_height_and_category_filters(self, recorder):
        tracing.instant(tracing.CONSENSUS, "a", height=1)
        tracing.instant(tracing.CONSENSUS, "b", height=2)
        tracing.instant(tracing.CRYPTO, "c", height=2)
        assert {e["name"] for e in tracing.snapshot(height=2)} == \
            {"b", "c"}
        assert {e["name"]
                for e in tracing.snapshot(category=tracing.CRYPTO)
                } == {"c"}
        assert len(tracing.snapshot(limit=1)) == 1

    def test_height_context_inherited(self, recorder):
        tracing.set_height(7)
        tracing.instant(tracing.P2P, "send", bytes=1)
        with tracing.span(tracing.CRYPTO, "batch_verify", batch=4):
            pass
        assert all(e["height"] == 7 for e in tracing.snapshot())

    def test_ring_is_bounded(self, tmp_path):
        old = tracing.set_recorder(
            Recorder(buffer_size=16, dump_dir=str(tmp_path)))
        try:
            for i in range(100):
                tracing.instant(tracing.P2P, "send", seq=i)
            evs = tracing.snapshot()
            assert len(evs) == 16
            # the ring keeps the NEWEST events
            assert evs[-1]["attrs"]["seq"] == 99
        finally:
            tracing.set_recorder(old)

    def test_category_enable_list(self, tmp_path):
        old = tracing.set_recorder(
            Recorder(buffer_size=16, categories="consensus,crypto",
                     dump_dir=str(tmp_path)))
        try:
            tracing.instant(tracing.CONSENSUS, "a")
            tracing.instant(tracing.P2P, "b")
            with tracing.span(tracing.P2P, "c"):
                pass
            assert [e["name"] for e in tracing.snapshot()] == ["a"]
        finally:
            tracing.set_recorder(old)

    def test_span_records_error_attr(self, recorder):
        with pytest.raises(ValueError):
            with tracing.span(tracing.ABCI, "consensus/finalize"):
                raise ValueError("boom")
        (ev,) = tracing.snapshot()
        assert ev["attrs"]["error"] == "ValueError"

    def test_dump_is_parseable_and_atomic(self, recorder, tmp_path):
        tracing.instant(tracing.CONSENSUS, "commit", height=3)
        path = tracing.dump(reason="unit test!",
                            extra={"k": "v"})
        assert path and os.path.exists(path)
        assert not os.path.exists(path + ".tmp")
        with open(path) as f:
            record = json.load(f)
        assert record["reason"] == "unit test!"
        assert record["extra"] == {"k": "v"}
        assert record["events"][0]["name"] == "commit"


class TestCausality:
    def test_nested_spans_record_parent_and_inherit_height(
            self, recorder):
        tracing.set_height(99)      # the last fallback only
        with tracing.span(tracing.BLOCKSYNC, "sync_height",
                          height=7) as outer:
            with tracing.span(tracing.CONSENSUS, "commit_verify"):
                tracing.instant(tracing.CRYPTO, "mark")
                t0 = tracing.now_ns()
                tracing.record_span(tracing.CRYPTO, "host_prep", t0)
            with tracing.span(tracing.STATE, "apply_block",
                              height=8):
                pass
        with tracing.span(tracing.CRYPTO, "alone"):
            pass
        ev = {e["name"]: e for e in tracing.snapshot()}
        assert ev["sync_height"]["parent"] == 0
        assert ev["sync_height"]["id"] == outer.id
        assert ev["commit_verify"]["parent"] == outer.id
        for name in ("mark", "host_prep"):
            assert ev[name]["parent"] == ev["commit_verify"]["id"]
        # a span that names no height takes its parent's; one that
        # names its own keeps it; with no parent, the global one
        for name in ("commit_verify", "mark", "host_prep"):
            assert ev[name]["height"] == 7
        assert ev["apply_block"]["height"] == 8
        assert ev["alone"]["parent"] == 0
        assert ev["alone"]["height"] == 99
        ids = [e["id"] for e in ev.values()]
        assert len(set(ids)) == len(ids) and all(i > 0 for i in ids)

    def test_to_thread_keeps_the_parent(self, recorder):
        import threading

        def work():
            with tracing.span(tracing.CRYPTO, "host_prep"):
                pass
            return threading.get_ident()

        async def go():
            with tracing.span(tracing.CRYPTO, "batch_verify",
                              height=5) as outer:
                return outer.id, await asyncio.to_thread(work)

        outer_id, worker_tid = run(go())
        ev = {e["name"]: e for e in tracing.snapshot()}
        assert ev["host_prep"]["parent"] == outer_id
        assert ev["host_prep"]["height"] == 5
        assert ev["host_prep"]["tid"] == worker_tid
        assert ev["batch_verify"]["tid"] == threading.get_ident()
        assert worker_tid != threading.get_ident()

    def test_a_closed_parent_is_no_parent(self, recorder):
        """A long-lived task created inside a span copies the context
        that held it; once the span has closed it parents nothing."""
        async def go():
            gate = asyncio.Event()

            async def late():
                await gate.wait()
                with tracing.span(tracing.P2P, "late"):
                    pass

            async def early():
                with tracing.span(tracing.P2P, "early"):
                    pass

            with tracing.span(tracing.CONSENSUS, "starter", height=3):
                t_late = asyncio.get_running_loop().create_task(late())
                await asyncio.get_running_loop().create_task(early())
            gate.set()
            await t_late

        run(go())
        ev = {e["name"]: e for e in tracing.snapshot()}
        assert ev["early"]["parent"] == ev["starter"]["id"]
        assert ev["early"]["height"] == 3
        assert ev["late"]["parent"] == 0
        assert ev["late"]["height"] == 0

    def test_sibling_tasks_do_not_adopt_each_other(self, recorder):
        async def go():
            async def one(name):
                with tracing.span(tracing.P2P, name):
                    await asyncio.sleep(0.01)

            await asyncio.gather(one("a"), one("b"))

        run(go())
        assert [e["parent"] for e in tracing.snapshot()] == [0, 0]

    def test_id_parent_tid_in_snapshot_and_dump(self, recorder):
        import threading
        with tracing.span(tracing.ABCI, "consensus/finalize_block",
                          height=2):
            with tracing.span(tracing.ABCI, "state_root", hashes=3):
                pass
        snap = tracing.snapshot()
        with open(tracing.dump(reason="fields")) as f:
            dumped = json.load(f)["events"]
        for events in (snap, dumped):
            outer, inner = events
            for e in events:
                assert {"id", "parent", "tid"} <= set(e)
                assert e["tid"] == threading.get_ident()
            assert inner["parent"] == outer["id"] != 0
            assert outer["parent"] == 0

    def test_timed_span_lends_its_clock_readings(self, tmp_path):
        """timed(): one pair of readings for the span and for the
        metric at the same boundary — also with tracing off, where it
        records nothing and parents nothing."""
        for enabled in (True, False):
            old = tracing.set_recorder(
                Recorder(enabled=enabled, dump_dir=str(tmp_path)))
            try:
                with tracing.timed(tracing.CRYPTO, "host_prep",
                                   batch=4) as sp:
                    time.sleep(0.002)
                    with tracing.span(tracing.CRYPTO, "inner"):
                        pass
                assert sp.seconds >= 0.002
                evs = tracing.snapshot()
                if not enabled:
                    assert evs == []
                    continue
                outer = next(e for e in evs if e["name"] == "host_prep")
                assert outer["dur_ns"] == sp.t1 - sp.t0
                assert outer["dur_ns"] / 1e9 == sp.seconds
                inner = next(e for e in evs if e["name"] == "inner")
                assert inner["parent"] == outer["id"]
            finally:
                tracing.set_recorder(old)

    def test_begin_under_end_spans_an_interval_in_pieces(
            self, recorder):
        """A pipelined tile: begun before its dispatch, ended after
        its settle, the parent of what opens under() it in between
        and of nothing else."""
        sp = tracing.timed(tracing.CRYPTO, "kernel_execute",
                           tile=0).begin()
        with tracing.under(sp):
            with tracing.span(tracing.CRYPTO, "launch"):
                pass
        with tracing.span(tracing.CRYPTO, "host_prep"):
            pass
        with tracing.under(sp):
            with tracing.span(tracing.CRYPTO, "device_wait"):
                pass
        sp.end()
        ev = {e["name"]: e for e in tracing.snapshot()}
        assert ev["launch"]["parent"] == sp.id
        assert ev["device_wait"]["parent"] == sp.id
        assert ev["host_prep"]["parent"] == 0
        ke = ev["kernel_execute"]
        assert ke["id"] == sp.id
        assert ke["ts_ns"] <= ev["launch"]["ts_ns"]
        assert ke["ts_ns"] + ke["dur_ns"] >= \
            ev["device_wait"]["ts_ns"] + ev["device_wait"]["dur_ns"]

    def test_current_names_the_open_span_for_a_later_child(
            self, recorder):
        """A span begun after the block that was open at some
        earlier moment has closed (the seam's batch_verify, begun
        inside the walk) still names what was open then as its
        parent: current() remembers it, under() lends it."""
        assert tracing.current() is None
        assert tracing.under(tracing.current()).__enter__() is not None
        with tracing.span(tracing.CONSENSUS, "commit_verify",
                          height=4) as request:
            creator = tracing.current()
            assert creator is request
            with tracing.span(tracing.CONSENSUS, "commit_walk") as walk:
                assert tracing.current() is walk
                with tracing.under(creator):
                    late = tracing.timed(tracing.CRYPTO,
                                         "batch_verify").begin()
                assert tracing.current() is walk
            late.end()
        assert tracing.current() is None
        ev = {e["name"]: e for e in tracing.snapshot()}
        assert ev["batch_verify"]["parent"] == request.id
        assert ev["batch_verify"]["height"] == 4
        assert ev["commit_walk"]["parent"] == request.id
        # closed: no parent for anyone any more
        with tracing.under(creator):
            assert tracing.current() is None


def _children(events):
    """parent id -> the program's own spans below it.  A collection
    may strike under any of them: its gc_pause (category runtime) is
    TestRuntime's subject, not a span tree's."""
    out = {}
    for e in events:
        if e["category"] != tracing.RUNTIME:
            out.setdefault(e["parent"], []).append(e)
    return out


class TestBlocksyncSpanTree:
    """A short block sync on the CPU (the benchmark's catch-up driver
    at its tiniest: a fabricated 8-validator chain, one source peer,
    the real BlocksyncReactor over localhost p2p) leaves, for every
    height, one tree under its sync_height span."""

    TINY = {"validators": 8, "chain_heights": 300, "chain_margin": 4,
            "forged_height": 4, "prewarm_ops": 4, "warmup_ops": 8,
            "quiet_ops": 4, "txs_per_block": 2, "tx_bytes": 64,
            "kv_check_keys": 4}

    def _sync(self, tmp_path):
        import sys
        if _ROOT not in sys.path:
            sys.path.insert(0, _ROOT)
        from benchmark.lib import loader
        from benchmark.lib.compiles import CompileLog
        from benchmark.lib.session import Ctx, Window

        bench = loader.Bench(_ROOT)
        cell = bench.cell("qa-175.catchup")
        driver = bench.traffic(cell.driver)
        ctx = Ctx(bench, cell, seed=11, seconds=0.3, trace=False,
                  rehearsal=False, compiles=CompileLog(),
                  t_start=time.monotonic())
        ctx.work_dir = str(tmp_path)
        ctx.overrides.update(self.TINY)

        async def go():
            ctx.configure_tracing()
            state = await driver.set_up(ctx)
            tracing.clear()
            await driver.run(ctx, state, Window(
                start=time.monotonic(), seconds=ctx.seconds))
            events = tracing.snapshot()
            await driver.tear_down(ctx, state)
            return events

        old = tracing.recorder()
        try:
            return run(go())
        finally:
            tracing.set_recorder(old)

    def test_one_height_is_one_tree(self, tmp_path):
        events = self._sync(tmp_path)
        kids = _children(events)

        def child(parent, name, **attrs):
            found = [e for e in kids.get(parent["id"], ())
                     if e["name"] == name and all(
                         (e.get("attrs") or {}).get(k) == v
                         for k, v in attrs.items())]
            assert len(found) == 1, (parent["name"], name, found)
            return found[0]

        heights = [e for e in events if e["name"] == "sync_height"
                   and e["attrs"]["outcome"] == "applied"]
        assert len(heights) >= 3
        top = heights[len(heights) // 2]
        h = top["height"]
        assert h > 2 and top["parent"] == 0
        # only what something reads rides on the spans (gc_us:
        # tools/trace_report.py's runtime column)
        assert set(top["attrs"]) == {"outcome", "gc_us"}

        child(top, "part_set")
        # the light verification of this height's commit (stops past
        # 2/3 of 8) ...
        light = child(top, "commit_verify")
        child(light, "commit_walk")
        assert child(light, "batch_verify")["attrs"]["batch"] == 6
        # ... and the strict one of the block's LastCommit, whose
        # request is the height before
        validate = child(top, "validate_block")
        strict = child(validate, "commit_verify")
        assert strict["height"] == h - 1
        child(strict, "commit_walk")
        strict_seam = child(strict, "batch_verify")
        assert strict_seam["height"] == h - 1
        assert strict_seam["attrs"]["batch"] == 8

        child(top, "store_save_block")
        apply = child(top, "apply_block")
        fin = child(apply, "consensus/finalize_block")
        # two txs a block write two keys: two leaves hashed anew and
        # every inner node above the leaves the tree then holds
        assert child(fin, "state_root")["attrs"]["hashes"] > 2
        child(apply, "save_finalize_response")
        child(apply, "update_state")
        child(child(apply, "app_commit"), "consensus/commit")
        child(apply, "state_save")
        child(apply, "fire_events")
        # one span per boundary: no second apply_block, validate_block
        # or block save around these
        for name in ("apply_block", "validate_block",
                     "store_save_block"):
            assert sum(1 for e in events if e["name"] == name
                       and e["height"] == h) == 1, name

        # every span of the height, the LastCommit's subtree aside,
        # carries the height and lies inside sync_height
        def walk_tree(e):
            yield e
            for k in kids.get(e["id"], ()):
                yield from walk_tree(k)

        last_commit = {e["id"] for e in walk_tree(strict)}
        end = top["ts_ns"] + top["dur_ns"]
        for e in walk_tree(top):
            assert e["height"] == (h - 1 if e["id"] in last_commit
                                   else h), e
            assert top["ts_ns"] <= e["ts_ns"] and \
                e["ts_ns"] + e["dur_ns"] <= end, e
        # the peers' sides are spans of their own, stamped with the
        # height of the block they carry
        for name in ("block_decode", "block_serve"):
            wire = [e for e in events if e["name"] == name]
            assert wire and all(
                e["height"] > 0 and e["parent"] == 0
                for e in wire), name
        # apply_block has no time of its own to speak of
        covered = sum(k["dur_ns"] for k in kids[apply["id"]])
        assert covered > 0.5 * apply["dur_ns"]


class TestTiledDispatchSpans:
    def test_each_tile_runs_from_dispatch_to_mask(self, recorder,
                                                  monkeypatch):
        """A batch above one tile: one kernel_execute span per tile,
        begun before the tile's launch and ended after its own
        settle, the four legs its children; one host_prep span per
        tile, the prep's own time on the native thread's clock
        readings, over before its tile is launched.  The kernel is
        stubbed (its compile takes minutes on a CPU); the dispatch
        path around it is the real one."""
        import jax.numpy as jnp

        from cometbft_tpu.crypto import ed25519
        from cometbft_tpu.crypto import pipeline as crypto_pipeline
        from cometbft_tpu.ops import ed25519_jax as ej

        def stub(wire):
            return jnp.ones(wire.shape[0], dtype=bool)

        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
        monkeypatch.setattr(crypto_pipeline, "TILE", 64)
        monkeypatch.setattr(ej, "_jit_verify_packed", stub)
        priv = ed25519.gen_priv_key_from_secret(b"tiles")
        pub = priv.pub_key().bytes()
        items = [(pub, b"m%d" % i, priv.sign(b"m%d" % i))
                 for i in range(150)]
        streamed = ej.streamed_tiles_counter()
        before = sum(streamed.with_labels(how).value
                     for how in ("ready", "waited"))
        with tracing.span(tracing.CRYPTO, "batch_verify", height=9,
                          batch=len(items)) as seam:
            ok, mask = ej.verify_batch(items)
        assert ok and len(mask) == 150
        assert sum(streamed.with_labels(how).value
                   for how in ("ready", "waited")) == before + 3

        events = tracing.snapshot()
        kids = _children(events)
        tiles = sorted((e for e in events
                        if e["name"] == "kernel_execute"),
                       key=lambda e: e["attrs"]["tile"])
        preps = [e for e in events if e["name"] == "host_prep"]
        assert [t["attrs"]["tile"] for t in tiles] == [0, 1, 2]
        assert len(preps) == 3
        for t, prep in zip(tiles, preps):
            assert t["parent"] == seam.id and t["height"] == 9
            assert t["attrs"]["pipelined"] is True
            assert t["attrs"]["batch"] == 50
            assert t["attrs"]["bucket"] == 64
            assert t["attrs"]["platform"] == "cpu"
            assert t["attrs"]["prep_wait_us"] >= 0
            legs = kids[t["id"]]
            assert [e["name"] for e in legs] == \
                ["h2d", "launch", "device_wait", "d2h"]
            assert all(e["height"] == 9 for e in legs)
            end = t["ts_ns"] + t["dur_ns"]
            assert t["ts_ns"] <= legs[0]["ts_ns"]
            assert legs[3]["ts_ns"] + legs[3]["dur_ns"] <= end
            # the tile's prep: under the seam, inside it, a cost of
            # its own and over before the launch
            assert prep["parent"] == seam.id and prep["height"] == 9
            assert prep["attrs"] == {"batch": 50, "bucket": 64,
                                     "pipelined": True}
            assert seam.t0 <= prep["ts_ns"] and prep["dur_ns"] > 0
            assert prep["ts_ns"] + prep["dur_ns"] <= t["ts_ns"]
        # a tile's prep is begun before the tile before it is launched
        for i in (0, 1):
            assert preps[i]["ts_ns"] < preps[i + 1]["ts_ns"]
            assert tiles[i]["ts_ns"] < tiles[i + 1]["ts_ns"]

    @pytest.fixture
    def seam(self, recorder, monkeypatch):
        """The seam's device path with a stubbed kernel at a 64-lane
        tile: verifier(n) walks n adds under commit_verify /
        commit_walk as types/validation does and returns the
        verifier, the two spans still to be closed by the caller's
        ``with``."""
        import jax.numpy as jnp

        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.crypto import ed25519
        from cometbft_tpu.crypto import pipeline as crypto_pipeline
        from cometbft_tpu.ops import ed25519_jax as ej

        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
        monkeypatch.setattr(crypto_pipeline, "TILE", 64)
        monkeypatch.setattr(ej, "SHARD_MIN", 1000000)
        monkeypatch.setattr(
            ej, "_jit_verify_packed",
            lambda wire: jnp.ones(wire.shape[0], dtype=bool))
        monkeypatch.setattr(crypto_batch, "_backend", "tpu")
        crypto_batch.reset_tpu_breaker()
        priv = ed25519.gen_priv_key_from_secret(b"seam")
        pub = priv.pub_key()

        def verify(n):
            with tracing.span(tracing.CONSENSUS, "commit_verify",
                              height=9) as request:
                bv = crypto_batch.create_batch_verifier(pub)
                with tracing.span(tracing.CONSENSUS, "commit_walk"):
                    for i in range(n):
                        bv.add(pub, b"m%d" % i, priv.sign(b"m%d" % i))
                ok, mask = bv.verify()
            assert ok and len(mask) == n
            return request

        yield verify
        crypto_batch.reset_tpu_breaker()

    def test_a_streamed_batch_opens_its_span_at_the_first_feed(
            self, seam):
        """150 items at a 64-lane tile: add() hands over tiles 0 and
        1 inside the walk (the 64th add begins tile 0's prep, the
        128th launches it), verify() the 22 left.  batch_verify opens
        at the first hand-over, a child of the span open when the
        verifier was made (commit_verify) and not of the walk it
        overlaps; under it, a tile: host_prep (the prep's own time)
        and kernel_execute with ``tile``, ``prep_wait_us`` and, on
        the tiles handed over from add() only, ``eager``;
        mask_handback last; no span of a hand-over, which is a list
        slice now (what benchmark/layers/seam_outside_tiles_ms and
        eager_tiles_per_commit read is made of them)."""
        request = seam(150)
        events = tracing.snapshot()
        (bv,) = [e for e in events if e["name"] == "batch_verify"]
        (walk,) = [e for e in events if e["name"] == "commit_walk"]
        assert bv["parent"] == request.id == walk["parent"]
        assert bv["height"] == 9
        assert bv["attrs"] == {"backend": "tpu", "batch": 150}
        walk_end = walk["ts_ns"] + walk["dur_ns"]
        assert walk["ts_ns"] < bv["ts_ns"] < walk_end
        assert bv["ts_ns"] + bv["dur_ns"] > walk_end
        kids = _children(events)[bv["id"]]
        assert sorted(e["name"] for e in kids) == \
            ["host_prep"] * 3 + ["kernel_execute"] * 3 + \
            ["mask_handback"]
        preps = [e for e in kids if e["name"] == "host_prep"]
        tiles = [e for e in kids if e["name"] == "kernel_execute"]
        for i, (prep, t) in enumerate(zip(preps, tiles)):
            a = t["attrs"]
            assert a["pipelined"] is True and a["tile"] == i
            assert a["bucket"] == 64 and "warm" in a
            assert a["batch"] == (64, 64, 22)[i]
            assert a.get("eager") == (True, True, None)[i]
            assert isinstance(a["prep_wait_us"], int)
            # launched from the walk, or after it
            assert (t["ts_ns"] < walk_end) == (i < 1)
            assert prep["attrs"]["batch"] == a["batch"]
            assert bv["ts_ns"] <= prep["ts_ns"]
            assert prep["ts_ns"] + prep["dur_ns"] <= t["ts_ns"]
        # tile 0's prep ran beside the walk
        assert preps[0]["ts_ns"] + preps[0]["dur_ns"] < walk_end
        ends = [e["ts_ns"] + e["dur_ns"] for e in kids]
        assert max(ends[:-1]) <= kids[-1]["ts_ns"]
        assert kids[-1]["name"] == "mask_handback"
        assert ends[-1] <= bv["ts_ns"] + bv["dur_ns"]
        # a span where the time is, not one a signature
        assert not [e for e in events if e["name"] in (
            "item_handover", "item_release")]

    def test_an_untiled_batch_keeps_its_tree(self, seam):
        """Below one tile nothing is fed from add(): batch_verify
        opens in verify(), after the walk, with the one dispatch's
        spans as before."""
        request = seam(60)
        events = tracing.snapshot()
        (bv,) = [e for e in events if e["name"] == "batch_verify"]
        (walk,) = [e for e in events if e["name"] == "commit_walk"]
        assert bv["parent"] == request.id
        assert bv["attrs"] == {"backend": "tpu", "batch": 60}
        assert walk["ts_ns"] + walk["dur_ns"] <= bv["ts_ns"]
        kids = sorted(_children(events)[bv["id"]],
                      key=lambda e: e["ts_ns"])
        assert [e["name"] for e in kids] == [
            "host_prep", "kernel_execute"]
        for attr in ("pipelined", "eager", "prep_wait_us"):
            assert attr not in kids[1]["attrs"]
        assert [e["name"] for e in _children(events)[kids[1]["id"]]] \
            == ["h2d", "launch", "device_wait", "d2h"]
        ends = [e["ts_ns"] + e["dur_ns"] for e in kids]
        assert all(a <= b["ts_ns"] for a, b in zip(ends, kids[1:]))
        assert ends[-1] <= bv["ts_ns"] + bv["dur_ns"]

    def test_read_back_is_bare_with_the_recorder_off(self, tmp_path,
                                                     monkeypatch):
        """With the crypto category off the mask comes back by one
        np.asarray, as before the legs were split: no wait of its own,
        no span."""
        import numpy as np

        from cometbft_tpu.ops import ed25519_jax as ej

        calls = []

        class Dev:
            def copy_to_host_async(self):
                calls.append("copy_to_host_async")

            def block_until_ready(self):
                calls.append("block_until_ready")

            def __array__(self, dtype=None, copy=None):
                calls.append("asarray")
                return np.ones(4, bool)

        for categories, expected in (
                (("consensus",), ["asarray"]),
                (None, ["copy_to_host_async", "block_until_ready",
                        "asarray"])):
            old = tracing.set_recorder(Recorder(
                categories=categories, dump_dir=str(tmp_path)))
            try:
                del calls[:]
                assert ej._force(Dev()).all()
                assert calls == expected
                assert [e["name"] for e in tracing.snapshot()] == \
                    (["device_wait", "d2h"] if categories is None
                     else [])
            finally:
                tracing.set_recorder(old)


class TestDisabledOverhead:
    def test_noop_span_under_1us(self, tmp_path):
        """The always-on budget: with tracing disabled, a span call
        (create + enter + exit) must cost <1µs — the hot paths
        (per-packet p2p, per-vote consensus) run it unconditionally."""
        old = tracing.set_recorder(
            Recorder(enabled=False, dump_dir=str(tmp_path)))
        try:
            span = tracing.span
            n = 50_000
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(n):
                    with span("consensus", "x"):
                        pass
                best = min(best, (time.perf_counter() - t0) / n)
            assert best < 1e-6, f"{best * 1e9:.0f}ns per no-op span"
            assert tracing.snapshot() == []
        finally:
            tracing.set_recorder(old)

    def test_noop_instant_records_nothing(self, tmp_path):
        old = tracing.set_recorder(
            Recorder(enabled=False, dump_dir=str(tmp_path)))
        try:
            tracing.instant(tracing.P2P, "send", bytes=1)
            tracing.record_span(tracing.P2P, "x", 0, 1)
            assert tracing.snapshot() == []
        finally:
            tracing.set_recorder(old)


@pytest.fixture
def quiet_gc():
    """No automatic collection during the test: the only collections
    are the ones it asks for."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


def _gc_state():
    return tracing.gc_ns_total(), tracing.gc_generation_totals()


class TestRuntime:
    """Category ``runtime``: the collector's pauses as spans and
    totals, and what a flagged span notes of them."""

    def test_a_full_collection_is_a_child_span_and_the_same_pause(
            self, recorder, quiet_gc):
        total0, gens0 = _gc_state()
        with tracing.span(tracing.CONSENSUS, "outer", height=7,
                          runtime=True):
            with tracing.span(tracing.STATE, "inner"):
                gc.collect()
        total1, gens1 = _gc_state()
        events = {e["name"]: e for e in tracing.snapshot()}
        pause = events["gc_pause"]
        assert pause["category"] == tracing.RUNTIME
        # under the span it struck, with that span's height
        assert pause["parent"] == events["inner"]["id"]
        assert pause["height"] == 7
        assert pause["attrs"]["generation"] == 2
        assert pause["attrs"]["collected"] >= 0
        # one pause: the span, the total, the generation's total and
        # the flagged span's gc_us are the same nanoseconds
        assert total1 - total0 == pause["dur_ns"]
        assert gens1[2][0] - gens0[2][0] == pause["dur_ns"]
        assert gens1[2][1] - gens0[2][1] == 1
        assert gens1[0] == gens0[0] and gens1[1] == gens0[1]
        assert events["outer"]["attrs"] == {
            "gc_us": pause["dur_ns"] // 1000}
        # the unflagged span notes nothing
        assert "attrs" not in events["inner"]

    def test_a_collection_under_the_recorders_lock_records(
            self, recorder, quiet_gc):
        """A collection can strike while the recorder holds its lock
        (clear() freeing the rings, a ring being made); the hook then
        makes the runtime ring on the same thread.  With a plain lock
        that was a deadlock (PR 38: clear() between commits)."""
        tracing.clear()
        with recorder._lock:
            gc.collect()
        assert [e["name"] for e in tracing.snapshot()] == ["gc_pause"]

    @pytest.mark.parametrize("generation,min_ns,recorded", [
        (0, 10**12, False),     # short and young: counted only
        (0, 0, True),           # young but as long as the threshold
        (1, 10**12, True),      # an older generation: always a span
        (2, 10**12, True),
    ])
    def test_which_collections_become_spans(
            self, recorder, quiet_gc, monkeypatch, generation, min_ns,
            recorded):
        monkeypatch.setattr(tracing, "GC_SPAN_MIN_NS", min_ns)
        _, gens0 = _gc_state()
        gc.collect(generation)
        _, gens1 = _gc_state()
        assert gens1[generation][1] - gens0[generation][1] == 1
        assert gens1[generation][0] > gens0[generation][0]
        pauses = [e for e in tracing.snapshot()
                  if e["name"] == "gc_pause"]
        assert len(pauses) == (1 if recorded else 0)
        if recorded:
            assert pauses[0]["attrs"]["generation"] == generation
            assert pauses[0]["parent"] == 0

    @pytest.mark.parametrize("kwargs", [
        {"enabled": False},
        {"categories": "consensus,crypto"},     # runtime is off
    ])
    def test_counters_move_with_nothing_recorded(
            self, tmp_path, quiet_gc, kwargs):
        old = tracing.set_recorder(
            Recorder(dump_dir=str(tmp_path), **kwargs))
        try:
            total0, gens0 = _gc_state()
            with tracing.span(tracing.CONSENSUS, "outer",
                              runtime=True):
                gc.collect()
            total1, gens1 = _gc_state()
            assert total1 > total0
            assert gens1[2][1] - gens0[2][1] == 1
            assert gens1[2][0] - gens0[2][0] == total1 - total0
            events = tracing.snapshot()
            # the category carries the flag's readings too
            assert all("attrs" not in e for e in events)
            assert [e["name"] for e in events] == (
                [] if "enabled" in kwargs else ["outer"])
        finally:
            tracing.set_recorder(old)

    def test_the_hook_is_installed_once(self):
        assert gc.callbacks.count(tracing._gc_hook) == 1

    def test_a_timed_span_reads_nothing_with_its_category_off(
            self, tmp_path):
        old = tracing.set_recorder(Recorder(
            categories="crypto", dump_dir=str(tmp_path)))
        try:
            sp = tracing.timed(tracing.CONSENSUS, "commit_verify",
                               runtime=True)
            with sp:
                pass
            assert sp.attrs is None and sp.seconds >= 0
        finally:
            tracing.set_recorder(old)


def _best_ns(*fns, n=20_000, rounds=9):
    """The existing guards' method, the best of several loops; the
    loops of several functions take turns, so that a busy minute of
    the host strikes them alike."""
    best = [float("inf")] * len(fns)
    for _ in range(rounds):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            fn(n)
            best[i] = min(best[i], (time.perf_counter() - t0) / n)
    return [b * 1e9 for b in best]


class TestRuntimeOverhead:
    def test_hook_under_2us_a_collection(self, tmp_path, quiet_gc,
                                         monkeypatch):
        """Two Python calls a collection, recorder on or off: a
        generation-0 collection, the common one, records nothing."""
        # a pair the host deschedules for a millisecond is no span here
        monkeypatch.setattr(tracing, "GC_SPAN_MIN_NS", 10**12)
        hook = tracing._gc_hook
        info = {"generation": 0, "collected": 0, "uncollectable": 0}

        def pairs(n):
            for _ in range(n):
                hook("start", info)
                hook("stop", info)

        def empty(n):
            for _ in range(n):
                pass

        before = _gc_state()
        for enabled in (True, False):
            old = tracing.set_recorder(Recorder(
                enabled=enabled, dump_dir=str(tmp_path)))
            try:
                ns, loop_ns = _best_ns(pairs, empty)
                assert ns - loop_ns < 2000, f"{ns:.0f}ns a collection"
                assert tracing.snapshot() == []
            finally:
                tracing.set_recorder(old)
        # the loops above were no collections: take them out again
        tracing._gc_ns = before[0]
        for gen, (ns, count) in enumerate(before[1]):
            tracing._gc_gen_ns[gen] = ns
            tracing._gc_gen_n[gen] = count

    def test_flagged_span_under_1us_more(self, tmp_path, quiet_gc):
        """gc_us is two int reads and one dict a span."""
        span = tracing.span

        def loop(flagged):
            def run(n):
                for _ in range(n):
                    with span("consensus", "x", runtime=flagged):
                        pass
            return run

        old = tracing.set_recorder(
            Recorder(buffer_size=1024, dump_dir=str(tmp_path)))
        try:
            plain, with_gc = _best_ns(loop(False), loop(True))
        finally:
            tracing.set_recorder(old)
        assert with_gc - plain < 1000, (plain, with_gc)


class TestSupervisorGiveupDump:
    def test_giveup_dumps_flight_record(self, recorder, tmp_path):
        async def go():
            sup = Supervisor("t")

            async def boom():
                raise RuntimeError("kaput")

            st = sup.spawn(boom, name="boom", kind="boom",
                           policy=RestartPolicy(max_restarts=0))
            await st.wait()
            return st

        st = run(go())
        assert st.gave_up
        path = recorder.last_dump_path
        assert path and os.path.exists(path)
        with open(path) as f:
            record = json.load(f)
        assert "supervisor_giveup" in record["reason"]
        assert record["extra"]["kind"] == "boom"
        assert any(e["name"] == "giveup"
                   for e in record["events"])


class TestNemesisSafetyDump:
    def test_conflicting_commits_dump_heights(self, recorder,
                                              tmp_path):
        from nemesis import NemesisNet

        class _Block:
            def __init__(self, h):
                self._h = h

            def hash(self):
                return self._h

        class _Store:
            def __init__(self, blocks):
                self._b = blocks

            def load_block(self, h):
                return self._b.get(h)

        class _Node:
            def __init__(self, idx, blocks):
                self.idx = idx
                self.block_store = _Store(blocks)
                self.height = max(blocks, default=0)

        net = object.__new__(NemesisNet)
        net.nodes = [
            _Node(0, {1: _Block(b"\xaa" * 32), 2: _Block(b"\xcc" * 32)}),
            _Node(1, {1: _Block(b"\xbb" * 32), 2: _Block(b"\xcc" * 32)}),
        ]
        with pytest.raises(AssertionError) as ei:
            net.assert_no_conflicting_commits()
        assert "SAFETY VIOLATION" in str(ei.value)
        path = recorder.last_dump_path
        assert path and os.path.exists(path)
        with open(path) as f:
            record = json.load(f)
        # the dump names the conflicting heights (height 2 agreed)
        assert record["extra"]["conflicting_heights"] == [1]
        assert "aa" * 4 in json.dumps(record["extra"]["conflicts"])
        # and the report renders it
        report = _load_trace_report().render_report(record)
        assert "conflicting-commit heights: [1]" in report

    def test_agreeing_commits_do_not_dump(self, recorder):
        from nemesis import NemesisNet

        class _Node:
            def __init__(self, idx):
                self.idx = idx
                self.height = 0
                self.block_store = type(
                    "S", (), {"load_block":
                              staticmethod(lambda h: None)})()

        net = object.__new__(NemesisNet)
        net.nodes = [_Node(0), _Node(1)]
        net.assert_no_conflicting_commits()
        assert recorder.last_dump_path == ""


class TestTraceReport:
    def test_per_height_breakdown(self, recorder):
        base = tracing.now_ns()
        # height 4: propose step, proposal completes, crypto batch,
        # abci finalize, the block store's save
        tracing.record_span(tracing.CONSENSUS, "step:Propose",
                            base, base + 10_000_000, height=4)
        recorder.record_instant(tracing.CONSENSUS,
                                "proposal_complete", 4, None)
        tracing.record_span(tracing.CRYPTO, "batch_verify",
                            base + 2_000_000, base + 5_000_000,
                            height=4, batch=128, backend="cpu")
        tracing.record_span(tracing.ABCI, "consensus/finalize_block",
                            base + 6_000_000, base + 9_000_000,
                            height=4)
        tracing.record_span(tracing.STATE, "store_save_block",
                            base + 9_000_000, base + 9_500_000,
                            height=4)
        mod = _load_trace_report()
        record = {"events": tracing.snapshot()}
        rows = mod.analyze(record)
        assert 4 in rows
        r = rows[4]
        assert r["verify_ms"] == pytest.approx(3.0)
        assert r["execute_ms"] == pytest.approx(3.0)
        assert r["commit_ms"] == pytest.approx(0.5)
        assert r["batches"][0]["batch"] == 128
        assert r["batches"][0]["backend"] == "cpu"
        text = mod.render_report(record)
        assert "verify_ms" in text and "batch=128" in text

    def test_runtime_column(self, recorder, quiet_gc):
        """gc_pause by generation, the outermost gc_us and
        commit_release, per height."""
        base = tracing.now_ns()
        ms = 1_000_000
        rec = recorder.record
        # height 5: a sync_height that notes 3,500 us of collections,
        # over a commit_verify that notes 3,000 of them (not summed
        # twice), and the release
        rec(tracing.BLOCKSYNC, "sync_height", base, base + 40 * ms, 5,
            {"outcome": "applied", "gc_us": 3500}, span_id=901)
        rec(tracing.CONSENSUS, "commit_verify", base + ms,
            base + 30 * ms, 5, {"gc_us": 3000}, span_id=902,
            parent=901)
        rec(tracing.CONSENSUS, "commit_walk", base + ms, base + 21 * ms,
            5, {"lookup": "index", "gc_us": 3000},
            span_id=903, parent=902)
        rec(tracing.RUNTIME, "gc_pause", base + 2 * ms, base + 5 * ms,
            5, {"generation": 2, "collected": 10}, parent=903)
        rec(tracing.RUNTIME, "gc_pause", base + 31 * ms,
            base + 31 * ms + ms // 2, 0, {"generation": 1,
                                          "collected": 0}, parent=901)
        rec(tracing.CONSENSUS, "commit_release", base + 27 * ms,
            base + 29 * ms, 5, None, parent=902)
        # height 6: nothing of the kind
        rec(tracing.BLOCKSYNC, "sync_height", base + 50 * ms,
            base + 60 * ms, 6, {"outcome": "applied", "gc_us": 0})
        mod = _load_trace_report()
        record = {"events": tracing.snapshot()}
        rows = mod.analyze(record)
        r = rows[5]
        assert r["gc_ms"] == pytest.approx(3.5)
        assert r["gc_pause_ms"] == pytest.approx([0.0, 0.5, 3.0])
        assert r["release_ms"] == pytest.approx(2.0)
        assert rows[6]["gc_ms"] == 0.0
        assert rows[6]["gc_pause_ms"] == [0.0, 0.0, 0.0]
        text = mod.render_report(record)
        assert "runtime" in text.splitlines()[0]
        assert "gc 3.5 (0.0/0.5/3.0) rel 2.00" in text
        assert "gc 0.0 (0.0/0.0/0.0) rel 0.00" in text

    def test_heightless_events_attributed_by_window(self, recorder):
        base = tracing.now_ns()
        tracing.record_span(tracing.CONSENSUS, "step:Prevote",
                            base, base + 10_000_000, height=9)
        # a crypto span with NO height, inside height 9's window
        recorder.record(tracing.CRYPTO, "kernel_execute",
                        base + 1_000_000, base + 2_000_000, -1, None)
        mod = _load_trace_report()
        evs = tracing.snapshot()
        for e in evs:       # strip the height for the crypto event
            if e["category"] == "crypto":
                e["height"] = 0
        rows = mod.analyze({"events": evs})
        assert rows[9]["verify_ms"] == pytest.approx(1.0)


class TestTraceRPC:
    def test_trace_route(self, recorder):
        from cometbft_tpu.rpc import core
        tracing.instant(tracing.CONSENSUS, "commit", height=12)
        tracing.instant(tracing.P2P, "send", height=13)
        routes = core.routes(None)
        resp = run(routes["trace"](height="12"))
        assert resp["enabled"] is True
        assert resp["count"] == 1
        (ev,) = resp["events"]
        assert ev["name"] == "commit"
        assert ev["height"] == "12"          # int64-as-string
        resp_all = run(routes["trace"]())
        assert resp_all["count"] == 2
        resp_cat = run(routes["trace"](height="0", category="p2p"))
        assert resp_cat["count"] == 1

    def test_pprof_trace_dump(self, recorder, tmp_path):
        from cometbft_tpu.libs.pprof import _trace_dump
        tracing.instant(tracing.CONSENSUS, "commit", height=1)
        body = json.loads(_trace_dump(False))
        assert body["events"][0]["name"] == "commit"
        body = json.loads(_trace_dump(True))
        assert os.path.exists(body["dump_path"])


class TestSignatureCacheLRU:
    def test_lru_cap_and_counters(self):
        c = SignatureCache(capacity=3)
        for i in range(4):
            c.add(bytes([i]) * 64,
                  SignatureCacheValue(b"a", bytes([i])))
        assert len(c) == 3
        assert c.evictions == 1
        assert c.get(b"\x00" * 64) is None       # evicted (oldest)
        assert c.get(b"\x03" * 64) is not None
        assert c.misses == 1 and c.hits == 1

    def test_get_refreshes_recency(self):
        c = SignatureCache(capacity=2)
        c.add(b"a" * 64, SignatureCacheValue(b"a", b"1"))
        c.add(b"b" * 64, SignatureCacheValue(b"b", b"2"))
        assert c.get(b"a" * 64) is not None      # refresh a
        c.add(b"c" * 64, SignatureCacheValue(b"c", b"3"))
        assert c.get(b"b" * 64) is None          # b evicted, not a
        assert c.get(b"a" * 64) is not None

    def test_default_capacity_configurable(self):
        from cometbft_tpu.types import signature_cache as sc
        old = sc.DEFAULT_CAPACITY
        try:
            sc.set_default_capacity(5)
            assert SignatureCache().capacity == 5
        finally:
            sc.set_default_capacity(old)


# ---------------------------------------------------------------------
# acceptance: live testnet timeline

class TestLiveNetTrace:
    def test_trace_height_timeline_on_live_net(self, recorder):
        """/trace?height=H on a running 4-validator net over real
        sockets: consensus step spans, >=1 batch-verify dispatch span
        (with batch size and backend), and p2p send/recv events, all
        strictly ordered by monotonic timestamp."""
        from test_testnet import _make_net, _wait_all_height

        from cometbft_tpu.rpc import core

        async def go():
            nodes = await _make_net(4)
            try:
                await _wait_all_height(nodes, 3)
            finally:
                for n in nodes:
                    await n.stop()

        run(go())
        routes = core.routes(None)
        # pick a height that fully played out
        resp = run(routes["trace"](height="2"))
        evs = resp["events"]
        names = [(e["category"], e["name"]) for e in evs]
        assert any(n.startswith("step:") for _, n in names
                   if _ == "consensus"), names
        batch = [e for e in evs if e["category"] == "crypto"
                 and e["name"] == "batch_verify"]
        assert batch, names
        assert batch[0]["attrs"]["batch"] >= 2
        assert batch[0]["attrs"]["backend"] in (
            "cpu", "tpu", "bls_native")
        assert any(c == "p2p" and n == "send" for c, n in names)
        assert any(c == "p2p" and n == "recv" for c, n in names)
        ts = [int(e["ts_ns"]) for e in evs]
        assert ts == sorted(ts)
        # the report renders a breakdown for this height
        report = _load_trace_report().render_report(
            {"events": tracing.snapshot()}, height=2)
        assert "gossip_ms" in report
