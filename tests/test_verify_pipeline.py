"""Overlapped verification pipeline (ISSUE 14): tile kernel
correctness, tiled-vs-monolithic verdict parity, per-tile bisection
attribution, GIL-free worker overlap, the async verify seam, and the
committed perf-claim gates.

The tile kernel (native ed25519_batch_verify_tile: packed blobs,
staged pubkey decompression, signed-digit MSM, fe_sqr decompression)
must agree with the legacy monolithic entry and the golden model on
every verdict — including ZIP-215 corner encodings — and the python
pipeline (crypto/pipeline.py) must attribute bad signatures to exact
indices no matter where they fall relative to tile boundaries.
"""
import asyncio
import os
import secrets
import struct
import threading
import time

import pytest

from cometbft_tpu.crypto import _native_loader
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto import pipeline as cpipe
from cometbft_tpu.libs.workers import SupervisedWorker


def _native():
    mod = _native_loader.load()
    if mod is None:
        pytest.skip("no compiler available")
    if not hasattr(mod, "ed25519_batch_verify_tile"):
        pytest.skip("module predates the tile kernel")
    return mod


def _valid(i, msg=None):
    from cometbft_tpu.crypto import _ed25519_ref as ref
    seed = bytes([i % 256, i // 256 % 256]) + secrets.token_bytes(30)
    pub = ref.public_key(seed)
    m = msg if msg is not None else b"tile-msg-%d" % i
    return (pub, m, ref.sign(seed, m))


def _blobs(chunk):
    return (b"".join(p for p, _, _ in chunk),
            b"".join(m for _, m, _ in chunk),
            struct.pack(f"<{len(chunk)}I",
                        *(len(m) for _, m, _ in chunk)),
            b"".join(s for _, _, s in chunk))


def _tile_verdict(native, items, staged=False):
    z = secrets.token_bytes(16 * len(items))
    blobs = _blobs(items)
    if staged:
        pts = native.ed25519_stage_pubs(blobs[0])
        return bool(native.ed25519_batch_verify_tile(*blobs, z, pts))
    return bool(native.ed25519_batch_verify_tile(*blobs, z))


# ---------------------------------------------------------------------
# tile kernel vs golden model / legacy entry

class TestTileKernel:
    @pytest.mark.parametrize("staged", [False, True])
    @pytest.mark.parametrize("n", [1, 2, 7, 40])
    def test_valid_tiles_accept(self, n, staged):
        native = _native()
        items = [_valid(i) for i in range(n)]
        assert _tile_verdict(native, items, staged=staged)

    @pytest.mark.parametrize("staged", [False, True])
    def test_corrupted_signature_rejects(self, staged):
        native = _native()
        items = [_valid(i) for i in range(9)]
        pub, msg, sig = items[4]
        items[4] = (pub, msg, sig[:7] + bytes([sig[7] ^ 1]) + sig[8:])
        assert not _tile_verdict(native, items, staged=staged)

    def test_wrong_message_rejects(self):
        native = _native()
        items = [_valid(i) for i in range(5)]
        pub, _, sig = items[0]
        items[0] = (pub, b"forged", sig)
        assert not _tile_verdict(native, items)

    def test_non_canonical_s_rejects(self):
        from cometbft_tpu.crypto import _ed25519_ref as ref
        native = _native()
        items = [_valid(i) for i in range(3)]
        pub, msg, sig = items[1]
        s = int.from_bytes(sig[32:], "little") + ref.L
        items[1] = (pub, msg, sig[:32] + s.to_bytes(32, "little"))
        assert not _tile_verdict(native, items)

    @pytest.mark.parametrize("staged", [False, True])
    def test_zip215_corner_encodings_accept(self, staged):
        # A = order-4 point (y=0), R = non-canonical identity (y=p+1),
        # S=0: a ZIP-215 accept the golden model certifies — the
        # fe_sqr decompression chain must agree with the legacy one
        from cometbft_tpu.crypto import _ed25519_ref as ref
        native = _native()
        a_small = bytes(32)
        r_nc = (ref.P + 1).to_bytes(32, "little")
        corner = (a_small, b"whatever", r_nc + bytes(32))
        assert ref.verify(*corner)
        items = [_valid(0), corner, _valid(2)]
        assert _tile_verdict(native, items, staged=staged)

    def test_off_curve_pubkey_rejects(self):
        from cometbft_tpu.crypto import _ed25519_ref as ref
        native = _native()
        bad_pub = bytes([2]) + bytes(30) + bytes([0])
        if ref.decompress(bad_pub) is not None:
            pytest.skip("encoding unexpectedly valid")
        items = [_valid(0), (bad_pub, b"m", _valid(0)[2])]
        assert not _tile_verdict(native, items)
        assert not _tile_verdict(native, items, staged=True)

    def test_decompress_parity_fuzz_vs_legacy(self):
        """Random + structured encodings: the tile entry (fast
        decompression) and the legacy entry must return identical
        verdicts item-for-item (checked via singleton batches, where
        verdict == per-item acceptance)."""
        from cometbft_tpu.crypto import _ed25519_ref as ref
        native = _native()
        rng_cases = [secrets.token_bytes(32) for _ in range(24)]
        structured = [
            bytes(32),                                # y=0
            (ref.P - 1).to_bytes(32, "little"),       # y=p-1
            (ref.P).to_bytes(32, "little"),           # y=p (non-canon 0)
            (ref.P + 1).to_bytes(32, "little"),       # non-canon 1
            bytes([1] + [0] * 31),                    # identity
            bytes([0] * 31 + [0x80]),                 # y=0, sign=1
            bytes([0xFF] * 32),
        ]
        good = _valid(7)
        for enc in rng_cases + structured:
            item = (enc, b"m", good[2])
            z = secrets.token_bytes(16)
            legacy = bool(native.ed25519_batch_verify([item], z))
            tiled = bool(native.ed25519_batch_verify_tile(
                *_blobs([item]), z))
            assert legacy == tiled, enc.hex()

    def test_stage_pubs_blob_shape_and_invalid_marker(self):
        native = _native()
        good = _valid(1)[0]
        from cometbft_tpu.crypto import _ed25519_ref as ref
        bad = bytes([2]) + bytes(30) + bytes([0])
        if ref.decompress(bad) is not None:
            pytest.skip("encoding unexpectedly valid")
        blob = native.ed25519_stage_pubs(good + bad)
        rec = len(blob) // 2
        assert len(blob) % 2 == 0
        assert blob[rec - 1] == 1          # valid marker
        assert blob[2 * rec - 1] == 0      # invalid marker

    def test_mismatched_staged_blob_is_ignored_not_trusted(self):
        # a stale/mismatched staged blob must not corrupt verdicts
        native = _native()
        items = [_valid(i) for i in range(3)]
        z = secrets.token_bytes(16 * 3)
        assert native.ed25519_batch_verify_tile(
            *_blobs(items), z, b"\x00" * 7)


# ---------------------------------------------------------------------
# tiled pipeline: verdict parity + per-tile bisection attribution

class TestTiledParityFuzz:
    def _run_pair(self, items, tile):
        native = _native()
        raw = list(items)

        def verify_one(i):
            from cometbft_tpu.crypto import _ed25519_ref as ref
            pub, m, s = raw[i]
            return ref.verify(pub, m, s)

        ok_t, mask_t = cpipe.verify_items_pipelined(
            native, raw, verify_one, tile=tile)
        z = secrets.token_bytes(16 * len(raw))
        ok_m = bool(native.ed25519_batch_verify(raw, z))
        return (ok_t, mask_t), ok_m

    def test_all_valid_parity(self):
        items = [_valid(i) for i in range(150)]
        (ok_t, mask_t), ok_m = self._run_pair(items, tile=64)
        assert ok_t and ok_m and all(mask_t)

    @pytest.mark.parametrize("bad_idx", [
        [0],                      # first item of first tile
        [63], [64],               # tile boundary straddle
        [149],                    # last item of partial tile
        [127, 128],               # boundary pair
        [5, 70, 148],             # one per tile
    ])
    def test_bad_positions_attributed_exactly(self, bad_idx):
        items = [_valid(i) for i in range(150)]
        for i in bad_idx:
            pub, m, s = items[i]
            items[i] = (pub, m, s[:9] + bytes([s[9] ^ 0x40]) + s[10:])
        (ok_t, mask_t), ok_m = self._run_pair(items, tile=64)
        assert not ok_t and not ok_m
        assert [i for i, v in enumerate(mask_t) if not v] == bad_idx

    def test_random_fuzz_matches_monolithic_bisection(self, monkeypatch):
        """Random bad positions: the per-tile bisection's mask must
        equal the monolithic path's mask (CpuBatchVerifier pipelined
        vs monolithic=True) — the attribution contract."""
        import random
        monkeypatch.setattr(cpipe, "TILE", 64)  # monolithic ignores it
        rng = random.Random(1400)
        for trial in range(3):
            n = rng.randrange(130, 200)
            items = [_valid(1000 * trial + i) for i in range(n)]
            bad = sorted(rng.sample(range(n), rng.randrange(1, 5)))
            for i in bad:
                pub, m, s = items[i]
                items[i] = (pub, m,
                            s[:3] + bytes([s[3] ^ 0x11]) + s[4:])

            def bv(monolithic):
                v = ed25519.CpuBatchVerifier(monolithic=monolithic)
                for pub, m, s in items:
                    v.add(ed25519.Ed25519PubKey(pub), m, s)
                return v

            ok_t, mask_t = bv(False).verify()
            ok_m, mask_m = bv(True).verify()
            assert ok_t == ok_m is False
            assert mask_t == mask_m
            assert [i for i, v in enumerate(mask_t) if not v] == bad

    def test_tile_reject_counter_counts_rejecting_tiles(self):
        native = _native()
        ctr = cpipe._tile_reject_counter()
        before = ctr.value
        items = [_valid(i) for i in range(150)]
        pub, m, s = items[70]
        items[70] = (pub, m, s[:5] + bytes([s[5] ^ 2]) + s[6:])
        self._run_pair(items, tile=64)
        assert ctr.value == before + 1     # exactly one tile bisected


class TestTilePlan:
    def test_balanced_and_bounded(self):
        plan = cpipe.tile_plan(10000, 4096)
        sizes = [hi - lo for lo, hi in plan]
        assert sum(sizes) == 10000
        assert max(sizes) <= 4096
        # balanced: no degenerate tail tile (the naive plan's 1808)
        assert max(sizes) - min(sizes) <= len(sizes)
        assert plan[0][0] == 0 and plan[-1][1] == 10000

    def test_small_and_exact(self):
        assert cpipe.tile_plan(10, 64) == [(0, 10)]
        assert cpipe.tile_plan(128, 64) == [(0, 64), (64, 128)]
        assert cpipe.tile_plan(0, 64) == []


# ---------------------------------------------------------------------
# GIL release / two-thread overlap

class TestKernelGilRelease:
    N = 5000

    def _items(self, tag):
        sk = ed25519.gen_priv_key()
        pkb = sk.pub_key().bytes()
        out = []
        for i in range(self.N):
            m = b"%s-%05d" % (tag, i)
            out.append((pkb, m, sk.sign(m)))
        return out

    def test_python_progress_during_native_batch(self):
        """The 1-core-safe GIL proof: while a 5k batch runs on a
        worker thread, the main thread must keep executing python —
        with the GIL held through the kernel the counter would stay
        at ~0."""
        native = _native()
        items = self._items(b"gil")
        z = secrets.token_bytes(16 * self.N)
        native.ed25519_batch_verify(items, z)        # warm
        done = threading.Event()
        result = {}

        def run():
            result["ok"] = native.ed25519_batch_verify(items, z)
            done.set()

        t = threading.Thread(target=run)
        t.start()
        ticks = 0
        while not done.is_set():
            ticks += 1
        t.join()
        assert result["ok"] == 1
        # a held GIL yields only the handful of iterations before the
        # kernel grabs it; released, the loop runs millions — 1000 is
        # orders of magnitude above the held case on any host
        assert ticks > 1000, ticks

    def test_two_threads_inside_the_native_call_at_once(self):
        """Two concurrent 5k batches must both be INSIDE the native
        call at the same time: each thread stamps the clock just
        before entering and just after leaving, and the two intervals
        must overlap.  With the GIL held through the kernel the second
        thread could not even take its entry stamp until the first
        had left — the intervals would be disjoint.  No timing ratio:
        on a shared box the wall-clock of overlapped kernels proves
        nothing either way."""
        native = _native()
        work = [(self._items(b"ova"), secrets.token_bytes(16 * self.N)),
                (self._items(b"ovb"), secrets.token_bytes(16 * self.N))]
        for items, z in work:
            native.ed25519_batch_verify(items, z)    # warm
        start = threading.Barrier(2, timeout=30)
        spans = [None, None]

        def run(k):
            items, z = work[k]
            start.wait()
            t_in = time.perf_counter()
            ok = native.ed25519_batch_verify(items, z)
            spans[k] = (t_in, time.perf_counter(), ok)

        ts = [threading.Thread(target=run, args=(k,)) for k in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive()
        assert all(sp is not None and sp[2] == 1 for sp in spans)
        last_in = max(sp[0] for sp in spans)
        first_out = min(sp[1] for sp in spans)
        assert last_in < first_out, spans


# ---------------------------------------------------------------------
# the async seam + the supervised worker

class TestVerifyAsync:
    def test_verify_async_matches_verify(self):
        privs = [ed25519.gen_priv_key() for _ in range(6)]
        bv = ed25519.CpuBatchVerifier()
        for i, p in enumerate(privs):
            sig = p.sign(b"a%d" % i)
            if i == 3:
                sig = bytes([sig[0] ^ 1]) + sig[1:]
            bv.add(p.pub_key(), b"a%d" % i, sig)

        async def go():
            return await bv.verify_async()

        ok, mask = asyncio.run(go())
        assert not ok
        assert mask == [True, True, True, False, True, True]

    def test_traced_wrapper_keeps_async_seam(self):
        from cometbft_tpu.crypto import batch as crypto_batch
        p = ed25519.gen_priv_key()
        bv = crypto_batch.create_batch_verifier(p.pub_key())
        bv.add(p.pub_key(), b"w0", p.sign(b"w0"))
        bv.add(p.pub_key(), b"w1", p.sign(b"w1"))

        async def go():
            return await bv.verify_async()

        ok, mask = asyncio.run(go())
        assert ok and list(mask) == [True, True]

    def test_loop_stays_responsive_during_verify_async(self):
        """The event-loop-stall contract at test scale: a ticker's
        max gap while a 2k batch verifies off-loop must be far below
        the batch's own duration."""
        sk = ed25519.gen_priv_key()
        pkb = sk.pub_key()
        bv = ed25519.CpuBatchVerifier()
        for i in range(2000):
            m = b"stall-%04d" % i
            bv.add(pkb, m, sk.sign(m))

        async def go():
            t0 = time.perf_counter()
            ok, _ = bv.verify()              # sync: measures duration
            sync_s = time.perf_counter() - t0
            assert ok
            max_gap = 0.0
            done = asyncio.Event()

            async def ticker():
                nonlocal max_gap
                last = time.perf_counter()
                while not done.is_set():
                    await asyncio.sleep(0.001)
                    now = time.perf_counter()
                    max_gap = max(max_gap, now - last)
                    last = now

            t = asyncio.ensure_future(ticker())
            await asyncio.sleep(0.02)
            max_gap = 0.0
            ok, _ = await bv.verify_async()
            done.set()
            await t
            assert ok
            return sync_s, max_gap

        sync_s, gap = asyncio.run(go())
        assert gap < max(0.5 * sync_s, 0.02), (sync_s, gap)

    def test_preverify_signatures_async_fills_memo(self):
        from cometbft_tpu.types import vote as vote_mod
        privs = [ed25519.gen_priv_key() for _ in range(4)]
        entries = [(p.pub_key(), b"pv%d" % i, p.sign(b"pv%d" % i))
                   for i, p in enumerate(privs)]
        vote_mod._VERIFIED.clear()

        async def go():
            await asyncio.wrap_future(
                vote_mod.preverify_signatures_async(entries))

        asyncio.run(go())
        for pub, msg, sig in entries:
            assert vote_mod._memo_key(pub, msg, sig) in \
                vote_mod._VERIFIED


class TestSupervisedWorker:
    def test_submit_result_and_metrics(self):
        from cometbft_tpu.libs import metrics as libmetrics
        reg = libmetrics.Registry()
        w = SupervisedWorker("t_basic", registry=reg)
        try:
            assert w.submit(lambda a, b: a + b, 2, 3).result(5) == 5
            # queue-wait histogram observed at least once
            fam = reg.histogram(
                "crypto", "verify_queue_wait_seconds", "",
                labels=("worker",), buckets=(0.001, 1.0))
            assert fam.with_labels("t_basic")._count >= 1
        finally:
            w.stop()

    def test_exception_captured_and_worker_survives(self):
        w = SupervisedWorker("t_crash")
        try:
            fut = w.submit(lambda: 1 / 0)
            with pytest.raises(ZeroDivisionError):
                fut.result(5)
            # the worker thread survived the crash
            assert w.submit(lambda: 41 + 1).result(5) == 42
        finally:
            w.stop()

    def test_stop_drains_queued_tasks(self):
        w = SupervisedWorker("t_drain")
        futs = [w.submit(time.sleep, 0.01) for _ in range(3)]
        last = w.submit(lambda: "done")
        w.stop()
        assert last.result(5) == "done"
        for f in futs:
            assert f.done()
        with pytest.raises(RuntimeError):
            w.submit(lambda: None)

    def test_depth_gauge_returns_to_zero(self):
        w = SupervisedWorker("t_depth")
        try:
            w.submit(time.sleep, 0.02).result(5)
            deadline = time.time() + 2
            while w.depth() and time.time() < deadline:
                time.sleep(0.005)
            assert w.depth() == 0
        finally:
            w.stop()


@pytest.mark.slow
class TestPipelinePartitioner:
    def test_sharded_pipeline_parity_forced_devices(self):
        """4 forced host devices: verify_sharded (now routed through
        the once-per-pipeline PipelinePartitioner) and the tiled JAX
        pipeline must produce exact masks.  Subprocess because
        XLA_FLAGS must be set before jax initializes."""
        import subprocess
        import sys
        code = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import secrets
from cometbft_tpu.crypto import _ed25519_ref as ref
from cometbft_tpu.crypto import pipeline
from cometbft_tpu.ops import ed25519_jax as ej
from cometbft_tpu.parallel import mesh as pmesh
ej.SHARD_MIN = 32
pipeline.TILE = 64
import jax
assert len(jax.devices()) == 4, jax.devices()
items = []
for i in range(130):
    seed = bytes([i]) + secrets.token_bytes(31)
    m = b"shard-%03d" % i
    items.append((ref.public_key(seed), m, ref.sign(seed, m)))
pub, m, s = items[65]
items[65] = (pub, m, s[:6] + bytes([s[6] ^ 1]) + s[7:])
wire, pre_bad = ej.prep_arrays(items, 130)
ok = pmesh.verify_sharded(*ej.wire_views(wire), ndev=4)
assert not ok[65] and ok[:65].all() and ok[66:].all()
ok2, mask = ej.verify_batch(items)       # tiled pipeline, sharded
assert not ok2 and mask.count(False) == 1 and not mask[65]
print("PARITY_OK")
"""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count"
                            "=4").strip()
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=900, env=env)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "PARITY_OK" in proc.stdout


@pytest.mark.slow
class TestPallasUnderShardMap:
    def test_two_virtual_devices_interpret_mode(self, monkeypatch):
        """The kernel `auto` picks on a TPU, sharded: on a host with
        several chips every commit of >= ej.SHARD_MIN lanes
        takes shard_map around pl.pallas_call.  JAX's varying-axes
        check refuses that wrapping (no vma on the kernel's
        out_shape), so the mesh turns the check off for the Pallas
        body — here on two of conftest's virtual CPU devices, in
        interpret mode at a block of 8, against the golden model."""
        from cometbft_tpu.crypto import _ed25519_ref as ref
        from cometbft_tpu.ops import ed25519_jax as ej
        from cometbft_tpu.parallel import mesh as pmesh

        items, golden = [], []
        for i in range(16):
            seed = bytes([i + 1]) * 32
            msg = b"vma-%02d" % i
            sig = ref.sign(seed, msg)
            if i in (2, 9):
                sig = sig[:32] + bytes(32)            # S = 0
            if i == 13:
                msg += b"tampered"
            items.append((ref.public_key(seed), msg, sig))
            golden.append(ref.verify(*items[-1]))
        assert golden.count(False) == 3
        wire, pre_bad = ej.prep_arrays(items, 16)
        ok = pmesh.verify_sharded(*ej.wire_views(wire), ndev=2,
                                  kernel="pallas", interpret=True,
                                  block=8)
        assert ok.tolist() == golden
        # and through the production selection: with the shard floor
        # lowered, _dispatch itself picks the mesh (all 8 devices)
        monkeypatch.setattr(ej, "SHARD_MIN", 1)
        part = ej._partitioner(16, "pallas", True, 8)
        assert part is not None and part.ndev == 8
        mask = ej._dispatch(16, wire, pre_bad, kernel="pallas",
                            interpret=True, block=8)
        assert mask.tolist() == golden


# ---------------------------------------------------------------------
# committed perf-claim gates (static checks on the baseline, the
# test_lightserve pattern: the live regression gate is perf_lab
# `check --fast`; the CLAIM is pinned against the committed numbers)

class TestCommittedClaims:
    @pytest.fixture(scope="class")
    def baseline(self):
        import json
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "perf_baseline.json")
        with open(path) as f:
            return json.load(f)["benchmarks"]

    def test_pipelined_dispatch_claim(self, baseline):
        b = baseline["ed25519_pipelined_dispatch"]
        assert b["monolithic_min_ms"] / b["min_ms"] >= 1.25, b
        # the host_prep/kernel_execute split was live during the
        # committed measurement (both phases observed)
        assert b["host_prep_ms"] > 0 and b["kernel_execute_ms"] > 0

    def test_event_loop_stall_claim(self, baseline):
        b = baseline["verify_event_loop_stall"]
        assert b["sync_stall_ms"] / b["min_ms"] >= 5.0, b
