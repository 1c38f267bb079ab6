"""TPU ed25519 kernel vs the ZIP-215 golden model.

Covers the semantics the reference pins down in crypto/ed25519/ed25519.go:36-44
(ZIP-215: cofactored equation, permissive A/R decoding, canonical-S check)
plus batch/single agreement (ed25519.go:189-222).
"""
import secrets

import numpy as np
import jax.numpy as jnp
import pytest

from cometbft_tpu.crypto import _ed25519_ref as ref
from cometbft_tpu.crypto import pipeline as crypto_pipeline
from cometbft_tpu.ops import ed25519_jax as ej
from cometbft_tpu.ops import field

pytestmark = pytest.mark.kernel


def _sig(msg=None):
    seed = secrets.token_bytes(32)
    msg = secrets.token_bytes(37) if msg is None else msg
    return ref.public_key(seed), msg, ref.sign(seed, msg)


def _small_order_point():
    """Find a small-order point by multiplying a random point by L."""
    while True:
        cand = secrets.token_bytes(32)
        pt = ref.decompress(cand)
        if pt is None:
            continue
        tor = ref.scalar_mult(ref.L, pt)
        if tor != (0, 1):
            return tor


class TestFieldOps:
    def test_mul_add_sub_roundtrip(self):
        rng = np.random.default_rng(0)
        for _ in range(10):
            a = int.from_bytes(rng.bytes(32), "little") % field.P
            b = int.from_bytes(rng.bytes(32), "little") % field.P
            la, lb = jnp.asarray(field.to_limbs(a)), jnp.asarray(field.to_limbs(b))
            assert field.from_limbs(field.mul(la, lb)) == a * b % field.P
            assert field.from_limbs(la + lb) == (a + b) % field.P
            assert field.from_limbs(la - lb) == (a - b) % field.P

    def test_canonical_and_parity(self):
        for v in (0, 1, 2, field.P - 1, 12345):
            lv = jnp.asarray(field.to_limbs(v))
            assert np.array_equal(np.asarray(field.canonical(lv)),
                                  field.to_limbs(v))
            assert int(field.parity(lv)) == v % 2
        # redundant representations of the same value canonicalize equally
        lv = jnp.asarray(field.to_limbs(7)) - jnp.asarray(field.to_limbs(9))
        assert field.from_limbs(field.canonical(lv)) == field.P - 2

    def test_pow_p58(self):
        x = 0xFEDCBA987654321 % field.P
        lx = jnp.asarray(field.to_limbs(x))
        assert field.from_limbs(field.pow_p58(lx)) == pow(
            x, (field.P - 5) // 8, field.P)


class TestVerifyKernel:
    pytestmark = pytest.mark.slow  # cold kernel compile (60-270s on 1 CPU)

    def test_valid_and_corrupted(self):
        items = [_sig() for _ in range(4)]
        pub, msg, sig = items[0]
        flipped_r = bytes([sig[10] ^ 0xFF]) + b""  # corrupt a byte mid-R
        items += [
            (pub, msg, sig[:10] + flipped_r + sig[11:]),
            (pub, b"wrong message", sig),
            (pub, msg, sig[:32] + bytes(32)),          # s = 0
            (pub, msg, bytes([sig[0] ^ 1]) + sig[1:]),
        ]
        golden = [ref.verify(p, m, s) for p, m, s in items]
        ok, mask = ej.verify_batch(items)
        assert mask == golden
        assert golden[:4] == [True] * 4 and golden[4] is False \
            and golden[5] is False and golden[7] is False
        assert ok == all(golden)

    def test_non_canonical_s_rejected(self):
        pub, msg, sig = _sig()
        s = int.from_bytes(sig[32:], "little") + ref.L
        bad = sig[:32] + s.to_bytes(32, "little")
        ok, mask = ej.verify_batch([(pub, msg, bad)])
        assert not ok and mask == [False]
        assert not ref.verify(pub, msg, bad)

    def test_small_order_components_zip215(self):
        """A and R of small order with S=0 verify under ZIP-215 (cofactored)
        for any message — the canonical ZIP-215/RFC-8032 divergence."""
        t1 = _small_order_point()
        t2 = _small_order_point()
        a_bytes = ref.compress(t1)
        r_bytes = ref.compress(t2)
        sig = r_bytes + bytes(32)  # S = 0
        for msg in (b"", b"arbitrary", secrets.token_bytes(100)):
            golden = ref.verify(a_bytes, msg, sig)
            ok, mask = ej.verify_batch([(a_bytes, msg, sig)])
            assert mask == [golden]
            # [8]*small-order == identity, so these must be accepted
            assert golden is True

    def test_non_canonical_y_encoding(self):
        """ZIP-215 accepts y >= p in point encodings; kernel must agree with
        the golden model on such inputs."""
        # encoding of y = p + 1 (same point as y = 1, the identity)
        enc = (field.P + 1).to_bytes(32, "little")
        pt = ref.decompress(enc)
        assert pt == (0, 1)
        # use it as R in a sig: S=0, A small order -> verifies cofactored
        a_bytes = ref.compress(_small_order_point())
        sig = enc + bytes(32)
        golden = ref.verify(a_bytes, b"m", sig)
        ok, mask = ej.verify_batch([(a_bytes, b"m", sig)])
        assert mask == [golden]

    def test_batch_matches_singles_random_mix(self):
        items, golden = [], []
        for i in range(12):
            pub, msg, sig = _sig()
            if i % 3 == 2:
                sig = sig[:32] + secrets.token_bytes(32)
            if i % 4 == 3:
                pub = secrets.token_bytes(32)
            items.append((pub, msg, sig))
            golden.append(ref.verify(pub, msg, sig))
        ok, mask = ej.verify_batch(items)
        assert mask == golden
        assert ok == all(golden)

    def test_empty_batch(self):
        assert ej.verify_batch([]) == (True, [])


class TestBatchVerifierDispatch:
    def test_tpu_verifier_contract(self):
        from cometbft_tpu.crypto import batch, ed25519
        priv = ed25519.gen_priv_key()
        pub = priv.pub_key()
        bv = batch.create_batch_verifier(pub)
        msgs = [secrets.token_bytes(20) for _ in range(5)]
        for m in msgs:
            bv.add(pub, m, priv.sign(m))
        ok, mask = bv.verify()
        assert ok and all(mask) and len(mask) == 5

    def test_tpu_verifier_flags_bad_sig(self, monkeypatch):
        """The verifier the seam serves under backend ``tpu``; a
        kernel fault would fall back to the CPU and still answer
        right, so the breaker must have stayed closed."""
        from cometbft_tpu.crypto import batch, ed25519
        monkeypatch.setattr(batch, "_backend", "tpu")
        batch.reset_tpu_breaker()
        priv = ed25519.gen_priv_key()
        pub = priv.pub_key()
        bv = batch.create_batch_verifier(pub)
        assert type(bv) is batch.GuardedTpuBatchVerifier
        bv.add(pub, b"a", priv.sign(b"a"))
        bv.add(pub, b"b", priv.sign(b"x"))   # wrong message
        bv.add(pub, b"c", priv.sign(b"c"))
        ok, mask = bv.verify()
        assert not ok and mask == [True, False, True]
        assert batch.tpu_breaker().state == "closed"
        batch.reset_tpu_breaker()


def _wire_items():
    """40 fixed items (RFC 8032 signing is deterministic): valid,
    forged, non-canonical S, short key, short signature."""
    items = []
    for i in range(40):
        seed = bytes([i + 1]) * 32
        msg = b"wire-%03d" % i + bytes(i * 7 % 150)
        pub, sig = ref.public_key(seed), ref.sign(seed, msg)
        if i % 7 == 3:          # forged: another R
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        if i % 9 == 4:          # non-canonical S
            s = int.from_bytes(sig[32:], "little") + ref.L
            sig = sig[:32] + s.to_bytes(32, "little")
        if i % 13 == 6:         # short key
            pub = pub[:31]
        if i == 20:             # short signature
            sig = sig[:63]
        items.append((pub, msg, sig))
    return items


def _four_arrays_item_by_item(items, m):
    """What the four-array prep before the packed wire returned, one
    item at a time from the golden model."""
    a_b = np.tile(np.frombuffer(ej._B_BYTES, np.uint8), (m, 1))
    r_b = np.tile(np.frombuffer(ej._IDENTITY_BYTES, np.uint8), (m, 1))
    s_w8 = np.zeros((m, 64), np.uint8)
    k_w8 = np.zeros((m, 64), np.uint8)
    pre_bad = np.zeros(m, bool)

    def windows(v):
        return [(v >> (4 * w)) & 0xF for w in range(64)]

    for i, (pub, msg, sig) in enumerate(items):
        s = int.from_bytes(sig[32:], "little")
        if len(pub) != 32 or len(sig) != 64 or s >= ref.L:
            pre_bad[i] = True
            continue
        a_b[i] = np.frombuffer(pub, np.uint8)
        r_b[i] = np.frombuffer(sig[:32], np.uint8)
        s_w8[i] = windows(s)
        k_w8[i] = windows(ref.sha512_mod_l(sig[:32], pub, msg))
    return a_b, r_b, s_w8, k_w8, pre_bad


@pytest.fixture(params=["native", "numpy"])
def prep_path(request, monkeypatch):
    """Both fillers of the wire buffer: the C pass and the numpy
    fallback."""
    from cometbft_tpu.crypto import _native_loader
    if request.param == "native":
        if _native_loader.load() is None:
            pytest.skip("no compiler for the native module")
    else:
        monkeypatch.setenv("COMETBFT_TPU_NATIVE", "0")
        monkeypatch.setattr(_native_loader, "_mod", None)
    return request.param


class TestPackedWire:
    """One buffer a dispatch: prep_arrays fills [m, 192] uint8 in
    place, a lane a row of A | R | S windows | k windows."""

    # sha256 of each array the four-array prep_arrays of commit
    # d3525d2 (PR 24) returned for _wire_items() padded to 64 lanes
    PARENT_SHA256 = {
        "a_b": "9dec0743cf10186b507730b5a0b0be87"
               "e1e0f6ac1710d07af31f3d1c0de82f78",
        "r_b": "c5f41624756ab2365e28c8b270f07dac"
               "a4c8b190f2dbf731e8c9d8321ebf27cd",
        "s_w8": "000cbf3761e86093f74765930373b237"
                "56f54ad249af784dafeaa72a215ffdc2",
        "k_w8": "317b84cac0fd92d29d6140a51bdf3624"
                "4b6bed39c308831693f46dbc81e0d83c",
        "pre_bad": "9a10fd2df8a1b85280124da22e92172e"
                   "15cffb1750bc1e56af2ebd91394bd795",
    }

    def test_one_contiguous_buffer(self, prep_path):
        wire, pre_bad = ej.prep_arrays(_wire_items(), 64)
        assert wire.shape == (64, ej.WIRE_LANE_BYTES)
        assert wire.dtype == np.uint8 and wire.flags.c_contiguous
        assert pre_bad.shape == (64,) and pre_bad.dtype == bool
        # the four arrays are views of it: nothing was concatenated
        for view in ej.wire_views(wire):
            assert np.shares_memory(view, wire)

    def test_views_are_what_the_four_array_prep_returned(
            self, prep_path):
        import hashlib
        items = _wire_items()
        wire, pre_bad = ej.prep_arrays(items, 64)
        got = dict(zip(("a_b", "r_b", "s_w8", "k_w8", "pre_bad"),
                       ej.wire_views(wire) + (pre_bad,)))
        want = dict(zip(got, _four_arrays_item_by_item(items, 64)))
        for name, arr in got.items():
            assert arr.shape == want[name].shape, name
            assert np.array_equal(arr, want[name]), name
            digest = hashlib.sha256(
                np.ascontiguousarray(arr).tobytes()).hexdigest()
            assert digest == self.PARENT_SHA256[name], name
        assert np.flatnonzero(pre_bad).tolist() == \
            [4, 6, 13, 19, 20, 22, 31, 32]

    def test_padding_and_refused_lanes_hold_b_and_identity(
            self, prep_path):
        wire, pre_bad = ej.prep_arrays(_wire_items(), 64)
        a_b, r_b, s_w8, k_w8 = ej.wire_views(wire)
        idle = np.r_[np.flatnonzero(pre_bad), 40:64]
        assert (a_b[idle] == np.frombuffer(ej._B_BYTES,
                                           np.uint8)).all()
        assert (r_b[idle] == np.frombuffer(ej._IDENTITY_BYTES,
                                           np.uint8)).all()
        assert not s_w8[idle].any() and not k_w8[idle].any()
        assert np.array_equal(wire[40:], ej._padding_wire(24))
        # forged lanes are not refused on the host: the kernel decides
        assert not pre_bad[[3, 10, 17, 24, 38]].any()


class TestOneTransferADispatch:
    """Every single-device dispatch hands the runtime one buffer: a
    transfer costs the same ~0.27 ms on a v5e whatever its size, so
    the count is what is paid for (PERF.md, PR 26).  The kernels are
    stubbed (their compile takes minutes on a CPU); prep, _launch and
    the pipeline around them are the real ones."""

    @pytest.fixture
    def counted(self, monkeypatch):
        import jax
        puts, calls = [], []
        real_put = jax.device_put

        def device_put(x, *args, **kw):
            puts.append(np.shape(x))
            return real_put(x, *args, **kw)

        def stub(*operands, **static):
            calls.append(operands)
            return jnp.ones(operands[0].shape[0], dtype=bool)

        monkeypatch.setattr(ej.jax, "device_put", device_put)
        # conftest's eight virtual devices would send 1,024 lanes to
        # the mesh partitioner: one chip is what a cell runs on
        monkeypatch.setattr(ej, "SHARD_MIN", 1000000)
        for name in ("_jit_verify_packed", "_pallas_verify_packed",
                     "_pallas_batch_verify_packed"):
            monkeypatch.setattr(ej, name, stub)
        return puts, calls

    @staticmethod
    def _commit(n):
        pub, msg, sig = _sig()
        return [(pub, msg, sig)] * n

    @pytest.mark.parametrize("kernel", ["xla", "pallas"])
    def test_verify_batch_of_175(self, counted, monkeypatch, kernel):
        import jax
        puts, calls = counted
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", kernel)
        ok, mask = ej.verify_batch(self._commit(175))
        assert ok and len(mask) == 175
        # at the tile's shape the Pallas wire carries the batch
        # equation's columns too: still one buffer
        width = ej.batch_lane_bytes(1024) if kernel == "pallas" \
            else ej.WIRE_LANE_BYTES
        assert puts == [(1024, width)]
        # and nothing rides in beside it: one operand, on the device
        (operands,) = calls
        assert len(operands) == 1
        assert isinstance(operands[0], jax.Array)
        assert operands[0].shape == (1024, width)

    def test_each_tile_of_the_pipelined_path(self, counted,
                                             monkeypatch):
        import jax
        puts, calls = counted
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
        monkeypatch.setattr(crypto_pipeline, "TILE", 64)
        ok, mask = ej.verify_batch(self._commit(150))
        assert ok and len(mask) == 150
        assert puts == [(64, ej.WIRE_LANE_BYTES)] * 3
        assert [len(operands) for operands in calls] == [1, 1, 1]
        assert all(isinstance(operands[0], jax.Array)
                   for operands in calls)

    def test_warmup_sends_the_shape_the_live_path_sends(
            self, counted, monkeypatch):
        puts, _ = counted
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
        ej._warmup_bucket.cache_clear()
        try:
            ej.warmup(175)
        finally:
            ej._warmup_bucket.cache_clear()
        assert puts == [(1024, ej.WIRE_LANE_BYTES)]


class TestFrameRoomForColdShapes:
    """A shape's first call traces and lowers ~43,000 equations in
    Python; _launch makes it on a frame-stack chunk of its own so the
    set-up time does not depend on where the caller's stack happens to
    cross one of CPython's 16 KiB chunks (PERF.md, PR 26)."""

    @staticmethod
    def _loop_at_depth(depth, call, n=20000):
        import time

        def leaf(a, b):
            return a

        def hot():
            t0 = time.perf_counter()
            for _ in range(n):
                leaf(1, 2)
            return time.perf_counter() - t0

        def rec(d):
            return call(hot) if d == 0 else rec(d - 1)

        return rec(depth)

    def test_a_loop_on_a_chunk_boundary_is_spared(self):
        plain = sorted((self._loop_at_depth(d, lambda f: f()), d)
                       for d in range(300))
        median = plain[len(plain) // 2][0]
        worst_s, worst_depth = plain[-1]
        if worst_s < 20 * median:
            pytest.skip("this interpreter shows no chunk-boundary "
                        "cliff to be spared")
        roomy = self._loop_at_depth(worst_depth, ej._with_frame_room)
        assert roomy < worst_s / 10, (worst_depth, worst_s, roomy)

    def test_only_a_cold_shape_is_called_with_room(self, monkeypatch):
        roomy, direct = [], []
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
        monkeypatch.setattr(
            ej, "_jit_verify_packed",
            lambda dw: direct.append(dw.shape) or
            jnp.ones(dw.shape[0], dtype=bool))
        real = ej._with_frame_room
        monkeypatch.setattr(
            ej, "_with_frame_room",
            lambda fn, *a, **kw: roomy.append(a[0].shape) or
            real(fn, *a, **kw))
        monkeypatch.setattr(ej, "_SEEN_SHAPES", set())
        items = [_sig()] * 3
        for _ in range(3):
            ok, _ = ej.verify_batch(items)
            assert ok
        assert roomy == [(64, ej.WIRE_LANE_BYTES)]
        assert direct == [(64, ej.WIRE_LANE_BYTES)] * 3

    def test_passes_arguments_and_result_through(self):
        assert ej._with_frame_room(
            lambda a, b=0, *, c=0: (a, b, c), 1, 2, c=3) == (1, 2, 3)
        with pytest.raises(ZeroDivisionError):
            ej._with_frame_room(lambda: 1 // 0)


class TestPackedEntryPoints:
    pytestmark = pytest.mark.slow  # cold kernel compile (60-270s on 1 CPU)

    """The jitted functions of one wire argument give the golden
    model's verdicts, lane for lane."""

    @pytest.mark.parametrize("kernel", ["xla", "pallas"])
    def test_forged_non_canonical_and_short_key(self, kernel):
        import jax
        items = _wire_items()[:8]
        # 3: forged R, 4: non-canonical S, 6: short key
        golden = [len(p) == 32 and ref.verify(p, m, s)
                  for p, m, s in items]
        assert golden == [True, True, True, False, False, True,
                          False, True]
        wire, pre_bad = ej.prep_arrays(items, 8)
        dw = jax.device_put(wire)
        if kernel == "xla":
            ok = ej._jit_verify_packed(dw)
        else:
            ok = ej._pallas_verify_packed(dw, interpret=True, block=8)
        ok = np.asarray(ok)
        # refused lanes run as padding lanes, which verify trivially;
        # the host's pre_bad is what fails them
        assert ok[pre_bad].all()
        assert (ok & ~pre_bad).tolist() == golden
        # and the same through the dispatch every caller takes
        mask = ej._dispatch(8, wire, pre_bad, kernel=kernel,
                            interpret=kernel == "pallas",
                            block=8 if kernel == "pallas" else 0)
        assert mask.tolist() == golden


class TestShardedTally:
    pytestmark = pytest.mark.slow  # cold kernel compile (60-270s on 1 CPU)

    def test_verify_tally_over_mesh(self):
        import jax
        from cometbft_tpu.parallel import mesh as pmesh
        ndev = len(jax.devices())
        mesh = pmesh.make_mesh(ndev)
        step = pmesh.sharded_verify_tally(mesh)
        n = 2 * ndev
        a = np.zeros((n, 32), np.uint8)
        r = np.zeros((n, 32), np.uint8)
        s_raw = np.zeros((n, 32), np.uint8)
        k_raw = np.zeros((n, 32), np.uint8)
        golden = []
        for i in range(n):
            pub, msg, sig = _sig()
            if i % 3 == 0:
                sig = sig[:32] + (1).to_bytes(32, "little")  # bad S
            a[i] = np.frombuffer(pub, np.uint8)
            r[i] = np.frombuffer(sig[:32], np.uint8)
            s_raw[i] = np.frombuffer(sig[32:], np.uint8)
            k = ref.sha512_mod_l(sig[:32], pub, msg)
            k_raw[i] = np.frombuffer(k.to_bytes(32, "little"), np.uint8)
            golden.append(ref.verify(pub, msg, sig))
        ok, count = step(jnp.asarray(a), jnp.asarray(r),
                         jnp.asarray(ej._windows_u8(s_raw)),
                         jnp.asarray(ej._windows_u8(k_raw)))
        assert list(np.asarray(ok)) == golden
        assert int(count) == sum(golden)


def _pallas_verify_items(items, block=8):
    """Run the Pallas kernel in interpret mode through the production
    prep + dispatch path (ops/ed25519_jax.py), with a small block so
    the emulated kernel stays tractable."""
    n = len(items)
    m = -(-n // block) * block
    wire, pre_bad = ej.prep_arrays(items, m)
    return ej._dispatch(n, wire, pre_bad, kernel="pallas",
                        interpret=True, block=block).tolist()


class TestPallasKernel:
    pytestmark = pytest.mark.slow  # cold kernel compile (60-270s on 1 CPU)

    """Interpret-mode parity of the fused Mosaic kernel
    (ops/ed25519_pallas.py) against the ZIP-215 golden model — the
    same semantics the XLA-kernel suite above pins down
    (reference: crypto/ed25519/ed25519.go:36-44)."""

    def test_valid_and_corrupted(self):
        items = [_sig() for _ in range(3)]
        pub, msg, sig = items[0]
        items += [
            (pub, msg, sig[:10] + bytes([sig[10] ^ 0xFF]) + sig[11:]),
            (pub, b"wrong message", sig),
            (pub, msg, sig[:32] + bytes(32)),          # s = 0
            (pub, msg, bytes([sig[0] ^ 1]) + sig[1:]),
        ]
        golden = [ref.verify(p, m, s) for p, m, s in items]
        assert _pallas_verify_items(items) == golden
        assert golden[:3] == [True] * 3
        assert golden[3:] == [False] * 4

    def test_non_canonical_s_rejected(self):
        pub, msg, sig = _sig()
        s = int.from_bytes(sig[32:], "little") + ref.L
        bad = sig[:32] + s.to_bytes(32, "little")
        assert _pallas_verify_items([(pub, msg, bad)]) == [False]
        assert not ref.verify(pub, msg, bad)

    def test_small_order_components_zip215(self):
        t1, t2 = _small_order_point(), _small_order_point()
        a_bytes, r_bytes = ref.compress(t1), ref.compress(t2)
        sig = r_bytes + bytes(32)  # S = 0
        for msg in (b"", b"arbitrary"):
            golden = ref.verify(a_bytes, msg, sig)
            assert _pallas_verify_items([(a_bytes, msg, sig)]) == \
                [golden]
            assert golden is True  # cofactored: must accept

    def test_non_canonical_y_encoding(self):
        enc = (field.P + 1).to_bytes(32, "little")  # y=p+1 == identity
        assert ref.decompress(enc) == (0, 1)
        a_bytes = ref.compress(_small_order_point())
        sig = enc + bytes(32)
        golden = ref.verify(a_bytes, b"m", sig)
        assert _pallas_verify_items([(a_bytes, b"m", sig)]) == [golden]

    def test_batch_matches_singles_random_mix(self):
        items, golden = [], []
        for i in range(10):
            pub, msg, sig = _sig()
            if i % 3 == 2:
                sig = sig[:32] + secrets.token_bytes(32)
            if i % 4 == 3:
                pub = secrets.token_bytes(32)
            items.append((pub, msg, sig))
            golden.append(ref.verify(pub, msg, sig))
        assert _pallas_verify_items(items) == golden

    def test_padding_lanes_verify_trivially(self):
        # 1 real item in an 8-lane block: the 7 padding lanes must not
        # disturb the real lane's verdict
        pub, msg, sig = _sig()
        assert _pallas_verify_items([(pub, msg, sig)]) == [True]

    def test_agrees_with_xla_kernel(self, monkeypatch):
        """Both kernels consume identical prepped arrays; their
        verdicts must be bit-identical on a mixed batch."""
        # pin the dispatch so this really is pallas-vs-XLA even on a
        # TPU host (where _kernel_choice defaults to pallas)
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
        items = []
        for i in range(8):
            pub, msg, sig = _sig()
            if i % 2:
                sig = sig[:32] + secrets.token_bytes(32)
            items.append((pub, msg, sig))
        golden = [ref.verify(p, m, s) for p, m, s in items]
        assert _pallas_verify_items(items) == golden
        _, xla_mask = ej.verify_batch(items)
        assert xla_mask == golden


class TestMultiChipDispatch:
    pytestmark = pytest.mark.slow  # cold kernel compile (60-270s on 1 CPU)

    def test_verify_batch_auto_shards_with_mixed_lanes(
            self, monkeypatch):
        """The PRODUCTION dispatch (verify_batch -> _dispatch) must
        auto-shard over the virtual 8-device mesh and return the exact
        per-lane mask for a mixed valid/invalid batch — the same code
        path a node runs, not a dryrun-only seam."""
        import jax
        assert len(jax.devices()) == 8, "conftest mesh missing"
        monkeypatch.setattr(ej, "SHARD_MIN", 1)
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "xla")
        items, golden = [], []
        for i in range(12):
            pub, msg, sig = _sig()
            if i % 3 == 1:
                sig = sig[:32] + bytes(32)            # S = 0
            if i % 4 == 3:
                msg = msg + b"tampered"
            items.append((pub, msg, sig))
            golden.append(ref.verify(pub, msg, sig))
        ok, mask = ej.verify_batch(items)
        assert mask == golden
        assert ok == all(golden)
        # malformed input lanes are masked before/after the mesh too
        items.append((b"short", b"m", b"also-short"))
        golden.append(False)
        ok, mask = ej.verify_batch(items)
        assert mask == golden


class TestPallasMultiBlock:
    pytestmark = pytest.mark.slow  # cold kernel compile (60-270s on 1 CPU)

    def test_grid_of_two_blocks(self):
        """A batch spanning two grid steps (n=16, block=8) must
        produce the same per-lane verdicts — exercises the BlockSpec
        index maps and the per-block VMEM scratch reset, which a
        single-block run never touches."""
        items, golden = [], []
        for i in range(16):
            pub, msg, sig = _sig()
            if i in (3, 11):
                sig = sig[:32] + bytes(32)            # S = 0
            items.append((pub, msg, sig))
            golden.append(ref.verify(pub, msg, sig))
        assert _pallas_verify_items(items, block=8) == golden
        assert golden[3] is False and golden[11] is False
