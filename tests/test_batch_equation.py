"""The tile's batch equation: [8](S·B - Σ z·R - Σ c·A) == identity on
the device (ops/ed25519_pallas._batch_kernel, behind
ops/ed25519_jax._pallas_batch_verify_packed), its columns of the host
prep (native/_native.cpp, their one producer), and the routing by the
dispatch's shape (crypto/pipeline.batch_equation).

The kernel runs in interpret mode at a block of 8 lanes, four grid
steps, one compile of the entry (batch kernel and per-lane fallback)
shared by every test of the file that dispatches at that shape.
"""
import secrets

import jax.numpy as jnp
import numpy as np
import pytest

from cometbft_tpu.crypto import _ed25519_ref as ref
from cometbft_tpu.crypto import pipeline as crypto_pipeline
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.ops import ed25519_jax as ej
from cometbft_tpu.ops import field

M, BLOCK = 32, 8
FAMILY = "cometbft_crypto_batch_equation_total"


def _sig(seed=None, msg=None):
    seed = secrets.token_bytes(32) if seed is None else seed
    msg = secrets.token_bytes(37) if msg is None else msg
    return ref.public_key(seed), msg, ref.sign(seed, msg)


def _honest(n):
    return [_sig(bytes([i + 1]) * 32, b"tile-%03d" % i) for i in range(n)]


def _small_order_point():
    """A point of small order: a random point times L, until it is not
    the identity."""
    while True:
        pt = ref.decompress(secrets.token_bytes(32))
        if pt is not None:
            tor = ref.scalar_mult(ref.L, pt)
            if tor != (0, 1):
                return tor


def _undecodable():
    while True:
        enc = secrets.token_bytes(32)
        if ref.decompress(enc) is None:
            return enc


def _outcomes():
    got = {}
    for line in libmetrics.DEFAULT.render().splitlines():
        if line.startswith(FAMILY + "{"):
            outcome = line.split('outcome="', 1)[1].split('"', 1)[0]
            got[outcome] = float(line.rsplit(" ", 1)[1])
    return got


def _counted(fn):
    before = _outcomes()
    out = fn()
    after = _outcomes()
    return out, {k: v - before.get(k, 0.0) for k, v in after.items()
                 if v != before.get(k, 0.0)}


def _entry(wire):
    """The batch-equation entry on a wire: ([m] mask, held)."""
    out = np.asarray(ej._pallas_batch_verify_packed(
        jnp.asarray(wire), interpret=True, block=BLOCK))
    return out[:wire.shape[0]], bool(out[wire.shape[0]])


def _need_native():
    from cometbft_tpu.crypto import _native_loader
    if _native_loader.load() is None:
        pytest.skip("no compiler for the native module")


def _dispatch(items):
    """The production prep and settle around the entry, at M lanes."""
    _need_native()
    wire, pre_bad = ej.prep_arrays(items, M, True)
    assert wire.shape == (M, ej.batch_lane_bytes(M))
    return ej._dispatch(len(items), wire, pre_bad, kernel="pallas",
                        interpret=True, block=BLOCK).tolist()


def _digits(v: int, n: int) -> list:
    """Signed radix-16 digits in [-8, 7], low first."""
    out, carry = [], 0
    for i in range(n):
        d = ((v >> (4 * i)) & 0xF) + carry
        carry = int(d >= 8)
        out.append(d - 16 * carry)
    assert carry == 0
    return out


def _value(row) -> int:
    return sum(int(np.int8(d)) << (4 * i) for i, d in enumerate(row))


def _wire_with(items, zs):
    """A batch-equation wire whose z the test chose: the plain prep's
    192 columns, then c = z·k mod L, z and S = Σ z·s mod L."""
    plain, pre_bad = ej.prep_arrays(items, M)
    wire = np.zeros((M, ej.batch_lane_bytes(M)), np.uint8)
    wire[:, :ej.WIRE_LANE_BYTES] = plain
    c8, z8, sd8 = ej.batch_views(wire)
    s_sum = 0
    for i, ((pub, msg, sig), z) in enumerate(zip(items, zs)):
        assert not pre_bad[i]
        k = ref.sha512_mod_l(sig[:32], pub, msg)
        s = int.from_bytes(sig[32:], "little")
        c8[i] = np.array(_digits(z * k % ref.L, 64), np.int8).view(np.uint8)
        z8[i] = np.array(_digits(z, 33), np.int8).view(np.uint8)
        s_sum += z * s
    flat = np.zeros(sd8.size, np.uint8)
    flat[:64] = np.array(_digits(s_sum % ref.L, 64),
                         np.int8).view(np.uint8)
    sd8[:] = flat.reshape(sd8.shape)
    return wire


class TestKernelParity:
    """Interpret-mode parity with the golden model, four grid steps."""

    def test_an_honest_tile_holds_and_every_lane_is_true(self):
        items = _honest(29)
        mask, counted = _counted(lambda: _dispatch(items))
        assert mask == [True] * 29
        assert counted == {"held": 1.0}

    @pytest.mark.parametrize("lane", [2, 13, 30],
                             ids=["first_block", "middle_block",
                                  "last_block"])
    def test_a_forged_lane_fails_the_tile_and_the_fallback_names_it(
            self, lane):
        items = _honest(31)
        pub, msg, sig = items[lane]
        items[lane] = (pub, msg + b"!", sig)
        golden = [ref.verify(*it) for it in items]
        mask, counted = _counted(lambda: _dispatch(items))
        assert mask == golden
        assert [i for i, ok in enumerate(mask) if not ok] == [lane]
        assert counted == {"failed": 1.0}

    def test_zip215_edges_and_refused_lanes_hold(self):
        """Lanes the equation holds over (small-order A and R, a
        non-canonical y) and lanes the host refuses (S >= L, a short
        key: zero digits, out of S), padding after them: the tile
        holds, and its mask is the golden model's, lane by lane."""
        items = _honest(24)
        small_a = ref.compress(_small_order_point())
        small_r = ref.compress(_small_order_point())
        items.append((small_a, b"", small_r + bytes(32)))
        items.append((small_a, b"arbitrary", small_r + bytes(32)))
        p_plus_1 = (field.P + 1).to_bytes(32, "little")
        items.append((small_a, b"m", p_plus_1 + bytes(32)))
        pub, msg, sig = _sig()
        s = int.from_bytes(sig[32:], "little") + ref.L
        items.append((pub, msg, sig[:32] + s.to_bytes(32, "little")))
        items.append((pub[:31], msg, sig))
        golden = [ref.verify(*it) for it in items]
        assert golden[24:] == [True, True, True, False, False]
        mask, counted = _counted(lambda: _dispatch(items))
        assert mask == golden
        assert counted == {"held": 1.0}

    @pytest.mark.parametrize("point", ["A", "R"])
    def test_an_undecodable_point_leaves_its_lane_false(self, point):
        """A lane whose A or R has no curve point adds no term (its
        digits are masked on the device) and is false in the mask,
        whichever way the equation goes: with S = 0 its z·s is not in
        S either and the tile holds; with S != 0 that term has nothing
        to cancel, the tile fails and the per-lane kernel decides."""
        items = _honest(20)
        pub, msg, sig = _sig()
        for s in (bytes(32), sig[32:]):
            lane = (_undecodable(), msg, sig[:32] + s) if point == "A" \
                else (pub, msg, _undecodable() + s)
            golden = [True] * 20 + [False]
            mask, counted = _counted(lambda: _dispatch(items + [lane]))
            assert mask == golden
            assert counted == {"held" if s == bytes(32) else "failed": 1.0}

    @pytest.mark.parametrize("forged", [False, True])
    def test_the_verdict_is_the_golden_batch_equations_under_one_z(
            self, forged, monkeypatch):
        """With z chosen by the test, the device's equation holds where
        crypto/_ed25519_ref.batch_verify's does: it holds when that
        one falls back to no single verification."""
        items = _honest(M)
        if forged:
            pub, msg, sig = items[17]
            items[17] = (pub, msg, sig[:40] + bytes([sig[40] ^ 4])
                         + sig[41:])
        zs = [secrets.randbits(128) | 1 for _ in items]
        singles = []
        real_verify = ref.verify
        monkeypatch.setattr(ref, "verify", lambda *it: singles.append(1)
                            or real_verify(*it))
        ok, per = ref.batch_verify(items, rand_fn=iter(zs).__next__)
        golden_held = not singles
        assert golden_held == (not forged)
        mask, held = _entry(_wire_with(items, zs))
        assert held == golden_held
        assert mask.tolist() == per


class TestHostPrep:
    """The batch equation's columns, from the native prep."""

    @pytest.fixture
    def native(self):
        _need_native()

    @staticmethod
    def _without_native(monkeypatch):
        from cometbft_tpu.crypto import _native_loader
        monkeypatch.setenv("COMETBFT_TPU_NATIVE", "0")
        monkeypatch.setattr(_native_loader, "_mod", None)

    @staticmethod
    def _items():
        items = _honest(40)
        pub, msg, sig = items[5]
        s = int.from_bytes(sig[32:], "little") + ref.L
        items[5] = (pub, msg, sig[:32] + s.to_bytes(32, "little"))
        items[9] = (b"short", b"m", b"sig")
        items[11] = (items[11][0], items[11][1] * 40, items[11][2])
        return items

    @pytest.mark.parametrize("m", [64, 1024])
    def test_c_and_s_are_pythons_arithmetic(self, native, m):
        items = self._items()
        wire, pre_bad = ej.prep_arrays(items, m, True)
        assert wire.shape == (m, ej.batch_lane_bytes(m))
        c8, z8, sd8 = ej.batch_views(wire)
        s_sum = 0
        for i, (pub, msg, sig) in enumerate(items):
            if pre_bad[i]:
                assert not c8[i].any() and not z8[i].any()
                continue
            z = _value(z8[i])
            assert z & 1 and 0 < z < 1 << 128
            assert [int(np.int8(d)) for d in z8[i]] == _digits(z, 33)
            k = ref.sha512_mod_l(sig[:32], pub, msg)
            assert _value(c8[i]) == z * k % ref.L
            assert [int(np.int8(d)) for d in c8[i]] == \
                _digits(z * k % ref.L, 64)
            s_sum += z * int.from_bytes(sig[32:], "little")
        assert pre_bad[[5, 9]].all()
        assert not c8[len(items):].any() and not z8[len(items):].any()
        flat = sd8.reshape(-1)
        assert _value(flat[:64]) == s_sum % ref.L
        assert not flat[64:].any()

    def test_two_preps_of_the_same_items_draw_different_z(self, native):
        items = self._items()
        one, _ = ej.prep_arrays(items, 64, True)
        two, _ = ej.prep_arrays(items, 64, True)
        z_one, z_two = ej.batch_views(one)[1], ej.batch_views(two)[1]
        drawn = [i for i in range(len(items)) if z_one[i].any()]
        assert len(drawn) == len(items) - 2
        assert all((z_one[i] != z_two[i]).any() for i in drawn)
        assert len({z_one[i].tobytes() for i in drawn}) == len(drawn)

    @pytest.mark.parametrize("plain_from", ["native", "numpy"])
    def test_below_the_tile_the_wire_is_the_plain_one(
            self, native, monkeypatch, plain_from):
        """The batch wire's first 192 columns are the plain wire, byte
        for byte, whichever prep filled the plain one."""
        items = self._items()
        batch, pre_bad_b = ej.prep_arrays(items, 64, True)
        if plain_from == "numpy":
            self._without_native(monkeypatch)
        plain, pre_bad = ej.prep_arrays(items, 64)
        assert plain.shape == (64, ej.WIRE_LANE_BYTES)
        assert (batch[:, :ej.WIRE_LANE_BYTES] == plain).all()
        assert (pre_bad == pre_bad_b).all()

    def test_the_streamed_prep_carries_the_same_columns(self, native):
        items = self._items()
        wire, pre_bad, _, _, _ = ej._prep_begin(items, 64, True).result()
        wire, pre_bad = ej._wire_arrays(wire, pre_bad, 64)
        c8, z8, _ = ej.batch_views(wire)
        pub, msg, sig = items[0]
        k = ref.sha512_mod_l(sig[:32], pub, msg)
        assert _value(c8[0]) == _value(z8[0]) * k % ref.L
        plain, _ = ej.prep_arrays(items, 64)
        assert (wire[:, :ej.WIRE_LANE_BYTES] == plain).all()

    def test_without_the_native_prep_the_tile_keeps_the_per_lane_wire(
            self, monkeypatch):
        """The numpy prep writes no batch columns: a host without the
        native module never routes a tile to the batch equation."""
        self._without_native(monkeypatch)
        monkeypatch.setattr(ej, "SHARD_MIN", 1 << 30)
        m = crypto_pipeline.tile_bucket()
        assert not ej._batched(m, "pallas")
        with pytest.raises(RuntimeError):
            ej.prep_arrays(self._items(), 64, True)
        wire, _ = ej.prep_arrays(self._items(), 64)
        assert wire.shape == (64, ej.WIRE_LANE_BYTES)


class TestRouting:
    """Which entry a dispatch takes follows its padded lane count, and
    the counter says what settled it.  The kernels are stubbed."""

    @pytest.fixture
    def entries(self, monkeypatch):
        from cometbft_tpu.ops import ed25519_pallas as ep
        calls = []
        held = {"bit": True}

        def per_lane(wire, **static):
            calls.append(("per_lane", wire.shape))
            return jnp.ones(wire.shape[0], dtype=bool)

        def batch(wire, **static):
            calls.append(("batch", wire.shape))
            return jnp.concatenate([jnp.ones(wire.shape[0], dtype=bool),
                                    jnp.asarray([held["bit"]])])

        monkeypatch.setattr(ej, "_pallas_verify_packed", per_lane)
        monkeypatch.setattr(ej, "_pallas_batch_verify_packed", batch)
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "pallas")
        monkeypatch.setattr(ep, "BLOCK", 8)
        monkeypatch.setattr(ej, "SHARD_MIN", 1000000)
        monkeypatch.setattr(crypto_pipeline, "TILE", 64)
        monkeypatch.setattr(ej, "_SEEN_SHAPES", set(ej._SEEN_SHAPES))
        saved = list(ej._BUCKETS)
        ej._BUCKETS[:] = [16, 64, 1024]
        yield calls, held
        ej._BUCKETS[:] = saved

    def test_the_tile_shape_takes_the_batch_entry(self, entries):
        calls, held = entries
        items = _honest(1) * 150
        assert crypto_pipeline.tile_bucket() == 64
        assert crypto_pipeline.batch_equation(64)
        assert not crypto_pipeline.batch_equation(16)
        (ok, mask), counted = _counted(lambda: ej.verify_batch(items[:10]))
        assert ok and calls == [("per_lane", (16, ej.WIRE_LANE_BYTES))]
        assert counted == {"bypassed": 1.0}
        calls.clear()
        (ok, mask), counted = _counted(lambda: ej.verify_batch(items[:50]))
        assert ok and calls == [("batch", (64, ej.batch_lane_bytes(64)))]
        assert counted == {"held": 1.0}
        calls.clear()
        held["bit"] = False
        (ok, mask), counted = _counted(lambda: ej.verify_batch(items))
        assert ok and len(mask) == 150
        assert calls == [("batch", (64, ej.batch_lane_bytes(64)))] * 3
        assert counted == {"failed": 3.0}

    def test_the_xla_kernel_and_the_mesh_keep_the_per_lane_path(
            self, entries, monkeypatch):
        calls, _ = entries
        assert ej._batched(64, "pallas")
        assert not ej._batched(64, "xla")
        assert not ej._batched(16, "pallas")
        monkeypatch.setattr(ej, "SHARD_MIN", 1)
        assert not ej._batched(64, "pallas")

    def test_warmup_of_the_tile_compiles_the_batch_entry(self, entries):
        calls, _ = entries
        ej._warmup_bucket.cache_clear()
        try:
            (_, counted) = _counted(lambda: ej.warmup(10_000))
        finally:
            ej._warmup_bucket.cache_clear()
        assert calls == [("batch", (64, ej.batch_lane_bytes(64)))]
        assert counted == {}
