"""C++ fast-path module (native/_native.cpp): build, correctness
against the pure-Python implementations, and fallback behavior."""
import hashlib
import secrets

import pytest

from cometbft_tpu.crypto import _native_loader, merkle


def _native():
    mod = _native_loader.load()
    if mod is None:
        pytest.skip("no compiler available")
    return mod


class TestNative:
    def test_sha256_parity(self):
        native = _native()
        for n in [0, 1, 55, 56, 63, 64, 65, 119, 120, 1000, 65537]:
            d = secrets.token_bytes(n)
            assert native.sha256(d) == hashlib.sha256(d).digest(), n

    def test_sha256_many(self):
        native = _native()
        items = [secrets.token_bytes(i * 13 % 300) for i in range(40)]
        cat = native.sha256_many(items)
        assert len(cat) == 40 * 32
        for i, m in enumerate(items):
            assert cat[i * 32:(i + 1) * 32] == \
                hashlib.sha256(m).digest()

    def test_merkle_root_parity(self):
        native = _native()
        for n in [0, 1, 2, 3, 5, 7, 8, 9, 64, 100, 257]:
            items = [secrets.token_bytes(30 + i % 70)
                     for i in range(n)]
            want = _py_root(items)
            assert native.merkle_root(items) == want, f"n={n}"
            assert merkle.hash_from_byte_slices(items) == want

    def test_leaf_hashes(self):
        native = _native()
        items = [b"a", b"bb", b"ccc"]
        cat = native.leaf_hashes(items)
        for i, it in enumerate(items):
            assert cat[i * 32:(i + 1) * 32] == merkle.leaf_hash(it)

    def test_proofs_still_verify_against_native_root(self):
        native = _native()
        items = [secrets.token_bytes(50) for _ in range(33)]
        root, proofs = merkle.proofs_from_byte_slices(items)
        assert root == native.merkle_root(items)
        for i, p in enumerate(proofs):
            p.verify(root, items[i])

    def test_disabled_fallback(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_NATIVE", "0")
        monkeypatch.setattr(_native_loader, "_failed", False)
        monkeypatch.setattr(_native_loader, "_mod", None)
        assert _native_loader.load() is None
        items = [secrets.token_bytes(20) for _ in range(20)]
        assert merkle.hash_from_byte_slices(items) == _py_root(items)
        # restore for other tests
        monkeypatch.setenv("COMETBFT_TPU_NATIVE", "1")
        monkeypatch.setattr(_native_loader, "_failed", False)

    def test_no_build_on_hot_path(self, monkeypatch, tmp_path):
        """load(allow_build=False) must never shell out to g++."""
        import subprocess

        monkeypatch.setattr(_native_loader, "_failed", False)
        monkeypatch.setattr(_native_loader, "_mod", None)
        monkeypatch.setattr(_native_loader, "_target_path",
                            lambda: str(tmp_path / "absent.so"))

        def boom(*a, **kw):
            raise AssertionError("hot path invoked the compiler")
        monkeypatch.setattr(subprocess, "run", boom)
        assert _native_loader.load(allow_build=False) is None

    def test_hot_path_remembers_a_miss(self, monkeypatch):
        """wire.encode asks once a message: with nothing built, the
        nine source files are not stat'ed again for a second; a load
        that may build still looks at once."""
        looked = []
        monkeypatch.setattr(_native_loader, "_failed", False)
        monkeypatch.setattr(_native_loader, "_mod", None)
        monkeypatch.setattr(_native_loader, "_miss_until", 0.0)
        monkeypatch.setattr(_native_loader, "_target_fresh",
                            lambda: looked.append(1) and False)
        monkeypatch.setattr(_native_loader, "_build", lambda: None)
        for _ in range(50):
            assert _native_loader.load(allow_build=False) is None
        assert len(looked) == 1
        assert _native_loader.load() is None and len(looked) == 2


def _py_root(items):
    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = merkle._split_point(n)
    return hashlib.sha256(b"\x01" + _py_root(items[:k]) +
                          _py_root(items[k:])).digest()


class TestEd25519Prep:
    def test_malformed_items_marked_bad_no_error_state(self):
        """Non-tuple / wrong-length items become pre_bad lanes without
        leaving a live CPython error set (SystemError regression)."""
        native = _native()
        if not hasattr(native, "ed25519_prep"):
            pytest.skip("older native module")
        out = native.ed25519_prep(
            [None, 42, (b"x" * 32, b"m", b"s" * 64),
             (b"short", b"m", b"s" * 64)],
            8, b"b" * 32, b"i" * 32)
        wire, bad = out
        assert bad[0] == 1 and bad[1] == 1 and bad[3] == 1
        # one buffer, a lane a row of A | R | S windows | k windows
        assert len(wire) == 8 * 192 and len(bad) == 8


def _prep_items(n=200):
    """A seeded batch with malformed and non-canonical-S items among
    honest ones, messages across SHA-512's block boundaries."""
    import random

    from cometbft_tpu.crypto import _ed25519_ref as ref
    rng = random.Random(36)
    lengths = [0, 5, 47, 48, 63, 64, 111, 112, 120, 200, 300, 1000]
    items = []
    for i in range(n):
        seed = rng.randbytes(32)
        msg = rng.randbytes(lengths[i % len(lengths)])
        pub, sig = ref.public_key(seed), ref.sign(seed, msg)
        if i % 9 == 4:      # non-canonical S
            sig = sig[:32] + (ref.L + 5).to_bytes(32, "little")
        if i % 13 == 6:     # malformed
            pub = b"short"
        items.append((pub, msg, sig))
    if n > 17:
        items[17] = None
    return items


class TestEd25519PrepFuture:
    """ed25519_prep_begin: the same prep, phase 2 on the module's
    native thread, behind a handle."""
    B, I = b"b" * 32, b"i" * 32

    def _begin(self, items, m=256):
        native = _native()
        if not hasattr(native, "ed25519_prep_begin"):
            pytest.skip("older native module")
        return native.ed25519_prep_begin(items, m, self.B, self.I)

    def test_result_is_ed25519_preps_and_the_numpy_paths(
            self, monkeypatch):
        import numpy as np

        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import ed25519_jax as ej
        items = _prep_items()
        t_before = tracing.now_ns()
        wire, bad, start_ns, elapsed_ns, waited_ns = \
            self._begin(items).result()
        t_after = tracing.now_ns()
        assert (wire, bad) == _native().ed25519_prep(
            items, 256, self.B, self.I)
        # the prep's own readings, on the recorder's clock
        assert t_before <= start_ns <= start_ns + elapsed_ns <= t_after
        assert elapsed_ns > 0 and 0 <= waited_ns <= t_after - t_before
        # through ops, against the numpy path (which cannot take the
        # None among the items)
        del items[17]
        h = ej._prep_begin(items, 256)
        assert type(h).__name__ == "PrepHandle"
        wire, bad = h.result()[:2]
        monkeypatch.setenv("COMETBFT_TPU_NATIVE", "0")
        saved_mod, saved_failed = (_native_loader._mod,
                                   _native_loader._failed)
        _native_loader._mod = None
        try:
            assert type(ej._prep_begin(items, 256)).__name__ == \
                "_PrepNow"
            py_wire, py_bad = ej._prep_begin(items, 256).result()[:2]
        finally:
            _native_loader._mod = saved_mod
            _native_loader._failed = saved_failed
        assert (wire, bad) == (py_wire, py_bad)
        assert np.array_equal(ej._wire_arrays(wire, bad, 256)[0],
                              ej.prep_arrays(items, 256)[0])
        assert 0 < sum(py_bad) < len(items)

    def test_a_finished_prep_is_not_waited_for(self):
        import time
        h = self._begin(_prep_items(64), 64)
        time.sleep(0.2)
        assert h.result()[4] == 0

    def test_the_result_is_taken_once(self):
        h = self._begin(_prep_items(16), 16)
        h.result()
        with pytest.raises(RuntimeError):
            h.result()

    def test_two_handles_in_flight_settle_in_either_order(self):
        items = _prep_items()
        want = _native().ed25519_prep(items, 256, self.B, self.I)
        first, second = self._begin(items), self._begin(items[:100])
        short = second.result()
        assert first.result()[:2] == want
        assert short[:2] == _native().ed25519_prep(
            items[:100], 256, self.B, self.I)
        with pytest.raises(ValueError):
            self._begin(items, 8)       # m < len(items)

    def test_a_handle_dropped_unread_frees_what_it_borrowed(self):
        import sys
        items = _prep_items()
        probe = items[0]
        refs = sys.getrefcount(probe), sys.getrefcount(items)
        for _ in range(50):
            h = self._begin(items)      # queued, running or done
            del h
        held = [self._begin(items) for _ in range(4)]
        assert sys.getrefcount(items) > refs[1]
        del held
        assert (sys.getrefcount(probe), sys.getrefcount(items)) == refs
        # the thread is still there for the next one
        assert self._begin(items).result()[:2] == \
            _native().ed25519_prep(items, 256, self.B, self.I)

    def test_a_forked_child_runs_what_its_parent_began(self):
        """The native thread does not cross a fork: the child runs a
        prep its parent had posted on the thread that asks for it, and
        starts a thread of its own at its first begin."""
        import os
        import signal
        items = _prep_items()
        want = _native().ed25519_prep(items, 256, self.B, self.I)
        h = self._begin(items)
        pid = os.fork()
        if pid == 0:
            ok = False
            try:
                signal.alarm(20)        # a hang fails, it does not wait
                ok = h.result()[:2] == want and \
                    self._begin(items).result()[:2] == want
            finally:
                os._exit(0 if ok else 1)
        assert os.waitpid(pid, 0)[1] == 0
        assert h.result()[:2] == want


class TestSha512AndKScalars:
    def test_sha512_many_parity(self):
        native = _native()
        if not hasattr(native, "sha512_many"):
            pytest.skip("older native module")
        items = [secrets.token_bytes(n)
                 for n in (0, 1, 63, 64, 111, 112, 127, 128, 129,
                           500)]
        cat = native.sha512_many(items)
        for i, d in enumerate(items):
            assert cat[i * 64:(i + 1) * 64] == \
                hashlib.sha512(d).digest(), f"len {len(d)}"

    def test_kscalars_barrett_mod_l_parity(self):
        """The C Barrett reduction must match python big-int mod L —
        this backs ed25519_prep's k-scalar math."""
        native = _native()
        if not hasattr(native, "ed25519_kscalars"):
            pytest.skip("older native module")
        L = 2 ** 252 + 27742317777372353535851937790883648493
        items = [secrets.token_bytes(32 + i % 150)
                 for i in range(500)]
        cat = native.ed25519_kscalars(items)
        for i, d in enumerate(items):
            want = int.from_bytes(hashlib.sha512(d).digest(),
                                  "little") % L
            got = int.from_bytes(cat[i * 32:(i + 1) * 32], "little")
            assert got == want, f"trial {i}"


class TestNativeBLS:
    """The C++ BLS12-381 port is differentially tested against the
    pure-python golden model (cometbft_tpu/crypto/_bls12381_math.py);
    point wire format: raw affine big-endian coords, b'' = infinity."""

    def _mod(self):
        native = _native()
        if not hasattr(native, "bls_pairings_product_is_one"):
            pytest.skip("older native module")
        return native

    def test_scalar_mult_and_subgroup_parity(self):
        import random

        from cometbft_tpu.crypto import _bls12381_math as M

        native = self._mod()
        rng = random.Random(5)
        orig = M._native
        try:
            for _ in range(4):
                k = rng.getrandbits(180)
                kb = k.to_bytes(23, "big")
                M._native = lambda: None      # python reference
                want1 = M.pt_mul(M.G1_OPS, M.G1_GEN, k)
                want2 = M.pt_mul(M.G2_OPS, M.G2_GEN, k)
                got1 = M._g1_unraw(native.bls_g1_mul(
                    M._g1_raw(M.G1_GEN), kb))
                got2 = M._g2_unraw(native.bls_g2_mul(
                    M._g2_raw(M.G2_GEN), kb))
                assert got1 == want1 and got2 == want2
        finally:
            M._native = orig
        assert native.bls_g1_in_subgroup(M._g1_raw(M.G1_GEN))
        assert native.bls_g2_in_subgroup(M._g2_raw(M.G2_GEN))
        bad = (M.G1_GEN[0], (M.G1_GEN[1] + 1) % M.P)
        assert not native.bls_g1_in_subgroup(M._g1_raw(bad))

    def test_hash_to_g2_parity(self):
        from cometbft_tpu.crypto import _bls12381_math as M

        native = self._mod()
        orig = M._native
        try:
            for msg in (b"", b"abc", b"x" * 130):
                M._native = lambda: None
                want = M.hash_to_g2(msg, b"PARITY-DST")
                got = M._g2_unraw(
                    native.bls_hash_to_g2(msg, b"PARITY-DST"))
                assert got == want, msg
        finally:
            M._native = orig

    def test_pairing_bilinearity(self):
        import random

        from cometbft_tpu.crypto import _bls12381_math as M

        native = self._mod()
        P1, Q2 = M.G1_GEN, M.G2_GEN
        negP = M.pt_neg(M.G1_OPS, P1)
        pp = native.bls_pairings_product_is_one
        assert pp([(M._g1_raw(P1), M._g2_raw(Q2)),
                   (M._g1_raw(negP), M._g2_raw(Q2))])
        assert not pp([(M._g1_raw(P1), M._g2_raw(Q2))])
        rng = random.Random(9)
        x, y = rng.getrandbits(90), rng.getrandbits(90)
        xP = M.pt_mul(M.G1_OPS, P1, x)
        yQ = M.pt_mul(M.G2_OPS, Q2, y)
        xyP = M.pt_mul(M.G1_OPS, P1, x * y)
        # e(xP, yQ) * e(-xyP, Q) == 1
        assert pp([(M._g1_raw(xP), M._g2_raw(yQ)),
                   (M._g1_raw(M.pt_neg(M.G1_OPS, xyP)),
                    M._g2_raw(Q2))])
        # infinity pairs are skipped, matching the python model
        assert pp([(b"", M._g2_raw(Q2)), (M._g1_raw(P1), b"")])


class TestBLSFinalExp:
    def test_frobenius_and_fast_final_exp_selftest(self):
        """The C++ module's built-in algebra check: Frobenius equals a
        plain ^p pow, and the decomposed final exponentiation equals
        the naive one cubed (the ==1 verdict is unchanged since
        gcd(3, r) = 1)."""
        native = _native()
        if not hasattr(native, "bls_selftest"):
            pytest.skip("older native module")
        assert native.bls_selftest()


class TestPrepParityVariedLengths:
    def test_c_prep_matches_python_prep(self, monkeypatch):
        """The threaded C prep (incl. the 8-way AVX-512 SHA-512 path,
        its equal-block-count grouping, partial groups, and the scalar
        fallback) must produce bit-identical arrays to the pure-python
        prep across message lengths spanning 1..9 SHA-512 blocks,
        non-canonical S, and malformed lanes."""
        import numpy as np

        from cometbft_tpu.crypto import _ed25519_ref as ref
        from cometbft_tpu.ops import ed25519_jax as ej

        _native()   # skip when no compiler
        lengths = [0, 5, 47, 48, 63, 64, 111, 112, 120, 200, 300,
                   1000]
        items = []
        for i in range(200):
            seed = secrets.token_bytes(32)
            msg = secrets.token_bytes(lengths[i % len(lengths)])
            pub = ref.public_key(seed)
            sig = ref.sign(seed, msg)
            if i % 9 == 4:    # non-canonical S
                sig = sig[:32] + (ref.L + 5).to_bytes(32, "little")
            if i % 13 == 6:   # malformed
                pub = b"short"
            items.append((pub, msg, sig))
        native_out = ej.prep_arrays(items, 256)

        monkeypatch.setenv("COMETBFT_TPU_NATIVE", "0")
        saved_mod, saved_failed = (_native_loader._mod,
                                   _native_loader._failed)
        _native_loader._mod = None
        try:
            python_out = ej.prep_arrays(items, 256)
        finally:
            _native_loader._mod = saved_mod
            _native_loader._failed = saved_failed
        # the one packed buffer a device_put sends, byte for byte
        for name, a, b in zip(("wire", "pre_bad"), native_out,
                              python_out):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            assert np.array_equal(a, b), f"{name} differs"


class TestEd25519BatchMsm:
    """RLC batch verification (native/ed25519_msm.hpp) vs the golden
    model's batch_verify — the CPU analog of the reference's voi
    batch verifier (crypto/ed25519/ed25519.go:189-222)."""

    @staticmethod
    def _valid(i, msg=None):
        from cometbft_tpu.crypto import _ed25519_ref as ref
        seed = bytes([i % 256, i // 256 % 256]) + secrets.token_bytes(30)
        pub = ref.public_key(seed)
        m = msg if msg is not None else b"batch-msg-%d" % i
        return (pub, m, ref.sign(seed, m))

    def _check(self, items):
        from cometbft_tpu.crypto import _ed25519_ref as ref
        mod = _native()
        if not hasattr(mod, "ed25519_batch_verify"):
            pytest.skip("module predates ed25519_batch_verify")
        z = secrets.token_bytes(16 * len(items))
        got = bool(mod.ed25519_batch_verify(items, z))
        want_ok, want_mask = ref.batch_verify(items)
        assert got == want_ok, (got, want_ok, want_mask)
        return got

    @pytest.mark.parametrize("n", [2, 3, 7, 33, 200])
    def test_valid_batches_accept(self, n):
        assert self._check([self._valid(i) for i in range(n)])

    def test_corrupted_signature_rejects(self):
        items = [self._valid(i) for i in range(9)]
        pub, msg, sig = items[4]
        items[4] = (pub, msg, sig[:7] + bytes([sig[7] ^ 1]) + sig[8:])
        assert not self._check(items)

    def test_wrong_message_rejects(self):
        items = [self._valid(i) for i in range(5)]
        pub, _, sig = items[0]
        items[0] = (pub, b"forged", sig)
        assert not self._check(items)

    def test_non_canonical_s_rejects(self):
        from cometbft_tpu.crypto import _ed25519_ref as ref
        items = [self._valid(i) for i in range(3)]
        pub, msg, sig = items[1]
        s = int.from_bytes(sig[32:], "little") + ref.L
        items[1] = (pub, msg, sig[:32] + s.to_bytes(32, "little"))
        assert not self._check(items)

    def test_zip215_small_order_and_non_canonical_y(self):
        # A = order-4 point (y=0), R = non-canonical identity
        # encoding (y = p+1): S=0 signatures over any message verify
        # under ZIP-215 (cofactored) — the native path must agree
        # with the golden model on these
        from cometbft_tpu.crypto import _ed25519_ref as ref
        a_small = bytes(32)                      # y=0, sign 0
        r_nc = (ref.P + 1).to_bytes(32, "little")
        corner = (a_small, b"whatever", r_nc + bytes(32))
        assert ref.verify(*corner)               # golden ZIP-215 accept
        items = [self._valid(0), corner, self._valid(2)]
        assert self._check(items)

    def test_off_curve_pubkey_rejects_batch(self):
        # an encoding with no curve point: batch returns 0 and the
        # per-signature fallback produces the mask
        items = [self._valid(0), self._valid(1)]
        bad_pub = bytes([2]) + bytes(30) + bytes([0])
        from cometbft_tpu.crypto import _ed25519_ref as ref
        if ref.decompress(bad_pub) is not None:
            pytest.skip("encoding unexpectedly valid")
        items.append((bad_pub, b"m", items[0][2]))
        assert not self._check(items)

    def test_cpu_batch_verifier_uses_native_and_keeps_mask_contract(self):
        from cometbft_tpu.crypto import ed25519
        privs = [ed25519.gen_priv_key() for _ in range(6)]
        bv = ed25519.CpuBatchVerifier()
        for i, p in enumerate(privs):
            bv.add(p.pub_key(), b"m%d" % i, p.sign(b"m%d" % i))
        ok, mask = bv.verify()
        assert ok and mask == [True] * 6
        bv2 = ed25519.CpuBatchVerifier()
        for i, p in enumerate(privs):
            sig = p.sign(b"m%d" % i)
            if i == 2:
                sig = bytes([sig[0] ^ 4]) + sig[1:]
            bv2.add(p.pub_key(), b"m%d" % i, sig)
        ok, mask = bv2.verify()
        assert not ok
        assert mask == [True, True, False, True, True, True]

    def test_pub_decompress_cache_does_not_bypass_verification(self):
        # the A-point cache memoizes DECOMPRESSION only; a second
        # batch reusing a cached pubkey with a forged signature must
        # still reject, and a valid re-verify must still accept
        from cometbft_tpu.crypto import _ed25519_ref as ref
        mod = _native()
        if not hasattr(mod, "ed25519_batch_verify"):
            pytest.skip("module predates ed25519_batch_verify")
        seed = secrets.token_bytes(32)
        pub = ref.public_key(seed)
        items = [(pub, b"m-%d" % i, ref.sign(seed, b"m-%d" % i))
                 for i in range(4)]
        z = secrets.token_bytes(16 * 4)
        assert mod.ed25519_batch_verify(items, z)      # caches pub
        forged = list(items)
        forged[2] = (pub, b"forged", items[2][2])
        assert not mod.ed25519_batch_verify(forged, z)
        assert mod.ed25519_batch_verify(items, z)
