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

    def test_tpu_verifier_flags_bad_sig(self):
        from cometbft_tpu.crypto import ed25519
        priv = ed25519.gen_priv_key()
        pub = priv.pub_key()
        bv = ej.TpuBatchVerifier()
        bv.add(pub, b"a", priv.sign(b"a"))
        bv.add(pub, b"b", priv.sign(b"x"))   # wrong message
        bv.add(pub, b"c", priv.sign(b"c"))
        ok, mask = bv.verify()
        assert not ok and mask == [True, False, True]


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


def _pallas_verify_items(items, block=8, kernel="pallas"):
    """Run a Pallas kernel in interpret mode through the production
    prep + dispatch path (ops/ed25519_jax.py), with a small block so
    the emulated kernel stays tractable."""
    n = len(items)
    m = -(-n // block) * block
    a_b, r_b, s_win, k_win, pre_bad = ej.prep_arrays(items, m)
    return ej._dispatch(n, a_b, r_b, s_win, k_win, pre_bad,
                        kernel=kernel, interpret=True,
                        block=block).tolist()


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
        monkeypatch.setenv("COMETBFT_TPU_SHARD_MIN", "1")
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


class TestPallas8Fallback:
    pytestmark = pytest.mark.slow  # cold kernel compile (60-270s on 1 CPU)

    """The first-generation 32x8-bit kernel stays correct behind
    COMETBFT_TPU_KERNEL=pallas8 (one smoke case; its full parity
    history is r3's suite — the 24-limb kernel above inherits it)."""

    def test_valid_and_corrupted(self):
        pub, msg, sig = _sig()
        bad = sig[:10] + bytes([sig[10] ^ 0xFF]) + sig[11:]
        assert _pallas_verify_items(
            [(pub, msg, sig), (pub, msg, bad)],
            kernel="pallas8") == [True, False]
