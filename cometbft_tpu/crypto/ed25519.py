"""ed25519 keys — the default validator key type.

Reference: crypto/ed25519/ed25519.go — curve25519-voi with ZIP-215
verification semantics (:36-44), LRU expanded-pubkey cache of size 4096
(:62-68), batch verification (:189-222).

Design here:
  * Signing and the fast path of single verification use OpenSSL via the
    ``cryptography`` package (same performance class as the reference's Go).
  * OpenSSL implements cofactorless RFC-8032 verification; ZIP-215 is strictly
    more permissive (cofactored + permissive point decoding), so an OpenSSL
    "accept" is always a ZIP-215 "accept". On OpenSSL "reject" we re-check
    with the exact ZIP-215 golden model so consensus-visible semantics match
    the reference byte-for-byte.
  * Batch verification dispatches to the TPU backend (ops.ed25519_jax) when
    available, falling back to a CPU loop. See crypto/batch.py for dispatch.
"""
from __future__ import annotations

import secrets
from collections import OrderedDict
from typing import Sequence

try:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
    )
    _HAVE_OPENSSL = True
except ImportError:
    # Gate the missing dependency instead of dying at import: some
    # containers ship without the OpenSSL bindings, which used to take
    # down EVERY module that (transitively) imports this one.  The
    # exact pure-python ZIP-215 model signs/verifies, with the native
    # C++ batch equation as the single-verify fast-accept path.
    _HAVE_OPENSSL = False

    class InvalidSignature(Exception):
        pass

    Ed25519PrivateKey = Ed25519PublicKey = None  # type: ignore[assignment]

from . import _ed25519_ref as ref
from .keys import BatchVerifier, PrivKey, PubKey, address_hash, bisect_bad

KEY_TYPE = "ed25519"
PUB_KEY_SIZE = 32
PRIV_KEY_SIZE = 64  # seed || pubkey, matching the reference's 64-byte privkey
SIGNATURE_SIZE = 64

# LRU cache of parsed OpenSSL pubkey objects
# (reference: cachedVerification LRU, size 4096, ed25519.go:62-68)
_CACHE_SIZE = 4096
_pub_cache: OrderedDict[bytes, Ed25519PublicKey] = OrderedDict()


def _cached_openssl_pub(raw: bytes) -> Ed25519PublicKey:
    k = _pub_cache.get(raw)
    if k is None:
        k = Ed25519PublicKey.from_public_bytes(raw)
        _pub_cache[raw] = k
        if len(_pub_cache) > _CACHE_SIZE:
            _pub_cache.popitem(last=False)
    else:
        _pub_cache.move_to_end(raw)
    return k


class Ed25519PubKey(PubKey):
    __slots__ = ("_raw", "_addr")

    def __init__(self, raw: bytes):
        if len(raw) != PUB_KEY_SIZE:
            raise ValueError(f"ed25519 pubkey must be {PUB_KEY_SIZE} bytes")
        self._raw = bytes(raw)
        self._addr: bytes | None = None

    def address(self) -> bytes:
        if self._addr is None:
            self._addr = address_hash(self._raw)
        return self._addr

    def bytes(self) -> bytes:
        return self._raw

    def type(self) -> str:
        return KEY_TYPE

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        # NOTE: an OpenSSL reject falls back to the exact-but-slow Python
        # ZIP-215 model (required for consensus-identical semantics: a
        # cofactorless reject may still be a cofactored accept when A/R have
        # torsion components, which cannot be detected cheaply). This makes
        # invalid signatures ~1000x costlier than valid ones — an
        # amplification lever that the native/ C++ ZIP-215 verifier
        # (planned; see SURVEY §7 hard parts) removes by making the exact
        # check fast in both directions.
        if len(sig) != SIGNATURE_SIZE:
            return False
        if not _HAVE_OPENSSL:
            return _verify_without_openssl(self._raw, msg, sig)
        try:
            _cached_openssl_pub(self._raw).verify(sig, msg)
            return True
        except InvalidSignature:
            # ZIP-215 is strictly more permissive than OpenSSL's cofactorless
            # check; re-verify with the exact golden model on reject.
            return ref.verify(self._raw, msg, sig)
        except ValueError:
            # invalid point encoding for OpenSSL; ZIP-215 may still accept
            return ref.verify(self._raw, msg, sig)


def _verify_without_openssl(raw_pub: bytes, msg: bytes,
                            sig: bytes) -> bool:
    """Single-signature verify when the OpenSSL bindings are absent:
    fast-accept through the native C++ batch equation (one item), with
    the exact-but-slow python ZIP-215 model deciding rejects — the
    same accept/reject contract as the CpuBatchVerifier path."""
    native = _native_msm()
    if native is not None:
        try:
            if native.ed25519_batch_verify(
                    [(raw_pub, msg, sig)], secrets.token_bytes(16)):
                return True
        except Exception:
            pass   # malformed shapes fall through to the exact model
    return ref.verify(raw_pub, msg, sig)


class Ed25519PrivKey(PrivKey):
    __slots__ = ("_seed", "_pub", "_ossl")

    def __init__(self, raw: bytes):
        # accept 32-byte seed or 64-byte seed||pub (reference format)
        if len(raw) == 64:
            raw = raw[:32]
        if len(raw) != 32:
            raise ValueError("ed25519 privkey must be 32-byte seed or 64 bytes")
        self._seed = bytes(raw)
        if _HAVE_OPENSSL:
            self._ossl = Ed25519PrivateKey.from_private_bytes(
                self._seed)
            from cryptography.hazmat.primitives.serialization import (
                Encoding, PublicFormat,
            )
            self._pub = self._ossl.public_key().public_bytes(
                Encoding.Raw, PublicFormat.Raw)
        else:
            self._ossl = None
            self._pub = ref.public_key(self._seed)

    def bytes(self) -> bytes:
        return self._seed + self._pub  # 64-byte reference layout

    def sign(self, msg: bytes) -> bytes:
        if self._ossl is None:
            return ref.sign(self._seed, msg)
        return self._ossl.sign(msg)

    def pub_key(self) -> Ed25519PubKey:
        return Ed25519PubKey(self._pub)

    def type(self) -> str:
        return KEY_TYPE


def gen_priv_key() -> Ed25519PrivKey:
    return Ed25519PrivKey(secrets.token_bytes(32))


def gen_priv_key_from_secret(secret: bytes) -> Ed25519PrivKey:
    """Deterministic key from a secret (reference: GenPrivKeyFromSecret —
    seed = SHA-256(secret))."""
    from . import tmhash
    return Ed25519PrivKey(tmhash.sum(secret))


class CpuBatchVerifier(BatchVerifier):
    """CPU batch verifier — the reference's actual CPU design: a
    random-linear-combination batch equation over one Pippenger
    multi-scalar multiplication (crypto/ed25519/ed25519.go:189-222;
    curve25519-voi does the same multi-exponentiation internally),
    implemented in C (native/ed25519_msm.hpp, ~4.8x the per-signature
    OpenSSL loop at 10k signatures on one core).  On batch reject —
    or when the native module is unavailable — each signature is
    verified individually to produce the exact validity mask, the
    same fallback contract as the TPU path.

    Batches larger than one CPU pipeline tile (crypto/pipeline
    MSM_TILE, 4096) verify as a tiled pipeline through the native tile
    kernel: tile i runs GIL-free on the kernel worker while this
    thread packs and stages tile i+1 and settles tile i-1, and a
    reject bisects WITHIN its tile — one bad signature in a 10k
    burst re-checks O(log tile) subsets instead of the whole batch.
    Measured at the 10k-distinct-key commit-burst shape on the
    1-vCPU rig: 145 ms vs 187 ms monolithic (perf_baseline
    ed25519_pipelined_dispatch).  ``monolithic=True`` pins the
    pre-pipeline single-dispatch path (perf_lab's comparison arm).
    """

    def __init__(self, monolithic: bool = False):
        self._items: list[tuple[Ed25519PubKey, bytes, bytes]] = []
        self._monolithic = monolithic

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if not isinstance(pub_key, Ed25519PubKey):
            raise TypeError("CpuBatchVerifier requires ed25519 keys")
        if len(sig) != SIGNATURE_SIZE:
            raise ValueError("malformed signature")
        self._items.append((pub_key, bytes(msg), bytes(sig)))

    def __len__(self) -> int:
        return len(self._items)

    def _verify_one(self, i: int) -> bool:
        pk, m, s = self._items[i]
        return pk.verify_signature(m, s)

    def verify(self) -> tuple[bool, Sequence[bool]]:
        n = len(self._items)
        if n >= 2:
            native = _native_msm()
            if native is not None:
                raw = [(pk.bytes(), m, s) for pk, m, s in self._items]
                try:
                    from . import pipeline
                    if not self._monolithic and \
                            n > pipeline.MSM_TILE:
                        return pipeline.verify_items_pipelined(
                            native, raw, self._verify_one)
                    if self._batch_holds(native, raw):
                        return True, [True] * n
                    # batch rejected: bisect with the native batch
                    # equation (fresh randomizers per subset) so k bad
                    # signatures cost O(k log n) subset checks, not a
                    # whole-group per-signature sweep
                    mask = [True] * n
                    bisect_bad(
                        list(range(n)), mask,
                        lambda half: self._batch_holds(
                            native, [raw[i] for i in half]),
                        self._verify_one)
                    return all(mask), mask
                except Exception:
                    pass    # malformed shapes fall through per-sig
        per = [pk.verify_signature(m, s) for pk, m, s in self._items]
        return all(per), per

    @staticmethod
    def _batch_holds(native, raw) -> bool:
        z = secrets.token_bytes(16 * len(raw))
        return bool(native.ed25519_batch_verify(raw, z))


_NATIVE_MSM = False         # False = unprobed, None = unavailable


def _native_msm():
    global _NATIVE_MSM
    if _NATIVE_MSM is False:
        try:
            from . import _native_loader
            mod = _native_loader.load()
            _NATIVE_MSM = mod if (
                mod is not None and
                hasattr(mod, "ed25519_batch_verify")) else None
        except Exception:
            _NATIVE_MSM = None
    return _NATIVE_MSM
