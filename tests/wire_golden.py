"""A golden kernel at the wire: what the device kernels compute, from
the one [m, 192] uint8 buffer a dispatch sends (ops/ed25519_jax: a
lane a row of A | R | S windows | k windows), in the golden model's
big-int point arithmetic (crypto/_ed25519_ref).

It stands in for _jit_verify_packed / _pallas_verify_packed in tier-1
tests of the path around the kernel (prep_arrays -> tiles -> pre_bad
-> mask assembly -> verdict), whose real kernels take minutes to
compile on a CPU.  Like them it knows nothing of messages, canonical
S or lane counts: a padding lane (B, the identity, zero windows) is
true, and a lane the host refused is whatever its row says.
"""
from __future__ import annotations

import functools

import numpy as np

from cometbft_tpu.crypto import _ed25519_ref as ref

LANE_BYTES = 192


def _scalar(windows: bytes) -> int:
    """64 4-bit little-endian windows, one a byte -> the integer."""
    return sum(w << (4 * i) for i, w in enumerate(windows))


def _neg(pt):
    return ((ref.P - pt[0]) % ref.P, pt[1])


@functools.lru_cache(maxsize=None)
def verify_lane(row: bytes) -> bool:
    """[8](s.B - R - k.A) == identity, A and R decoded permissively
    (ZIP-215)."""
    a = ref.decompress(row[0:32])
    r = ref.decompress(row[32:64])
    if a is None or r is None:
        return False
    s, k = _scalar(row[64:128]), _scalar(row[128:192])
    chk = ref.point_add(
        ref.scalar_mult(s, ref.B),
        ref.point_add(_neg(r), _neg(ref.scalar_mult(k, a))))
    return ref.is_identity_cofactored(chk)


def verify_wire(wire) -> np.ndarray:
    """[m, 192] uint8 -> [m] bool, a lane at a time."""
    wire = np.asarray(wire)
    if wire.ndim != 2 or wire.shape[1] != LANE_BYTES \
            or wire.dtype != np.uint8:
        raise ValueError(f"not a wire buffer: {wire.dtype}"
                         f"{wire.shape}")
    return np.fromiter((verify_lane(row.tobytes()) for row in wire),
                       bool, count=wire.shape[0])
