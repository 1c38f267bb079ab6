"""What one call of the verification kernel must move, from its shapes.

The kernel (ops/ed25519_jax._pallas_verify_packed) takes ONE input,
the packed ``[lanes, 192]`` uint8 wire buffer (since PR 26; four
arrays before), a padded lane a row of A | R (32 bytes each) | the S
and k 4-bit windows (64 bytes each), and returns one mask byte a lane.
On the device it cuts the four column ranges (``wire_views``) and
widens them to int32 columns before the Pallas kernel reads them (4
bytes per element written and read once more).  These are the bytes
the algorithm needs; scratch traffic inside VMEM is not HBM traffic
and is not counted.
"""
from __future__ import annotations

import json
import os

WIRE_BYTES_PER_LANE = 32 + 32 + 64 + 64      # uint8 in
MASK_BYTES_PER_LANE = 1                      # bool out
WIDENED_ELEMS_PER_LANE = 32 + 32 + 64 + 64   # int32 columns


def hbm_bytes(lanes: int) -> int:
    """Bytes one kernel call of ``lanes`` padded lanes moves through
    HBM: wire layout read, int32 columns written then read, mask
    written."""
    widened = 4 * WIDENED_ELEMS_PER_LANE
    return lanes * (WIRE_BYTES_PER_LANE + 2 * widened
                    + MASK_BYTES_PER_LANE)


def peaks(device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an
    error, not a default."""
    with open(os.path.join(os.path.dirname(__file__),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]
