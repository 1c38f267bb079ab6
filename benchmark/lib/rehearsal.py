"""Rehearsal mode: debug the benchmark on a CPU before chip time is
spent.  Copied from chip_smoke.py's _rehearse_on_cpu: the device path
is routed to the Pallas kernel in interpret mode at a block of 8 lanes
and one 16-lane bucket.  A rehearsal says so on its first line, never
prints ``correct: true`` and never exits 0.
"""
from __future__ import annotations

import os

BANNER = "REHEARSAL — not a chip result"
EXIT = 4


def rehearse_on_cpu() -> None:
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.ops import ed25519_jax as ej
    from cometbft_tpu.ops import ed25519_pallas as ep

    os.environ["COMETBFT_TPU_KERNEL"] = "pallas"   # auto: xla on cpu
    ep.BLOCK = 8
    ej._BUCKETS[:] = [16]
    launch = ej._launch

    def interpreted(*args, **kw):
        kw["interpret"] = True
        return launch(*args, **kw)

    ej._launch = interpreted
    crypto_batch._backend = "tpu"
