"""The device gate: the one answer to "is there a chip".

Backend selection (crypto/batch.py), kernel choice and sharding
(ops/ed25519_jax.py, parallel/mesh.py), the benchmarks and
chip_smoke.py all ask here for the JAX platform, the device kind and
count, and where the persistent compile cache lives — so they cannot
disagree.  A failure to reach the backend propagates to the caller;
nothing here turns "no device" into "carry on".

JAX is imported by the first ``probe()``, never by importing this
module: a node whose backend is ``cpu`` never asks, and so never
loads JAX at all.
"""
from __future__ import annotations

import os
import threading
from typing import NamedTuple, Optional

# Platforms whose devices run the Mosaic/Pallas kernels.  An ALLOWLIST:
# a GPU or unknown accelerator would fail the TPU lowering on every
# batch, so anything not listed takes the CPU verifier.
TPU_PLATFORMS = frozenset({"tpu"})


class Device(NamedTuple):
    platform: str       # jax.devices()[0].platform
    kind: str           # jax.devices()[0].device_kind
    count: int          # len(jax.devices())
    cache_dir: str      # persistent compile cache in use

    @property
    def is_tpu(self) -> bool:
        return self.platform in TPU_PLATFORMS

    def summary(self) -> dict:
        """The device as every result line names it."""
        return {"platform": self.platform, "kind": self.kind,
                "count": self.count}


class NoTpuError(RuntimeError):
    """A TPU was required and JAX's default platform is something else."""


_lock = threading.Lock()
_device: Optional[Device] = None


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — a fixed path, because the path is
    part of how a later process finds what an earlier one compiled."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def _place_compile_cache(jax) -> str:
    """A cache directory that is already configured wins: JAX fills
    ``jax_compilation_cache_dir`` from JAX_COMPILATION_CACHE_DIR, and an
    embedding application may have set it.  Only when nothing did does
    the cache go to the in-checkout default."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    cache_dir = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def probe() -> Device:
    """Initialize the JAX backend (blocking: seconds on a chip host)
    and report what it found.  Resolved once per process; the compile
    cache is placed first, so everything compiled afterwards — by
    verify_batch, the mesh, the microbenchmarks or the smoke — lands in
    the same cache."""
    global _device
    if _device is None:
        with _lock:
            if _device is None:
                import jax
                cache_dir = _place_compile_cache(jax)
                devs = jax.devices()
                _device = Device(devs[0].platform, devs[0].device_kind,
                                 len(devs), cache_dir)
    return _device


def require_tpu() -> Device:
    """probe(), or NoTpuError naming the platform that answered."""
    dev = probe()
    if not dev.is_tpu:
        raise NoTpuError(
            f"no TPU: JAX's default platform is {dev.platform!r} "
            f"({dev.count} x {dev.kind})")
    return dev
