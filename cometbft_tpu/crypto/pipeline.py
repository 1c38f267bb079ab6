"""Tiled, overlapped signature-verification pipeline (CPU seam).

ROADMAP item 2b: the e2e verification path serializes host staging
with kernel execution — on the measured TPU window the 10k-sig path
was 452 ms e2e against 116 ms device-only, and on the CPU backend a
10k native batch blocks whatever thread dispatches it for ~170 ms.
This module makes verification a pipeline instead of a blocking call:

  * the batch splits into pad-bucket tiles (MSM_TILE, 4096 lanes, the
    kernel ladder's mid bucket — small enough that one bad signature
    bisects inside its own tile, large enough that the Pippenger MSM
    keeps most of its batch efficiency);
  * each tile dispatches through the native TILE KERNEL
    (``ed25519_batch_verify_tile``: packed-blob calling convention,
    cached fe_sqr decompression, signed-digit MSM with mixed bucket
    adds — KERNEL_NOTES round 6), measured ~1.3x faster e2e than the
    monolithic dispatch at 10k signatures on the 1-vCPU rig even
    before any thread overlap;
  * tile i's kernel runs on a dedicated worker thread (the native
    entry points release the GIL) while the staging thread packs
    tile i+1's blobs, pre-decompresses its uncached pubkeys
    (``ed25519_stage_pubs``) and applies tile i-1's verdict — on a
    multi-core host the phases genuinely overlap; on the 1-vCPU QA
    rig the win is that the *event loop* is never the thread paying
    for any of it;
  * a tile that rejects bisects WITHIN the tile via the shared
    ``keys.bisect_bad`` — one bad signature re-checks O(log tile)
    subsets instead of re-verifying the whole batch;
  * ``verify_async`` hands the entire pipeline to the staging worker
    and returns an awaitable verdict future, so consensus submits a
    vote-storm burst and keeps draining gossip until the verdict
    barrier (consensus/state.py).

The phase split is observed into the same
``crypto_kernel_dispatch_seconds`` histogram the ops dispatcher uses
(kernel label "native"), so /metrics shows host_prep overlapping
kernel_execute for CPU tiles exactly as it does for TPU buckets, and
each pipeline run records its measured overlap ratio
(sum of phase durations / wall clock — > 1.0 means phases ran
concurrently).
"""
from __future__ import annotations

import secrets
import struct
import time
from typing import Callable, Optional, Sequence

from ..libs import tracing
from ..libs.workers import SupervisedWorker
from .keys import bisect_bad

# ---------------------------------------------------------------------
# the geometry of a dispatch: the pad-bucket ladder and the tile.  This
# module is its one owner and imports no JAX, so that crypto/batch (the
# seam) and ops/ed25519_jax (the device dispatch) both read it here and
# a ``cpu`` node never loads the jax stack to label a histogram.

BASE_BUCKETS = (64, 1024, 4096, 10240, 16384)
# THE live ladder, one list object for the life of the process: the
# tuner (ops/ed25519_jax._tune_record) inserts refined buckets and
# test hooks write it, always in place — nothing rebinds it
BUCKETS = list(BASE_BUCKETS)
# the DEVICE pipeline's tile in lanes, a pad-bucket shape: the granule
# at which the seam's verifier streams from ``add()`` and the one shape
# of every chunk above it.  1,024 and not 4,096 since PR 36: a
# 10,000-validator commit (6,667 signatures) starts its first kernel
# after 2,048 signatures walked, pads to 7,168 lanes and not 8,192,
# and a tile's host prep (1.2 ms) hides under the walk of the next
TILE = 1024
# the CPU pipeline's tile (verify_items_pipelined): the Pippenger MSM
# loses batch efficiency below it (10,000 signatures on the CPU
# sandbox, PR 36: ~96 ms at 4,096, ~104 at 1,024), so it keeps its own
MSM_TILE = 4096


def bucket(n: int) -> int:
    """The padded lane count a batch of n signatures dispatches at:
    the shape the kernel compiles once for, and the ``pad_bucket``
    label value of the verify and dispatch histograms."""
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


def tile_bucket() -> int:
    """The tile's shape: the one lane count every chunk of the device
    pipeline dispatches at, and the number of items at which the
    seam's verifier feeds a tile."""
    return bucket(TILE)


def batch_equation(m: int) -> bool:
    """Whether a device dispatch of m padded lanes is settled by ONE
    batch equation (ops/ed25519_pallas._batch_kernel) before any lane
    is judged alone: at the tile's shape, and below it not.  The
    equation pays a fixed cost a dispatch (Horner's 252 doublings and
    the lanes' fold, on one block of lanes) for half the field
    multiplications a lane, so it wins only with enough blocks: ~1.9x
    at 1,024 lanes, ~1.6x at 512 and ~1.2x at 256 by the count, a loss
    at 128; and every shape it engages traces and lowers one more
    kernel in set-up (PERF.md §7)."""
    return m == tile_bucket()


def tile_plan(n: int, tile: int) -> list:
    """[(start, end), ...] covering n lanes in BALANCED slices of at
    most ``tile`` lanes: 10k at tile 4096 plans three ~3334-lane
    tiles, not 4096+4096+1808.  Balancing matters twice — the
    pipeline's overlap window is bounded by its narrowest tile, and
    the signed-digit MSM's per-tile bucket sweep amortizes best when
    no tile is small (measured ~3% fewer point adds at the 10k
    shape)."""
    t = tile
    if n <= 0:
        return []
    ntiles = -(-n // t)
    size = -(-n // ntiles)
    return [(s, min(s + size, n)) for s in range(0, n, size)]


# ---------------------------------------------------------------------
# workers (lazy singletons).  Two threads, each single-worker:
#   * stage  — runs whole async-submitted pipelines (and the verdict
#              barrier work), keeping the event loop out of it;
#   * kernel — runs the GIL-free kernel call of the current tile so
#              the staging side can prep the next tile concurrently.

_STAGE: Optional[SupervisedWorker] = None
_KERNEL: Optional[SupervisedWorker] = None


def _stage_worker() -> SupervisedWorker:
    global _STAGE
    if _STAGE is None:
        _STAGE = SupervisedWorker("verify_stage")
    return _STAGE


def _kernel_worker() -> SupervisedWorker:
    global _KERNEL
    if _KERNEL is None:
        _KERNEL = SupervisedWorker("verify_kernel")
    return _KERNEL


def reset_workers() -> None:
    """Test hook: stop and discard the singleton workers."""
    global _STAGE, _KERNEL
    for w in (_STAGE, _KERNEL):
        if w is not None:
            w.stop()
    _STAGE = _KERNEL = None


def submit(fn: Callable, *args):
    """Run ``fn(*args)`` on the staging worker; returns a concurrent
    Future."""
    return _stage_worker().submit(fn, *args)


def run_off_loop(fn: Callable, *args):
    """Awaitable for ``fn(*args)`` executed on the staging worker —
    the consensus/reactor seam for moving a synchronous verification
    off the event loop.  Must be awaited from a running loop."""
    import asyncio
    return asyncio.wrap_future(submit(fn, *args))


# ---------------------------------------------------------------------
# metrics

_DISPATCH_HIST = None
_OVERLAP_HIST = None
_TILE_REJECTS = None


def dispatch_histogram():
    """metrics v2: host_prep vs kernel_execute latency split per pad
    bucket, on the process-global registry (the dispatchers have no
    node context; /metrics merges DEFAULT in).  One family for the
    native tiles here (kernel label "native") and the device
    dispatches of ops/ed25519_jax.  ``warm`` separates first-dispatch
    compiles from steady-state execution so the execute distribution
    is not polluted by one-off trace+compile."""
    global _DISPATCH_HIST
    if _DISPATCH_HIST is None:
        from ..libs import metrics as libmetrics
        _DISPATCH_HIST = libmetrics.DEFAULT.histogram(
            "crypto", "kernel_dispatch_seconds",
            "ed25519 kernel dispatch phases (host_prep / "
            "kernel_execute) in seconds, by kernel, pad bucket and "
            "warm-shape flag.",
            labels=("phase", "kernel", "pad_bucket", "warm"),
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 5.0, 30.0, 120.0))
    return _DISPATCH_HIST


def overlap_histogram():
    """Measured overlap ratio per pipeline run: (host_prep wall +
    kernel_execute wall + verdict_apply wall) / pipeline wall.  1.0 =
    fully serial; the headroom above 1.0 is the dispatch cost the
    overlap removed (2.0 = perfect two-phase overlap)."""
    global _OVERLAP_HIST
    if _OVERLAP_HIST is None:
        from ..libs import metrics as libmetrics
        _OVERLAP_HIST = libmetrics.DEFAULT.histogram(
            "crypto", "verify_overlap_ratio",
            "Per-pipeline-run overlap ratio: summed phase wall time "
            "divided by pipeline wall time (1.0 = serial, higher = "
            "phases genuinely overlapped).",
            buckets=(0.5, 0.8, 0.9, 1.0, 1.05, 1.1, 1.25, 1.5, 1.75,
                     2.0, 2.5))
    return _OVERLAP_HIST


def _tile_reject_counter():
    global _TILE_REJECTS
    if _TILE_REJECTS is None:
        from ..libs import metrics as libmetrics
        _TILE_REJECTS = libmetrics.DEFAULT.counter(
            "crypto", "verify_tile_rejects",
            "Pipeline tiles whose batch equation rejected and were "
            "bisected within the tile (per-tile attribution keeps "
            "one bad signature from re-verifying the whole batch).")
    return _TILE_REJECTS


# ---------------------------------------------------------------------
# the CPU pipeline

def _pack_tile(chunk) -> tuple:
    """(pub, msg, sig) triples -> the tile kernel's packed-blob
    layout: pubs 32n || msgs concatenated || lens u32-LE || sigs 64n.
    Four contiguous buffers replace 3n PyObject extractions per
    dispatch — this is the "sign-bytes packing" half of host_prep."""
    pubs = b"".join(it[0] for it in chunk)
    msgs = b"".join(it[1] for it in chunk)
    lens = struct.pack(f"<{len(chunk)}I",
                       *(len(it[1]) for it in chunk))
    sigs = b"".join(it[2] for it in chunk)
    return pubs, msgs, lens, sigs


def _tile_holds(native, chunk) -> bool:
    """One tile through the best available native entry: the tile
    kernel (packed blobs, signed-digit MSM, cached fe_sqr
    decompression) when this module build has it, else the legacy
    monolithic entry on the tile's items."""
    z = secrets.token_bytes(16 * len(chunk))
    if hasattr(native, "ed25519_batch_verify_tile"):
        pubs, msgs, lens, sigs = _pack_tile(chunk)
        return bool(native.ed25519_batch_verify_tile(
            pubs, msgs, lens, sigs, z))
    return bool(native.ed25519_batch_verify(list(chunk), z))


def verify_items_pipelined(
        native, items: Sequence, verify_one: Callable[[int], bool],
        tile: Optional[int] = None) -> tuple:
    """Tiled + overlapped batch verification of raw (pub, msg, sig)
    byte triples through the native tile kernel.

    Staging (blob packing, randomizer generation, pubkey decompress
    pre-staging) and the verdict apply/bisection of tile i-1 run on
    the calling thread while tile i's kernel runs GIL-free on the
    kernel worker.  A rejecting tile bisects with fresh randomizers
    via ``keys.bisect_bad`` — attribution never leaves the tile.
    ``verify_one(i)`` is the caller's exact single-signature check
    (batch-index i).

    Returns (all_ok, mask) — the BatchVerifier.Verify contract.
    """
    n = len(items)
    if n == 0:
        return True, []
    t = tile or MSM_TILE
    pad_bucket = str(t)
    plan = tile_plan(n, t)
    mask = [True] * n
    hist = dispatch_histogram()
    worker = _kernel_worker()
    has_tile_kernel = hasattr(native, "ed25519_batch_verify_tile")
    can_stage = hasattr(native, "ed25519_stage_pubs")
    t_run0 = time.perf_counter()
    phase_s = 0.0

    def kernel_call(chunk, blobs, staged, z):
        k0 = tracing.now_ns()
        if blobs is not None and staged is not None:
            ok = bool(native.ed25519_batch_verify_tile(*blobs, z,
                                                       staged))
        elif blobs is not None:
            ok = bool(native.ed25519_batch_verify_tile(*blobs, z))
        else:
            ok = bool(native.ed25519_batch_verify(chunk, z))
        return ok, k0, tracing.now_ns()

    def stage(lo, hi):
        p0 = tracing.now_ns()
        chunk = list(items[lo:hi])
        z = secrets.token_bytes(16 * len(chunk))
        blobs = _pack_tile(chunk) if has_tile_kernel else None
        staged = None
        if blobs is not None and can_stage:
            # resolve this tile's A points (cache-backed decompress),
            # GIL-free: on a multi-core host this runs while the
            # PREVIOUS tile's MSM owns the kernel worker, so the
            # kernel call receives every A point pre-staged
            staged = native.ed25519_stage_pubs(blobs[0])
        p1 = tracing.now_ns()
        hist.with_labels("host_prep", "native", pad_bucket,
                         "1").observe((p1 - p0) / 1e9)
        tracing.record_span(tracing.CRYPTO, "host_prep", p0, p1,
                            batch=hi - lo, bucket=t)
        return chunk, blobs, staged, z, (p1 - p0) / 1e9

    def settle(lo, hi, chunk, fut):
        ok, k0, k1 = fut.result()
        hist.with_labels("kernel_execute", "native", pad_bucket,
                         "1").observe((k1 - k0) / 1e9)
        tracing.record_span(tracing.CRYPTO, "kernel_execute", k0, k1,
                            batch=hi - lo, bucket=t, kernel="native")
        if ok:
            return (k1 - k0) / 1e9
        # per-tile attribution: bisect INSIDE the tile with fresh
        # randomizers per subset; exact verify decides singletons
        _tile_reject_counter().add()
        a0 = time.perf_counter()
        sub = [True] * len(chunk)

        def subset_holds(idxs):
            return _tile_holds(native, [chunk[i] for i in idxs])

        bisect_bad(list(range(len(chunk))), sub, subset_holds,
                   lambda i: verify_one(lo + i))
        for i, good in enumerate(sub):
            if not good:
                mask[lo + i] = False
        return (k1 - k0) / 1e9 + (time.perf_counter() - a0)

    # software pipeline: stage tile i+1 while tile i's kernel runs on
    # the worker; settle tile i (verdict + bisection) before tile
    # i+1's verdict is needed
    inflight = None                      # (lo, hi, chunk, future)
    for lo, hi in plan:
        chunk, blobs, staged, z, prep_s = stage(lo, hi)
        phase_s += prep_s
        fut = worker.submit(kernel_call, chunk, blobs, staged, z)
        if inflight is not None:
            phase_s += settle(*inflight)
        inflight = (lo, hi, chunk, fut)
    if inflight is not None:
        phase_s += settle(*inflight)

    wall = time.perf_counter() - t_run0
    if wall > 0 and len(plan) > 1:
        overlap_histogram().observe(phase_s / wall)
    return all(mask), mask


__all__ = ["BASE_BUCKETS", "BUCKETS", "TILE", "MSM_TILE", "bucket",
           "tile_bucket", "batch_equation", "tile_plan",
           "verify_items_pipelined", "submit",
           "run_off_loop", "dispatch_histogram", "overlap_histogram",
           "reset_workers"]
