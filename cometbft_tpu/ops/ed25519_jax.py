"""Data-parallel ed25519 verification on TPU (JAX/XLA).

This is the north-star offload (reference seam: crypto/ed25519/ed25519.go
BatchVerifier :189-222, consumed by types/validation.go verifyCommitBatch and
types/vote_set.go AddVote).  The design is TPU-first, not a port:

  * one fused XLA computation verifies N signatures in parallel: permissive
    (ZIP-215) point decompression, a 4-bit-windowed Straus ladder evaluating
    s·B - k·A per lane from precomputed tables, subtraction of R, cofactor
    clearing by three doublings, and a vectorized identity test;
  * field arithmetic is `ops.field` (32x8-bit limbs in int32);
  * verification is *cofactored* ([8](s·B - R - k·A) == 0) exactly like the
    reference's ZIP-215 semantics, so single and batch verdicts agree;
  * shapes are bucketed (powers of two) so each bucket compiles once;
  * the per-signature validity mask comes straight out of the kernel,
    except at the tile's shape (crypto/pipeline.batch_equation), where
    the Pallas kernel first checks ONE random-linear-combination batch
    equation for the whole tile, and runs the per-lane ladder on the
    same wire only when it fails.

Host-side work is limited to SHA-512 reductions mod L (cheap, OpenSSL via
hashlib) and nibble-window decomposition of the scalars.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import os
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import _ed25519_ref as ref
from ..crypto.pipeline import (
    BASE_BUCKETS as _BASE_BUCKETS, BUCKETS as _BUCKETS, bucket as _bucket,
    batch_equation, dispatch_histogram, overlap_histogram, tile_bucket,
    tile_plan,
)
from ..libs import tracing
from . import device, field

# (kernel choice, bucket) shapes already dispatched in this process —
# the first dispatch of a shape pays tracing/compilation, so the
# flight-recorder span carries warm=False for it
_SEEN_SHAPES: set[tuple[str, int]] = set()

_WARNED_NO_NATIVE = False

L = ref.L

# --- constants (host-computed once from the golden model) -------------------

_D = field.constant(ref.D)
_SQRT_M1 = field.constant(ref.SQRT_M1)
_ONE = field.constant(1)

# --- point arithmetic (extended twisted Edwards coordinates) ----------------

def _ext_add(p, q):
    """Unified add (add-2008-hwcd-3): complete for a=-1, handles doubling and
    the identity, so the Straus loop needs no special cases."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    a = field.mul(Y1 - X1, Y2 - X2)
    b = field.mul(Y1 + X1, Y2 + X2)
    c = field.mul(field.mul(T1, T2), _2D)
    d = field.mul_const(field.mul(Z1, Z2), 2)
    e = b - a
    f = d - c
    g = d + c
    h = b + a
    return (field.mul(e, f), field.mul(g, h),
            field.mul(f, g), field.mul(e, h))


_2D = field.constant(2 * ref.D % ref.P)


def _ext_double(p):
    """Dedicated doubling (dbl-2008-hwcd, a=-1): 4 squarings + 4 products —
    one multiply and several adds cheaper than the unified add, and the
    ladder is ~2/3 doublings."""
    X1, Y1, Z1, _ = p
    a = field.sqr(X1)
    b = field.sqr(Y1)
    c = field.mul_const(field.sqr(Z1), 2)
    e = field.sqr(X1 + Y1) - a - b
    g = b - a                      # D + B with D = -A
    f = g - c
    h = -(a + b)                   # D - B
    return (field.mul(e, f), field.mul(g, h),
            field.mul(f, g), field.mul(e, h))


def _identity(batch_shape):
    z = jnp.zeros(batch_shape + (field.LIMBS,), jnp.int32)
    one = jnp.zeros(batch_shape + (field.LIMBS,), jnp.int32).at[..., 0].set(1)
    return (z, one, one, z)


def _is_identity(p):
    X, Y, Z, _ = p
    return field.is_zero(X) & field.eq(Y, Z)


# --- decompression (ZIP-215 permissive) -------------------------------------

def _decompress(b: jnp.ndarray):
    """[..., 32] uint8 -> (x, y, valid). Non-canonical y (>= p) accepted;
    'negative zero' x accepted (reference semantics: ed25519.go:36-44;
    golden model crypto/_ed25519_ref.decompress)."""
    sign = (b[..., 31] >> 7).astype(jnp.int32)
    y_bytes = b.at[..., 31].set(b[..., 31] & 0x7F)
    y = field.bytes_to_limbs(y_bytes)
    yy = field.sqr(y)
    u = yy - _ONE
    v = field.mul(yy, _D) + _ONE
    v3 = field.mul(field.sqr(v), v)
    v7 = field.mul(field.sqr(v3), v)
    x = field.mul(field.mul(u, v3), field.pow_p58(field.mul(u, v7)))
    vxx = field.mul(v, field.sqr(x))
    ok_direct = field.eq(vxx, u)
    ok_flip = field.eq(vxx, -u)
    x = jnp.where(ok_flip[..., None], field.mul(x, _SQRT_M1), x)
    valid = ok_direct | ok_flip
    wrong_sign = field.parity(x) != sign
    x = jnp.where(wrong_sign[..., None], -x, x)
    return x, y, valid


def _to_ext(x, y):
    one = jnp.zeros(x.shape, jnp.int32).at[..., 0].set(1)
    return (x, y, one, field.mul(x, y))


def _neg_ext(p):
    X, Y, Z, T = p
    return (-X, Y, Z, -T)


# --- the verification kernel ------------------------------------------------

# Constant 4-bit window table for the base point: i·B for i in 0..15, in
# extended coordinates (X, Y, Z=1, T=XY), one [16, 32] limb array per
# coordinate.  Host-computed once from the golden model.
def _build_b_table() -> tuple[np.ndarray, ...]:
    pts = [(0, 1)] + [ref.scalar_mult(i, ref.B) for i in range(1, 16)]
    X = np.stack([field.to_limbs(x) for x, _ in pts])
    Y = np.stack([field.to_limbs(y) for _, y in pts])
    Z = np.stack([field.to_limbs(1)] * 16)
    T = np.stack([field.to_limbs(x * y % ref.P) for x, y in pts])
    return X, Y, Z, T


_B_TABLE = tuple(jnp.asarray(c) for c in _build_b_table())
_WINDOWS = 64          # 256 bits as 64 4-bit little-endian windows


def _gather_const_table(table, idx):
    """table: [16, 32] constant; idx: [n] int32 -> [n, 32]."""
    return tuple(jnp.take(c, idx, axis=0) for c in table)


def _gather_lane_table(table, idx):
    """table: [16, n, 32] per-lane; idx: [n] int32 -> [n, 32]."""
    ix = idx[None, :, None]
    return tuple(
        jnp.take_along_axis(c, ix, axis=0)[0] for c in table)


def _verify_kernel(a_bytes, r_bytes, s_win, k_win):
    """Verify N signatures in parallel (interleaved windowed Straus).

    a_bytes, r_bytes: [n, 32] uint8 compressed points (pubkey A, nonce R)
    s_win, k_win:     [64, n] int32 — 4-bit little-endian windows of S and
                      k = SHA512(R||A||msg) mod L
    Returns ok: [n] bool — per-signature ZIP-215 verdicts.

    Evaluates [8](s·B - R - k·A) == identity with a 4-bit windowed ladder:
    per window, 4 doublings + 2 unified adds from precomputed tables
    (constant i·B table; per-lane i·(-A) table built with 15 adds).  The
    unified addition handles identity entries, so window value 0 needs no
    special case — there are no per-bit selects at all.
    """
    ax, ay, a_ok = _decompress(a_bytes)
    rx, ry, r_ok = _decompress(r_bytes)
    neg_a = _neg_ext(_to_ext(ax, ay))
    neg_r = _neg_ext(_to_ext(rx, ry))
    n = a_bytes.shape[0]

    # per-lane table of i·(-A), i in 0..15: [16, n, 32] per coordinate
    entries = [_identity((n,)), neg_a]
    for _ in range(14):
        entries.append(_ext_add(entries[-1], neg_a))
    neg_a_tab = tuple(
        jnp.stack([e[c] for e in entries]) for c in range(4))

    def body(j, acc):
        for _ in range(4):
            acc = _ext_double(acc)
        w = (_WINDOWS - 1) - j
        sw = lax.dynamic_index_in_dim(s_win, w, axis=0, keepdims=False)
        kw = lax.dynamic_index_in_dim(k_win, w, axis=0, keepdims=False)
        acc = _ext_add(acc, _gather_const_table(_B_TABLE, sw))
        acc = _ext_add(acc, _gather_lane_table(neg_a_tab, kw))
        return acc

    # derive the identity init from a (possibly sharded) input so its sharding
    # "varying" type matches the loop body under shard_map
    lane_zero = (s_win[0] * 0)[:, None]
    zero = jnp.zeros((n, field.LIMBS), jnp.int32) + lane_zero
    one = zero.at[..., 0].set(1) + lane_zero
    acc = lax.fori_loop(0, _WINDOWS, body, (zero, one, one, zero))
    acc = _ext_add(acc, neg_r)
    for _ in range(3):                  # cofactor clearing: [8]·
        acc = _ext_double(acc)
    return _is_identity(acc) & a_ok & r_ok


_jit_verify = jax.jit(_verify_kernel)


# --- host orchestration -----------------------------------------------------

_IDENTITY_BYTES = bytes([1] + [0] * 31)     # compressed identity (y=1)
_B_BYTES = ref.compress(ref.B)

# The pad-bucket ladder, the tile and the tile's shape are
# crypto/pipeline's (BUCKETS, TILE, tile_bucket): _BUCKETS here is a
# second name for THE live list, written in place only (the tuner
# below, reset_bucket_tuning, the benchmark's rehearsal).

# --- measured pad-bucket refinement -----------------------------------------
# The base buckets have a 16x gap at the bottom (64 -> 1024): a 100-sig
# commit pads 10x.  On the CPU/XLA path kernel cost scales with padded
# lanes, so that gap is real wasted work — but each extra bucket costs
# a fresh compile, so refinement must be earned by measurement, not
# hardcoded.  The host_prep vs kernel_execute split (already observed
# per dispatch into the metrics-v2 histogram) is the steering signal:
# refine only when kernel_execute dominates host_prep for repeatedly
# low-occupancy warm dispatches of a bucket (on a TPU the kernel is so
# fast that padding costs ~nothing and host_prep dominates — no
# refinement there).

_REFINE_CANDIDATES = (128, 256, 512, 2048)
_TUNE_MIN_SAMPLES = 8
_TUNE_WINDOW = 64
_tune_samples: dict[int, list] = {}     # bucket -> [(n, prep_s, exec_s)]
_REFINED_COUNTER = None


def reset_bucket_tuning() -> None:
    """Test hook: drop refined buckets and samples."""
    _BUCKETS[:] = _BASE_BUCKETS
    _tune_samples.clear()


def _tune_record(n: int, m: int, prep_s: float, exec_s: float) -> None:
    samples = _tune_samples.setdefault(m, [])
    samples.append((n, prep_s, exec_s))
    if len(samples) > _TUNE_WINDOW:
        samples.pop(0)
    lows = [s for s in samples if s[0] <= m // 2]
    if len(lows) < _TUNE_MIN_SAMPLES:
        return
    lows_sorted = sorted(p for _, p, _ in lows)
    execs_sorted = sorted(e for _, _, e in lows)
    med_prep = lows_sorted[len(lows_sorted) // 2]
    med_exec = execs_sorted[len(execs_sorted) // 2]
    # host_prep-dominated (TPU shape): padding wastes almost nothing
    if med_exec < 2 * med_prep:
        return
    target = max(s[0] for s in lows)
    prev = 0
    for b in _BUCKETS:
        if b >= m:
            break
        prev = b
    for cand in _REFINE_CANDIDATES:
        if cand >= m or cand in _BUCKETS or cand < target or \
                cand <= prev:
            continue
        _BUCKETS.append(cand)
        _BUCKETS.sort()
        samples.clear()
        _refine_counter().add()
        return


def _refine_counter():
    global _REFINED_COUNTER
    if _REFINED_COUNTER is None:
        from ..libs import metrics as libmetrics
        _REFINED_COUNTER = libmetrics.DEFAULT.counter(
            "crypto", "pad_bucket_refinements",
            "Pad buckets inserted by the measured host_prep/"
            "kernel_execute steering (small batches were "
            "padding into oversized buckets).")
    return _REFINED_COUNTER


def _windows_u8(scalars: np.ndarray) -> np.ndarray:
    """[m, 32] uint8 little-endian scalars -> [m, 64] uint8 4-bit
    windows, lane-major (window 2i = low nibble of byte i, window
    2i+1 = high nibble) — the host-side wire layout; the device casts
    and transposes to the kernels' window-major int32."""
    m = scalars.shape[0]
    win = np.empty((m, 64), np.uint8)
    win[:, 0::2] = scalars & 0x0F
    win[:, 1::2] = scalars >> 4
    return win


def _win_cols(w8):
    """Device-side: [m, 64] uint8 lane-major windows -> [64, m] int32."""
    return jnp.transpose(w8).astype(jnp.int32)


def _byte_cols(b8):
    """Device-side: [m, 32] uint8 byte rows -> [32, m] int32 columns."""
    return jnp.transpose(b8).astype(jnp.int32)


def _signed_cols(d8):
    """Device-side: [m, k] uint8 two's-complement digits -> [k, m]
    int32 in [-8, 7]."""
    return jnp.transpose(
        lax.bitcast_convert_type(d8, jnp.int8)).astype(jnp.int32)


# The packed wire: everything one dispatch sends to the device is ONE
# [m, 192] uint8 buffer, a lane a row — A | R | S windows | k windows.
# The host fills it in place (native ed25519_prep, or prep_arrays'
# numpy path), one jax.device_put moves it, and the jitted entry
# points below cut the four column ranges on the device.
WIRE_LANE_BYTES = 192


def wire_views(wire):
    """(a_b [m,32], r_b [m,32], s_w8 [m,64], k_w8 [m,64]) of a wire
    buffer, as views: on the host for what wants the four arrays
    (parallel/mesh, tests), on the device inside the jitted kernels."""
    return (wire[:, 0:32], wire[:, 32:64], wire[:, 64:128],
            wire[:, 128:192])


# A tile settled by the batch equation (crypto/pipeline.batch_equation)
# carries more columns after those 192, each a signed 4-bit digit in
# two's complement: c = z·k mod L (64 digits), the lane's 128-bit
# randomizer z (33), and the tile's S = Σ z·s mod L (64), digit t at
# byte t of the S columns read row-major: ONE column when m >= 64.
# The native prep draws z (getrandom, the OS's generator) and computes
# c and S in the same pass; a refused or padding lane has zero digits.
_C_DIGITS, _Z_DIGITS, _S_DIGITS = 64, 33, 64
_S_COL = WIRE_LANE_BYTES + _C_DIGITS + _Z_DIGITS     # 289


def batch_lane_bytes(m: int) -> int:
    """The lane width of a batch-equation wire of m lanes."""
    return _S_COL + -(-_S_DIGITS // m)


def batch_views(wire):
    """(c_d8 [m,64], z_d8 [m,33], s_d8 [m, cols]) of a batch-equation
    wire buffer: the columns after wire_views'."""
    return (wire[:, WIRE_LANE_BYTES:WIRE_LANE_BYTES + _C_DIGITS],
            wire[:, WIRE_LANE_BYTES + _C_DIGITS:_S_COL],
            wire[:, _S_COL:])


def _verify_packed(wire):
    """The xla kernel behind the packed uint8 wire buffer (4x smaller
    than the int32 device layouts); unpacking runs on device."""
    a8, r8, s8, k8 = wire_views(wire)
    return _verify_kernel(a8, r8, _win_cols(s8), _win_cols(k8))


_jit_verify_packed = jax.jit(_verify_packed)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def _pallas_verify_packed(wire, interpret=False, block=0):
    """The pallas kernel behind the packed uint8 wire buffer."""
    from . import ed25519_pallas as ep
    a8, r8, s8, k8 = wire_views(wire)
    return ep.verify_cols(_byte_cols(a8), _byte_cols(r8),
                          _win_cols(s8), _win_cols(k8),
                          interpret=interpret, block=block or ep.BLOCK)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def _pallas_batch_verify_packed(wire, interpret=False, block=0):
    """A tile's verdicts from ONE batch equation: the lanes' decode
    flags when it holds, else today's per-lane kernel on the same wire
    (``lax.cond``: one executable, one launch, one read-back).  Returns
    [m + 1] bool: the mask, then whether the equation held."""
    from . import ed25519_pallas as ep
    block = block or ep.BLOCK
    a8, r8, s8, k8 = wire_views(wire)
    c8, z8, sd8 = batch_views(wire)
    a, r = _byte_cols(a8), _byte_cols(r8)
    digits = jnp.concatenate([_signed_cols(c8), _signed_cols(z8)])
    s_digits = _signed_cols(sd8.reshape(1, -1)[:, :_S_DIGITS])
    held, decoded = ep.batch_cols(a, r, digits, s_digits,
                                  interpret=interpret, block=block)
    mask = lax.cond(
        held, lambda: decoded,
        lambda: ep.verify_cols(a, r, _win_cols(s8), _win_cols(k8),
                               interpret=interpret, block=block))
    return jnp.concatenate([mask, held[None]])


def verify_batch(
    items: Sequence[tuple[bytes, bytes, bytes]],
) -> tuple[bool, list[bool]]:
    """Verify [(pub, msg, sig), ...] on the default JAX device.

    Batches above one pipeline tile (crypto/pipeline.tile_bucket,
    1,024 lanes) are fed to a TilePipeline in
    the balanced chunks of crypto/pipeline.tile_plan (10,000 as ten
    1,000-lane tiles), all at the tile's bucket: while tile i
    executes under JAX's async dispatch, the native thread preps
    tile i+1.  Smaller batches keep the monolithic single-bucket
    dispatch.  A caller that has its items one at a time does not
    wait for the whole list: crypto/batch.GuardedTpuBatchVerifier
    feeds the same pipeline a full tile at a time from ``add()``.

    Returns (all_valid, per_sig_mask) — the reference BatchVerifier.Verify
    contract (crypto/crypto.go:47).
    """
    n = len(items)
    if n == 0:
        return True, []
    tile = tile_bucket()
    if n <= tile:
        out = np.zeros(n, bool)
        out[:] = _verify_chunk(items)
        return bool(out.all()), out.tolist()
    pipe = TilePipeline(tile)
    for lo, hi in tile_plan(n, tile):
        pipe.feed(items[lo:hi])
    return pipe.finish()


_STREAMED_TILES = None


def streamed_tiles_counter():
    """Tiles a TilePipeline launched, by whether the launch found the
    tile's prep done (``ready``) or waited for it (``waited``): the
    counter that says the prep runs beside its caller."""
    global _STREAMED_TILES
    if _STREAMED_TILES is None:
        from ..libs import metrics as libmetrics
        _STREAMED_TILES = libmetrics.DEFAULT.counter(
            "crypto", "streamed_tiles_total",
            "Tiles the device pipeline launched, by whether the "
            "launch found the tile's host prep finished (ready) or "
            "waited for it (waited).", labels=("prep",))
    return _STREAMED_TILES


_BATCH_EQUATION = None


def batch_equation_counter():
    """Pallas dispatches by what settled them: ``held`` (the tile's
    batch equation held, so every lane took its decode flag),
    ``failed`` (it did not, and the per-lane kernel decided every
    lane), ``bypassed`` (a shape below the tile's, which the per-lane
    kernel alone decides).  Counted where a dispatch is settled."""
    global _BATCH_EQUATION
    if _BATCH_EQUATION is None:
        from ..libs import metrics as libmetrics
        _BATCH_EQUATION = libmetrics.DEFAULT.counter(
            "crypto", "batch_equation_total",
            "Single-device Pallas dispatches by what settled them: "
            "the tile's batch equation held, failed (the per-lane "
            "kernel then decided every lane), or was bypassed (a "
            "shape below the tile's).", labels=("outcome",))
    return _BATCH_EQUATION


def _settled(out: np.ndarray, m: int, choice: str,
             single: bool) -> np.ndarray:
    """A settled dispatch's [m] mask, its batch equation counted: a
    batch-equation entry hands back [m + 1], the held bit last."""
    if out.shape[0] > m:
        batch_equation_counter().with_labels(
            "held" if out[m] else "failed").add()
        return out[:m]
    if choice == "pallas" and single:
        batch_equation_counter().with_labels("bypassed").add()
    return out


# a tile the pipeline has launched and not settled
_Launched = collections.namedtuple("_Launched",
                                   "n warm pre_bad dev span")


class TilePipeline:
    """The tiled, overlapped dispatch, fed a tile at a time.

    ``feed(chunk)`` BEGINS the chunk's host prep, which runs on the
    native module's prep thread (native ed25519_prep_begin: no GIL),
    launches the chunk fed before it, whose prep ran while the caller
    did something else (the seam's caller walked 1,024 signatures:
    2.7 ms for 1.2), and settles, without waiting, the tiles whose
    kernels have finished.  So a feed costs its caller a hand-off and
    a launch (JAX async dispatch: the jitted call returns a device
    future, and the mask's copy to the host is queued behind the
    kernel at once); tile i is launched by feed i+1, or by
    ``finish()``.  At most IN_FLIGHT tiles are launched and not
    settled: one more waits for the oldest.  ``finish()`` waits for
    the last prep, launches it, settles what is left and hands back
    (all_valid, mask) in feed order.  The one implementation of
    prep, launch, settle, pre_bad and mask assembly above one tile:
    verify_batch feeds it a planned list, the seam's verifier feeds
    it from ``add()`` while its caller is still walking (``eager``).

    Every chunk, the last and shortest too, dispatches at the ONE
    shape of the tile's bucket: warmup() warms exactly that.
    Multi-chip meshes pre-partition ONCE per process
    (parallel/mesh.pipeline_partitioner) so per-tile dispatch pays no
    mesh/sharding re-resolution.

    A tile's host_prep span is the prep's OWN time, from the native
    thread's clock readings, recorded when the tile is launched: a
    cost, not a wait.  What the launch waited for it is
    ``prep_wait_us`` on the tile's kernel_execute span (0 when the
    overlap works) and the ``prep`` label of
    crypto_streamed_tiles_total.  The kernel_execute span runs from
    the instant before the tile's _launch to the end of its settle
    (h2d and launch at its start, device_wait and d2h at its end);
    the spans of tiles in flight overlap.  A span records when it
    ends: a pipeline dropped with a prep or a tile in flight (its
    caller raised first) records nothing for it and raises nothing
    later; the prep's handle takes its job back or lets it finish."""

    IN_FLIGHT = 4

    def __init__(self, tile: int):
        self._choice = _kernel_choice()
        self._m = _padded(tile, self._choice)
        self._part = _partitioner(self._m, self._choice)
        self._batch = _batched(self._m, self._choice)
        self._hist = dispatch_histogram()
        self._masks: list[np.ndarray] = []
        self._prepping = None       # (handle, n, eager): begun
        self._launched = collections.deque()    # _Launched, feed order
        self._tiles = 0
        self._t_run0 = tracing.now_ns()
        self._phase_s = 0.0

    def feed(self, chunk, eager: bool = False) -> None:
        """Take one chunk of at most a tile of (pub, msg, sig) items.
        ``eager`` marks a tile fed before its batch was complete (the
        span's attribute, which
        benchmark/layers/eager_tiles_per_commit counts)."""
        prev, self._prepping = self._prepping, (
            _prep_begin(chunk, self._m, self._batch), len(chunk), eager)
        if prev is not None:
            self._launch_tile(*prev)
        while self._launched and self._launched[0].dev.is_ready():
            self._settle()

    def _launch_tile(self, handle, n: int, eager: bool) -> None:
        choice, m = self._choice, self._m
        wire, pre_bad, t0, elapsed, waited = handle.result()
        wire, pre_bad = _wire_arrays(wire, pre_bad, m)
        warm = (choice, m) in _SEEN_SHAPES
        tracing.record_span(tracing.CRYPTO, "host_prep", t0,
                            t0 + elapsed, batch=n, bucket=m,
                            pipelined=True)
        pad_bucket = str(m)
        self._hist.with_labels("host_prep", choice, pad_bucket,
                               "1" if warm else "0").observe(
                                   elapsed / 1e9)
        self._phase_s += elapsed / 1e9
        streamed_tiles_counter().with_labels(
            "waited" if waited else "ready").add()
        if len(self._launched) >= self.IN_FLIGHT:
            self._settle()
        attrs = {"eager": True} if eager else {}
        sp = tracing.timed(tracing.CRYPTO, "kernel_execute", batch=n,
                           bucket=m, kernel=choice, warm=warm,
                           pipelined=True, tile=self._tiles,
                           prep_wait_us=waited // 1000,
                           **attrs).begin()
        with tracing.under(sp):
            dev = _launch(wire, choice=choice, part=self._part)
        dev.copy_to_host_async()
        _SEEN_SHAPES.add((choice, m))
        self._tiles += 1
        self._launched.append(_Launched(n, warm, pre_bad, dev, sp))

    def _settle(self) -> None:
        """The oldest launched tile: wait, mask, span."""
        n, warm, pre_bad, dev, sp = self._launched.popleft()
        with tracing.under(sp):
            ok = _force(dev, sp)
        sp.end()
        # launch -> settled: the window the device (or the XLA runtime
        # thread) owned the tile; tiles in flight overlap each other
        # and the preps beside them, which is what the overlap ratio
        # reads above 1.0
        choice, pad_bucket = self._choice, str(self._m)
        self._hist.with_labels("kernel_execute", choice, pad_bucket,
                               "1" if warm else "0").observe(sp.seconds)
        self._phase_s += sp.seconds
        ok = _settled(ok, self._m, choice, self._part is None)
        ok = ok[:n].copy()
        ok[pre_bad[:n]] = False
        self._masks.append(ok)

    def finish(self) -> tuple[bool, list[bool]]:
        """Launch the last tile and settle every tile; (all_valid,
        mask) over everything fed, in feed order."""
        prev, self._prepping = self._prepping, None
        if prev is not None:
            self._launch_tile(*prev)
        while self._launched:
            self._settle()
        wall = (tracing.now_ns() - self._t_run0) / 1e9
        if wall > 0:
            overlap_histogram().observe(self._phase_s / wall)
        with tracing.span(tracing.CRYPTO, "mask_handback"):
            out = np.concatenate(self._masks) if self._masks \
                else np.zeros(0, bool)
            return bool(out.all()), out.tolist()


def _with_frame_room(fn, *args, **kwargs):
    """Call fn with room on the interpreter's frame stack.

    CPython 3.12 keeps a thread's Python frames in 16 KiB chunks and
    frees a chunk the moment its first frame returns.  A loop whose own
    frame ends a chunk therefore maps and unmaps a chunk for every call
    it makes — ~9 us a call here, far more on the chip's sealed machine
    — and tracing and lowering the ~43,000-equation Pallas kernel is
    such loops at a hundred depths.  Whether one of them lands on a
    boundary depends on every frame above it, so a shape's set-up read
    19 s or 42 s (3 s clear of a boundary) on the stack depth of the
    caller and on edits that moved a local variable (PERF.md, PR 26).
    This function's frame asks for 1 MiB, which the interpreter rounds
    up to a 2 MiB chunk: everything fn calls lives in that one chunk.
    Mapping it costs ~11 us, so only a cold shape is called this way."""
    return fn(*args, **kwargs)


_with_frame_room.__code__ = _with_frame_room.__code__.replace(
    co_stacksize=1 << 17)


def _launch(wire, *, choice: str, interpret: bool = False,
            block: int = 0, part=None):
    """Dispatch the selected kernel on one wire buffer WITHOUT forcing
    the result: the un-forced device array comes back (JAX async
    dispatch), and the caller settles it with _force — the pipeline
    only after the next tile is in flight.  Every dispatch and the
    warm-up go through here, so a shape warmed is the executable the
    live path runs.

    A single-device dispatch is one non-blocking ``jax.device_put`` of
    the whole buffer and one jitted call of one argument: a transfer
    costs ~0.27 ms a call on a v5e whatever its size (PERF.md, PR 24
    and 26).  No buffer is donated: a tile's wire is 197 KB at the
    1,024 bucket and at most TilePipeline.IN_FLIGHT tiles are in
    flight.  A wire that carries the batch equation's columns (its
    prep's shape was the tile's: crypto/pipeline.batch_equation) goes
    to the batch-equation entry, any other Pallas wire to the per-lane
    one."""
    if part is not None:
        return part.dispatch(*wire_views(wire))
    with tracing.span(tracing.CRYPTO, "h2d"):
        dw = jax.device_put(wire)
    if choice == "pallas":
        entry = _pallas_batch_verify_packed \
            if wire.shape[1] > WIRE_LANE_BYTES else _pallas_verify_packed
        fn = functools.partial(entry, interpret=interpret, block=block)
    else:
        fn = _jit_verify_packed
    with tracing.span(tracing.CRYPTO, "launch"):
        if (choice, wire.shape[0]) in _SEEN_SHAPES:
            return fn(dw)
        # a shape's first call traces, lowers and compiles inside it
        return _with_frame_room(fn, dw)


def _force(dev, sp=None) -> np.ndarray:
    """Block until the kernel finishes and bring the mask to the host,
    recording on the kernel_execute span where it was computed — the
    platform and device count of the output array itself, not of a
    label chosen before the dispatch.

    With the recorder on, the wait and the read-back are two spans;
    the copy is queued behind the kernel before the wait, as the bare
    np.asarray of the untraced path does (waiting first and only then
    asking for it cost 0.12 ms a dispatch on a v5e; PERF.md, PR 24)."""
    if tracing.enabled(tracing.CRYPTO):
        with tracing.span(tracing.CRYPTO, "device_wait"):
            dev.copy_to_host_async()
            dev.block_until_ready()
        with tracing.span(tracing.CRYPTO, "d2h"):
            ok = np.asarray(dev)
    else:
        ok = np.asarray(dev)
    if sp is not None:
        devs = dev.devices()
        sp.note(platform=next(iter(devs)).platform, devices=len(devs))
    return ok


def _kernel_choice() -> str:
    """'pallas' (the fused Mosaic 24-limb kernel) on a TPU, 'xla'
    (portable) on the CPU devices the kernel tests run on, where the
    Pallas kernel would run interpreted: ops/device.py decides, and
    ``auto`` is the only value a node runs with.

    COMETBFT_TPU_KERNEL=pallas|xla is how tests, chip_smoke.py and
    the benchmark's rehearsal get the Pallas kernel on a CPU (they
    then pass ``interpret=`` to _launch); it is no setting for
    operators."""
    choice = os.environ.get("COMETBFT_TPU_KERNEL", "auto").lower()
    if choice in ("pallas", "xla"):
        return choice
    return "pallas" if device.probe().is_tpu else "xla"


def _batched(m: int, choice: str) -> bool:
    """Whether a dispatch of m lanes is prepped with the batch
    equation's columns and settled by it: the Pallas kernel on one
    device, at the shape crypto/pipeline.batch_equation names.  The
    XLA kernel and the mesh partitioner keep the per-lane path, and so
    does a host without the native prep, the columns' one producer."""
    return choice == "pallas" and batch_equation(m) and \
        not _sharded(m) and _native_prep() is not None


def _padded(n: int, choice: str) -> int:
    """The lane count a chunk of n signatures dispatches at: its pad
    bucket, and at least one grid block for the Pallas kernel."""
    m = _bucket(n)
    if choice == "pallas":
        from . import ed25519_pallas as ep
        m = max(m, ep.BLOCK)
    return m


def _verify_chunk(items) -> np.ndarray:
    n = len(items)
    choice = _kernel_choice()
    m = _padded(n, choice)
    warm = (choice, m) in _SEEN_SHAPES
    hist = dispatch_histogram()
    # each phase's one pair of clock readings feeds its span and
    # crypto_kernel_dispatch_seconds
    with tracing.timed(tracing.CRYPTO, "host_prep", batch=n,
                       bucket=m) as prep:
        wire, pre_bad = prep_arrays(items, m, _batched(m, choice))
    # compile-vs-execute attribution: the first dispatch of a
    # (kernel, bucket) shape includes trace+compile (unless warmup()
    # or the persistent cache served it); warm dispatches are pure
    # execution
    with tracing.timed(tracing.CRYPTO, "kernel_execute", batch=n,
                       bucket=m, kernel=choice, warm=warm) as sp:
        out = _dispatch(n, wire, pre_bad, sp=sp)
    pad_bucket = str(m)
    hist.with_labels("host_prep", choice, pad_bucket,
                     "1" if warm else "0").observe(prep.seconds)
    hist.with_labels("kernel_execute", choice, pad_bucket,
                     "1" if warm else "0").observe(sp.seconds)
    if warm:
        # only warm dispatches steer bucket refinement — a cold one
        # includes trace+compile, which is exactly the cost refinement
        # must NOT mistake for per-lane kernel work
        _tune_record(n, m, prep.seconds, sp.seconds)
    _SEEN_SHAPES.add((choice, m))
    return out


def _padding_wire(m: int, batch: bool = False) -> np.ndarray:
    """A wire buffer of m padding lanes, which verify trivially:
    0·B - identity - 0·A == identity; with ``batch`` the batch
    equation's zero digits too, which add the identity."""
    wire = np.zeros((m, batch_lane_bytes(m) if batch
                     else WIRE_LANE_BYTES), np.uint8)
    a_b, r_b, _, _ = wire_views(wire)
    a_b[:] = np.frombuffer(_B_BYTES, np.uint8)
    r_b[:] = np.frombuffer(_IDENTITY_BYTES, np.uint8)
    return wire


def _native_prep():
    """The native module when it holds the C prep, else None (said
    once, because the numpy path is slow)."""
    global _WARNED_NO_NATIVE
    from ..crypto._native_loader import load as _load_native
    native = _load_native(allow_build=False)
    if native is not None and hasattr(native, "ed25519_prep_begin"):
        return native
    if not _WARNED_NO_NATIVE:
        _WARNED_NO_NATIVE = True
        from ..libs.log import new_logger
        new_logger("crypto").warn(
            "native module not built; device host prep is running "
            "per-item in Python")
    return None


def _wire_arrays(wire: bytes, pre_bad: bytes, m: int):
    """The native prep's two buffers as (wire [m, lane bytes]u8,
    pre_bad [m]bool)."""
    return (np.frombuffer(wire, np.uint8).reshape(m, -1),
            np.frombuffer(pre_bad, np.uint8).astype(bool))


class _PrepNow:
    """prep_arrays behind the native handle's interface, for a host
    without the native module: the prep runs in result(), on its
    caller's thread."""

    def __init__(self, items, m: int):
        self._args = (items, m)

    def result(self):
        t0 = tracing.now_ns()
        wire, pre_bad = prep_arrays(*self._args)
        elapsed = tracing.now_ns() - t0
        return (wire.tobytes(), pre_bad.astype(np.uint8).tobytes(),
                t0, elapsed, elapsed)


def _prep_begin(items, m: int, batch: bool = False):
    """Begin prep_arrays(items, m, batch) beside the caller: the native
    module's handle (phase 2 on its prep thread, the GIL never taken
    there), whose ``result()`` waits with the GIL released and returns
    (wire, pre_bad, start_ns, elapsed_ns, waited_ns) — the two
    buffers, the prep's own clock readings on tracing.now_ns's clock
    and how long the call waited, 0 when the prep had finished."""
    native = _native_prep()
    if native is None:
        return _PrepNow(items, m)
    return native.ed25519_prep_begin(items, m, _B_BYTES,
                                     _IDENTITY_BYTES, batch)


def prep_arrays(items, m: int, batch: bool = False):
    """The full host-side prep for a batch of (pub, msg, sig) items,
    padded to m lanes: length/canonical-S checks, k = SHA-512(R||A||msg)
    mod L, 4-bit window split.  Returns (wire [m,192]u8, pre_bad
    [m]bool): the one packed buffer a dispatch sends to the device, a
    lane a row of A (32 bytes) | R (32) | S windows (64) | k windows
    (64) — wire_views names the four column ranges; the device cuts
    them, transposes and casts to the kernels' int32 layouts, so the
    wire stays at 1 byte per element and one transfer.  Padding lanes
    hold B, the identity and zero windows, and verify trivially.  With
    ``batch`` (a tile settled by the batch equation) the lane is
    batch_lane_bytes(m) wide: the batch equation's digits follow
    (batch_views), from a fresh z a lane; only the native prep writes
    them (_batched).  Uses
    the one-pass C prep when the native module is built (the node
    builds it at start: ed25519_prep, which is _prep_begin's prep run
    on the calling thread with the GIL released), else the vectorized
    numpy path with a per-item Python SHA-512 — and says so once,
    because that path is slow.  Both fill the buffer in place: nothing
    is concatenated."""
    native = _native_prep()
    if native is not None:
        return _wire_arrays(*native.ed25519_prep(
            items, m, _B_BYTES, _IDENTITY_BYTES, batch), m)
    if batch:
        raise RuntimeError("the batch equation's columns need the "
                           "native prep")

    wire = _padding_wire(m)
    a_b, r_b, s_w8, k_w8 = wire_views(wire)
    pre_bad = np.zeros(m, bool)

    # ---- host prep, vectorized (it sits inside the <5 ms e2e budget:
    # a python per-item loop alone costs ~40 ms at 10k sigs) ----------
    good_idx = []
    pubs = []
    rs = []
    ss = []
    hashed = []            # R || A || msg per good item
    for i, (pub, msg, sig) in enumerate(items):
        if len(pub) != 32 or len(sig) != 64:
            pre_bad[i] = True
            continue
        good_idx.append(i)
        pubs.append(pub)
        rs.append(sig[:32])
        ss.append(sig[32:])
        hashed.append(sig[:32] + pub + msg)
    if good_idx:
        gi = np.asarray(good_idx)
        a_g = np.frombuffer(b"".join(pubs), np.uint8).reshape(-1, 32)
        r_g = np.frombuffer(b"".join(rs), np.uint8).reshape(-1, 32)
        s_g = np.frombuffer(b"".join(ss), np.uint8).reshape(-1, 32)
        # non-canonical S (>= L) rejection, vectorized as a
        # lexicographic big-endian compare (ZIP-215 requires S < L)
        s_be = s_g[:, ::-1]
        L_be = np.frombuffer(L.to_bytes(32, "big"), np.uint8)
        neq = s_be != L_be
        first = np.argmax(neq, axis=1)
        differs = neq.any(axis=1)
        s_ok = differs & (s_be[np.arange(len(gi)), first] <
                          L_be[first])
        pre_bad[gi[~s_ok]] = True
        # k = SHA-512(R || A || msg) mod L via the python reference —
        # this branch runs when the native module is absent or lacks
        # ed25519_prep (both native entry points ship together, so a
        # partial module cannot occur through our own loader)
        k_g = np.zeros((len(gi), 32), np.uint8)
        for j, buf in enumerate(hashed):
            k = ref.sha512_mod_l(buf[:32], buf[32:64], buf[64:])
            k_g[j] = np.frombuffer(k.to_bytes(32, "little"),
                                   np.uint8)
        keep = gi[s_ok]
        a_b[keep] = a_g[s_ok]
        r_b[keep] = r_g[s_ok]
        s_w8[keep] = _windows_u8(s_g[s_ok])
        k_w8[keep] = _windows_u8(k_g[s_ok])
    return wire, pre_bad


# Smallest padded batch that shards over a multi-device mesh.  Small
# batches stay single-device — the collective + copy overhead dwarfs
# the kernel there.
SHARD_MIN = 1024


def _sharded(m: int) -> bool:
    return device.probe().count > 1 and m >= SHARD_MIN


def _partitioner(m: int, choice: str, interpret: bool = False,
                 block: int = 0):
    """Multi-chip: when more than one JAX device is visible and the
    padded batch is at least SHARD_MIN lanes, the batch
    shards data-parallel over the full device mesh
    (parallel/mesh.py; SURVEY §2.11).  Returns that mesh's
    partitioner, or None for a single-device dispatch."""
    if _sharded(m):
        from ..parallel import mesh as pmesh
        return pmesh.pipeline_partitioner(device.probe().count, choice,
                                          interpret, block)
    return None


def _dispatch(n: int, wire, pre_bad, *, kernel: str = "",
              interpret: bool = False, block: int = 0,
              sp=None) -> np.ndarray:
    """Run the selected kernel on a prepped wire buffer and settle it.
    kernel/interpret/block override the environment-driven choice
    (used by the interpret-mode Pallas parity tests, which exercise
    this exact path with a small block)."""
    choice = kernel or _kernel_choice()
    m = wire.shape[0]
    part = _partitioner(m, choice, interpret, block)
    ok = _force(_launch(wire, choice=choice, interpret=interpret,
                        block=block, part=part), sp)
    ok = _settled(ok, m, choice, part is None)[:n].copy()
    ok[pre_bad[:n]] = False
    return ok


def warmup(n: int) -> None:
    """Pre-compile the shape a batch of n signatures dispatches at:
    the bucket covering n, or above one tile the tile's bucket — the
    one shape of every TilePipeline chunk, planned by verify_batch
    or fed from the seam's ``add()`` (6,667 signatures: 1,024)."""
    _warmup_bucket(_padded(min(n, tile_bucket()), _kernel_choice()))


@functools.lru_cache(maxsize=None)
def _warmup_bucket(m: int) -> None:
    choice = _kernel_choice()
    wire = _padding_wire(m, _batched(m, choice))
    with tracing.span(tracing.CRYPTO, "kernel_compile", bucket=m,
                      kernel=choice) as sp:
        _force(_launch(wire, choice=choice,
                       part=_partitioner(m, choice)), sp)
    _SEEN_SHAPES.add((choice, m))
