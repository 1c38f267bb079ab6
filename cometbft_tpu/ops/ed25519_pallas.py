"""Pallas TPU kernel for batch ed25519 verification — 24-limb radix.

The hot path of the framework (reference seam: crypto/ed25519/ed25519.go
BatchVerifier → types/validation.go verifyCommitBatch).  One fused Mosaic
kernel verifies a block of lanes end-to-end: ZIP-215 decompression,
4-bit-windowed Straus ladder for [8](s·B - R - k·A), and the identity
test — all in VMEM.

Second-generation field arithmetic (the r3 cost model's prescription,
KERNEL_NOTES.md): 24 balanced limbs in an (11, 11, 10)-bit cycle
(ops/field24.py has the schedule rationale and the int32 bounds
analysis).  The limb convolution drops from the 1024 slab MACs of
the first-generation 32x8-bit kernel (removed; KERNEL_NOTES.md)
to 576, and the off-grid x2 corrections are separable by residue
class, so each of the 24 slab MACs just picks one of three pre-scaled
copies of the multiplier.

Round-4 carry discipline: conv inputs that are already resting values
(_norm outputs, pre-balanced constants) skip the input carry pass
entirely; sums/differences of resting values and raw byte digits get
exactly one pass, applied once per value even when it feeds several
products.  The exact per-position worst case (field24.conv_bound over
the resting fixed point, re-derived in tests/test_field24.py) is a
1.474e9 conv accumulator and 1.744e9 carry pre-scale — both < 2^31.
This removes ~60% of the input carry passes (~10% of kernel ops).

Inputs are what the host prep makes: [32, B] byte columns for
A and R, [64, B] nibble windows for s and k — the host prep and the
dispatch are unchanged; bytes convert to limbs in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..crypto import _ed25519_ref as ref
from . import field24 as f24

LIMBS = f24.LIMBS               # 24
_FOLD = f24.FOLD                # 38 = 2^256 mod p
_SIZES = f24.SIZES
_OFFS = f24.OFFSETS
BLOCK = 128                     # lanes per grid step
_WINDOWS = 64

def _carry_row_consts():
    """Per-row carry constants ([24, 1], broadcast over lanes), built
    from iota because Mosaic kernels cannot capture ndarray constants:
    prescale m = 2^(11 - t_i) makes every row's rounding shift 11
    bits; weight 2^t_i undoes the scale when reconstructing the low
    part.  CSE collapses the repeats across carry passes."""
    m10 = (lax.broadcasted_iota(jnp.int32, (LIMBS, 1), 0) % 3) == 2
    prescale = jnp.where(m10, 2, 1)
    weight = jnp.where(m10, 1 << 10, 1 << 11)
    return prescale, weight


# --- balanced carry / field multiply ---------------------------------------

def _carry(x):
    """One balanced (round-to-nearest) carry pass, limb-major [24, B].
    The top carry folds into limb 0 at weight 38 and is immediately
    split again (fold-settle) so limb 0 keeps its resting bound.

    Vectorized across the limb dimension: per-row ops on [1, B] slices
    use one sublane of each (8, 128) int32 vreg — 1/8 of the VPU — so
    a 24-row loop here costs ~8x what a full [24, B] op does (measured
    on v5e: the row-sliced form put the whole kernel at ~126 ms for a
    16k batch, ~3x the full-utilization prediction).

    The per-row rounding shift uses the pre-scale trick instead of a
    two-way select on the (11, 11, 10) size cycle: z = x·m with
    m = 2^(11-t_i) ∈ {1, 2} makes every row an 11-bit shift, and
    lo = x - c·2^t_i is a per-row constant multiply.  Bound: under the
    relaxed carry discipline (resting operands enter the conv without
    an input pass) the exact per-position worst case of |x·m| is
    1.744e9 < 2^31 — 1.23x headroom (field24.conv_bound/resting_bound;
    re-derived in tests/test_field24.py)."""
    prescale, weight = _carry_row_consts()
    c = (x * prescale + 1024) >> 11
    lo = x - c * weight
    f = c[LIMBS - 1:] * _FOLD
    fc = (f + 1024) >> 11               # limb 0 is an 11-bit position
    y = lo + jnp.concatenate([f - (fc << 11), c[:LIMBS - 1]], axis=0)
    return jnp.concatenate([y[0:1], y[1:2] + fc, y[2:]], axis=0)


def _norm(x, passes):
    for _ in range(passes):
        x = _carry(x)
    return x


def _mul(a, b, pats, ca=1, cb=1):
    """Field multiply, limb-major.  ca/cb = input carry passes (0 or
    1) under the relaxed magnitude discipline (round 4):

      * ca=0 — operand is a RESTING value (a _norm(.., 2) output, a
        pre-balanced constant, or resting + O(1)).  With both operands
        resting, the exact worst-case conv accumulator is 1.474e9 and
        the carry pass's x*prescale peaks at 1.744e9 < 2^31 (1.23x
        headroom) — field24.conv_bound/resting_bound compute this and
        tests/test_field24.py re-derives it.
      * ca=1 — operand is a sum/difference of up to 4 resting values
        (or raw byte digits); one balanced pass brings it under ~1100
        per limb.  That is NOT elementwise below resting (resting
        limbs cycle down to ~543), so safety comes from the directly
        computed bounds conv(once, R) and conv(once, once) < 2^31 and
        from the closure property carry²(conv(once, once)) ≤ R —
        both asserted by tests/test_field24.py, not from domination
        by the resting case.

    Default (1,1) is the always-safe round-3 behavior."""
    return _mul_nn(_norm(a, ca), _norm(b, cb), pats)


def _mul_nn(a, b, pats):
    """Multiply of operands already inside the resting bound."""
    pat1, pat2 = pats
    v0 = b
    v1 = b * pat1
    v2 = b * pat2
    bt = []
    for v in (v0, v1, v2):
        w = v * _FOLD
        bt.append(jnp.concatenate([w[1:], v], axis=0))   # [47, B]
    acc = None
    for i in range(LIMBS):
        sl = bt[i % 3][LIMBS - 1 - i:2 * LIMBS - 1 - i]  # [24, B]
        term = sl * a[i:i + 1]
        acc = term if acc is None else acc + term
    return _norm(acc, 2)


def _make_sqr(pats):
    def _sqr(a, ca=0):
        """Square; ca=1 when the input is a sum or raw byte digits
        (same classes as _mul's ca)."""
        a = _norm(a, ca)
        return _mul_nn(a, a, pats)
    return _sqr


def _mul_const(x, c, passes=2):
    """x*c normalized.  passes=1 suffices when the result only feeds
    sums that are themselves carried before entering a conv (one
    balanced pass from 2R lands under ~1100 per limb)."""
    return _norm(x * c, passes)


# --- canonical / comparisons (limb-major) ----------------------------------

_P_DIGITS = [int(v) for v in f24.P_DIGITS]


def _seq_carry(x):
    """Exact sequential sweep: rows -> [0, 2^t_i), plus carry row."""
    outs = []
    c = jnp.zeros_like(x[0:1])
    for i in range(LIMBS):
        t = _SIZES[i]
        v = x[i:i + 1] + c
        outs.append(v & ((1 << t) - 1))
        c = v >> t
    return jnp.concatenate(outs, axis=0), c


def _canonical(x, four_p):
    x = _norm(x, 2)
    x = x + four_p                                        # + 4p > 0
    for _ in range(3):
        x, c = _seq_carry(x)
        x = jnp.concatenate([x[0:1] + _FOLD * c, x[1:]], axis=0)
    for _ in range(2):
        ge = jnp.ones_like(x[0:1], dtype=jnp.bool_)
        gt = jnp.zeros_like(x[0:1], dtype=jnp.bool_)
        for i in range(LIMBS - 1, -1, -1):
            pi = _P_DIGITS[i]
            gt = gt | (ge & (x[i:i + 1] > pi))
            ge = ge & (x[i:i + 1] == pi)
        take = gt | ge
        outs = []
        c = jnp.zeros_like(x[0:1])
        for i in range(LIMBS):
            t = _SIZES[i]
            v = x[i:i + 1] - _P_DIGITS[i] + c
            outs.append(v & ((1 << t) - 1))
            c = v >> t
        sub = jnp.concatenate(outs, axis=0)
        x = jnp.where(take, sub, x)
    return x


def _is_zero(x, four_p):
    c = _canonical(x, four_p)
    nz = c[0:1]
    for i in range(1, LIMBS):
        nz = nz | c[i:i + 1]
    return nz == 0


def _eq(a, b, four_p):
    return _is_zero(a - b, four_p)


def _parity(x, four_p):
    return _canonical(x, four_p)[0:1] & 1


# --- byte -> limb conversion (in VMEM) -------------------------------------

def _from_bytes(b):
    """[32, B] byte values -> [24, B] digits (limb i covers bits
    [OFFSETS[i], OFFSETS[i+1]) of the little-endian value)."""
    rows = []
    for i in range(LIMBS):
        s, t = _OFFS[i], _SIZES[i]
        b0, sh = s >> 3, s & 7
        acc = b[b0:b0 + 1] >> sh
        if sh + t > 8:
            acc = acc + (b[b0 + 1:b0 + 2] << (8 - sh))
        if sh + t > 16 and b0 + 2 < 32:
            acc = acc + (b[b0 + 2:b0 + 3] << (16 - sh))
        rows.append(acc & ((1 << t) - 1))
    return jnp.concatenate(rows, axis=0)


# --- exponentiation chain ---------------------------------------------------

def _pow_p58(x, pats):
    """x^(2^252 - 3) (same chain as field.pow_p58)."""
    _sqr = _make_sqr(pats)

    def pow2k(v, k):
        return lax.fori_loop(0, k, lambda _, u: _sqr(u), v)

    x2 = _sqr(x)
    t = _sqr(_sqr(x2))
    z9 = _mul(x, t, pats, 0, 0)
    z11 = _mul(x2, z9, pats, 0, 0)
    z_5_0 = _mul(z9, _sqr(z11), pats, 0, 0)
    z_10_0 = _mul(pow2k(z_5_0, 5), z_5_0, pats, 0, 0)
    z_20_0 = _mul(pow2k(z_10_0, 10), z_10_0, pats, 0, 0)
    z_40_0 = _mul(pow2k(z_20_0, 20), z_20_0, pats, 0, 0)
    z_50_0 = _mul(pow2k(z_40_0, 10), z_10_0, pats, 0, 0)
    z_100_0 = _mul(pow2k(z_50_0, 50), z_50_0, pats, 0, 0)
    z_200_0 = _mul(pow2k(z_100_0, 100), z_100_0, pats, 0, 0)
    z_250_0 = _mul(pow2k(z_200_0, 50), z_50_0, pats, 0, 0)
    return _mul(x, pow2k(z_250_0, 2), pats, 0, 0)


# --- point ops (extended twisted Edwards, limb-major) ----------------------

def _ext_add(p, q, two_d, pats, need_t=True):
    """Unified add (complete for a=-1).  Carry discipline: inputs are
    resting (point coords are _norm outputs; two_d is pre-balanced),
    sums get exactly one pass, each carried once even when used by two
    products — 8 input passes total vs 18 under the uniform rule."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    a = _mul(Y1 - X1, Y2 - X2, pats)            # 2R x 2R -> 1+1
    b = _mul(Y1 + X1, Y2 + X2, pats)
    c = _mul(_mul(T1, T2, pats, 0, 0), two_d, pats, 0, 0)
    d = _mul_const(_mul(Z1, Z2, pats, 0, 0), 2, passes=1)
    e = _carry(b - a)
    ff = _carry(d - c)
    g = _carry(d + c)
    h = _carry(b + a)
    return (_mul(e, ff, pats, 0, 0), _mul(g, h, pats, 0, 0),
            _mul(ff, g, pats, 0, 0),
            _mul(e, h, pats, 0, 0) if need_t else None)


def _ext_double(p, pats, need_t=True):
    """dbl-2008-hwcd, a=-1: 4 squarings + 4 products (3 when the
    caller doesn't need the extended T coordinate — the formula never
    reads T, so in a run of doublings only the last one, whose output
    feeds an addition, has to produce it)."""
    _sqr = _make_sqr(pats)
    X1, Y1, Z1, _ = p
    a = _sqr(X1)
    b = _sqr(Y1)
    c = _mul_const(_sqr(Z1), 2, passes=1)
    e = _carry(_sqr(X1 + Y1, ca=1) - a - b)     # 3R -> one pass
    g = b - a                                   # 2R
    ff = _carry(g - c)                          # ~2.5R -> one pass
    g = _carry(g)
    h = _carry(-(a + b))
    return (_mul(e, ff, pats, 0, 0), _mul(g, h, pats, 0, 0),
            _mul(ff, g, pats, 0, 0),
            _mul(e, h, pats, 0, 0) if need_t else None)


def _madd_affine(p, q3, pats):
    """Mixed add of a projective-extended point and an AFFINE
    precomputed entry (y-x, y+x, 2d·x·y) with Z2 = 1 — the constant
    B table ships in this form, saving the Z1·Z2 and 2d·T2 products
    of the unified add (madd-2008-hwcd shape): 7 field muls vs 9."""
    X1, Y1, Z1, T1 = p
    y2mx2, y2px2, dt2 = q3
    a = _mul(Y1 - X1, y2mx2, pats, 1, 0)        # table is pre-balanced
    b = _mul(Y1 + X1, y2px2, pats, 1, 0)
    c = _mul(T1, dt2, pats, 0, 0)
    d = Z1 + Z1                                 # 2R; sums below carry
    e = _carry(b - a)
    ff = _carry(d - c)
    g = _carry(d + c)
    h = _carry(b + a)
    return (_mul(e, ff, pats, 0, 0), _mul(g, h, pats, 0, 0),
            _mul(ff, g, pats, 0, 0), _mul(e, h, pats, 0, 0))


def _decompress(b, d_col, sqrt_m1, four_p, pats):
    """b: [32, B] int32 byte values -> (x, y, ok) limb-major [24, B]."""
    sign = b[31:32] >> 7
    yb = jnp.concatenate([b[:31], b[31:32] & 0x7F], axis=0)
    y = _from_bytes(yb)
    one = jnp.concatenate(
        [jnp.ones_like(y[0:1]), jnp.zeros_like(y[1:])], axis=0)
    _sqr = _make_sqr(pats)
    yy = _sqr(y, ca=1)              # y is raw byte digits -> one pass
    u = yy - one                    # resting + O(1)
    v = _mul(yy, d_col, pats, 0, 0) + one
    v3 = _mul(_sqr(v), v, pats, 0, 0)
    v7 = _mul(_sqr(v3), v, pats, 0, 0)
    x = _mul(_mul(u, v3, pats, 0, 0),
             _pow_p58(_mul(u, v7, pats, 0, 0), pats), pats, 0, 0)
    vxx = _mul(v, _sqr(x), pats, 0, 0)
    ok_direct = _eq(vxx, u, four_p)
    ok_flip = _eq(vxx, -u, four_p)
    x = jnp.where(ok_flip, _mul(x, sqrt_m1, pats, 0, 0), x)
    valid = ok_direct | ok_flip
    wrong_sign = _parity(x, four_p) != sign
    x = jnp.where(wrong_sign, -x, x)
    return x, y, valid


# --- constant tables --------------------------------------------------------

def _build_b_table_cols() -> np.ndarray:
    """Constant i·B table in affine-precomputed form, [16, 3, 24, 1]:
    (entry, (y-x | y+x | 2d·x·y), limb, bcast) — the shape
    _madd_affine consumes (entry 0 is the identity: (1, 1, 0))."""
    pts = [(0, 1)] + [ref.scalar_mult(i, ref.B) for i in range(1, 16)]
    out = np.zeros((16, 3, LIMBS, 1), np.int32)
    for i, (x, y) in enumerate(pts):
        out[i, 0, :, 0] = f24.balance(f24.to_limbs((y - x) % ref.P))
        out[i, 1, :, 0] = f24.balance(f24.to_limbs((y + x) % ref.P))
        out[i, 2, :, 0] = f24.balance(
            f24.to_limbs(2 * ref.D * x * y % ref.P))
    return out


_B_TABLE_NP = _build_b_table_cols()

# packed constants: D, 2D, sqrt(-1), 4p, pat1, pat2, then the B table.
# Field-element constants ship pre-balanced (one host-side carry) so
# they can enter the conv without a device-side input pass; 4p stays
# raw — _canonical's unsigned sweep depends on its exact digit rows.
_CONSTS_NP = np.concatenate([
    f24.balance(f24.to_limbs(ref.D)).reshape(LIMBS, 1),
    f24.balance(f24.to_limbs(2 * ref.D % ref.P)).reshape(LIMBS, 1),
    f24.balance(f24.to_limbs(ref.SQRT_M1)).reshape(LIMBS, 1),
    f24.FOUR_P_DIGITS.reshape(LIMBS, 1).astype(np.int32),
    f24.PAT_R1.reshape(LIMBS, 1).astype(np.int32),
    f24.PAT_R2.reshape(LIMBS, 1).astype(np.int32),
    _B_TABLE_NP.reshape(16 * 3 * LIMBS, 1),
], axis=0)


# --- the kernel -------------------------------------------------------------

def _kernel(a_ref, r_ref, swin_ref, kwin_ref, consts_ref, ok_ref,
            tab_ref):
    B = a_ref.shape[1]
    a_b = a_ref[:]
    r_b = r_ref[:]
    d_col = consts_ref[0:LIMBS]
    two_d = consts_ref[LIMBS:2 * LIMBS]
    sqrt_m1 = consts_ref[2 * LIMBS:3 * LIMBS]
    four_p = consts_ref[3 * LIMBS:4 * LIMBS]
    pats = (consts_ref[4 * LIMBS:5 * LIMBS],
            consts_ref[5 * LIMBS:6 * LIMBS])
    b_tab = consts_ref[6 * LIMBS:].reshape(16, 3, LIMBS, 1)

    ax, ay, a_ok = _decompress(a_b, d_col, sqrt_m1, four_p, pats)
    rx, ry, r_ok = _decompress(r_b, d_col, sqrt_m1, four_p, pats)
    zero = jnp.zeros((LIMBS, B), jnp.int32)
    one = jnp.concatenate(
        [jnp.ones((1, B), jnp.int32), zero[1:]], axis=0)

    # -A in extended coords
    nax, nay = -ax, ay
    nat = _mul(nax, nay, pats, 0, 0)

    # per-lane table of i·(-A), i=0..15, in VMEM scratch
    # tab layout: [16, 4*LIMBS, B]
    ident = jnp.concatenate([zero, one, one, zero], axis=0)
    tab_ref[0] = ident
    tab_ref[1] = jnp.concatenate([nax, nay, one, nat], axis=0)

    def build_body(i, _):
        prev = tab_ref[i]
        p = (prev[0:LIMBS], prev[LIMBS:2 * LIMBS],
             prev[2 * LIMBS:3 * LIMBS], prev[3 * LIMBS:])
        q = (nax, nay, one, nat)
        r = _ext_add(p, q, two_d, pats)
        tab_ref[i + 1] = jnp.concatenate(r, axis=0)
        return 0

    lax.fori_loop(1, 15, build_body, 0)

    def _where_tree(w, rows):
        """16-entry select as a binary where-tree over the window's 4
        index bits: 15 selects instead of 16 multiplies + 15 adds (the
        masked-sum form), ~2x fewer VPU ops.  Selected bounds are the
        max of the entries (no arithmetic on the values)."""
        bit = 1
        while len(rows) > 1:
            cond = (w & bit) != 0
            rows = [jnp.where(cond, rows[i + 1], rows[i])
                    for i in range(0, len(rows), 2)]
            bit <<= 1
        return rows[0]

    def select_lane_table(w):
        acc = _where_tree(w, [tab_ref[t] for t in range(16)])
        return (acc[0:LIMBS], acc[LIMBS:2 * LIMBS],
                acc[2 * LIMBS:3 * LIMBS], acc[3 * LIMBS:])

    def select_b_table(w):
        return tuple(_where_tree(w, [b_tab[t, cix] for t in range(16)])
                     for cix in range(3))

    def ladder_body(j, acc):
        # only the last doubling's output feeds an addition, so only
        # it needs the extended T coordinate (3 muls saved each on the
        # first three)
        for i in range(4):
            acc = _ext_double(acc, pats, need_t=(i == 3))
        w = (_WINDOWS - 1) - j
        sw = swin_ref[pl.ds(w, 1)]
        kw = kwin_ref[pl.ds(w, 1)]
        acc = _madd_affine(acc, select_b_table(sw), pats)
        acc = _ext_add(acc, select_lane_table(kw), two_d, pats)
        return acc

    acc = lax.fori_loop(0, _WINDOWS, ladder_body,
                        (zero, one, one, zero))

    # subtract R, clear cofactor, identity test — nothing after the
    # subtraction reads T again
    nrt = _mul(-rx, ry, pats, 0, 0)
    acc = _ext_add(acc, (-rx, ry, one, nrt), two_d, pats,
                   need_t=False)
    for _ in range(3):
        acc = _ext_double(acc, pats, need_t=False)
    X, Y, Z, _T = acc
    ok = _is_zero(X, four_p) & _eq(Y, Z, four_p) & a_ok & r_ok
    ok_ref[:] = jnp.broadcast_to(ok.astype(jnp.int32), (8, B))


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def _pallas_verify(a_cols, r_cols, s_win, k_win, interpret=False,
                   block=BLOCK):
    """a_cols, r_cols: [32, n] int32 byte values; s_win, k_win:
    [64, n] int32 nibble windows.  Returns ok [n] bool.  n must be a
    multiple of block."""
    n = a_cols.shape[1]
    if n % block != 0:
        raise ValueError(
            f"lane count {n} must be a multiple of block {block} — "
            "remainder lanes would never be written by the kernel")
    grid = n // block
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((8, n), jnp.int32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((32, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((32, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_WINDOWS, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_WINDOWS, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_CONSTS_NP.shape[0], 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((8, block), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((16, 4 * LIMBS, block), jnp.int32),
        ],
        interpret=interpret,
    )(a_cols, r_cols, s_win, k_win, jnp.asarray(_CONSTS_NP))
    return out[0] != 0


def verify_cols(a_cols, r_cols, s_win, k_win, interpret=False,
                block=BLOCK):
    return _pallas_verify(a_cols, r_cols, s_win, k_win,
                          interpret=interpret, block=block)


# --- the batch equation: one verdict a tile ---------------------------------
#
# [8](S·B - Σ z_i·R_i - Σ c_i·A_i) == identity, with fresh random
# 128-bit z_i, c_i = z_i·k_i mod L and S = Σ z_i·s_i mod L, all made by
# the host prep: the random-linear-combination equation of the
# reference's BatchVerifier (curve25519-voi), of crypto/_ed25519_ref
# .batch_verify and of the CPU tile kernel (native/ed25519_msm.hpp).
# It holds for a tile whose every decodable lane satisfies the
# cofactored single-signature equation, and for a tile holding a lane
# that does not, with probability about 2^-125 at most.  It says
# nothing about WHICH lane failed: the caller then runs the per-lane
# kernel above on the same wire (ops/ed25519_jax).
#
# As a windowed multi-scalar multiplication: a grid step decodes its
# block's A and R (one _decompress over both, side by side), builds
# the lanes' tables i·(-A) and i·(-R) for i = 0..8 in cached form, and
# adds each lane's term of window j (its signed digit's entry) into
# window j's accumulator, a block of lane-wise partial sums that stays
# in VMEM across the grid.  The S·B term is spread over the grid steps,
# in lane 0 alone, from the constant B table.  The last step folds the
# windows by Horner's rule (4 doublings a window, once a tile, on the
# one accumulator block), sums the block's lanes by rotation, clears
# the cofactor and tests for the identity.  No lane is ever doubled:
# that is where the per-lane ladder's 256 doublings a lane go.

_C_DIGITS = _WINDOWS            # signed 4-bit digits of c < L
_Z_DIGITS = 33                  # signed 4-bit digits of the 128-bit z
DIGIT_ROWS = _C_DIGITS + _Z_DIGITS
_ENTRIES = 9                    # 0..8: a signed digit is in [-8, 7]


def _build_b_cached() -> np.ndarray:
    """Entries 0..8 of the constant B table in cached form,
    [9, 4, 24, 1]: (y-x | y+x | Z = 1 | 2d·x·y) — the affine table of
    _build_b_table_cols with a Z row, read by _cached_add."""
    out = np.zeros((_ENTRIES, 4, LIMBS, 1), np.int32)
    out[:, 0] = _B_TABLE_NP[:_ENTRIES, 0]
    out[:, 1] = _B_TABLE_NP[:_ENTRIES, 1]
    out[:, 2, 0, 0] = 1
    out[:, 3] = _B_TABLE_NP[:_ENTRIES, 2]
    return out


_BATCH_CONSTS_NP = np.concatenate([
    _CONSTS_NP[:6 * LIMBS],
    _build_b_cached().reshape(_ENTRIES * 4 * LIMBS, 1),
], axis=0)


def _cached(p, two_d, pats):
    """Extended point -> cached form (y-x, y+x, Z, 2d·T), [96, B]: the
    operands _ext_add would form from it, formed once for every add
    that reads the entry.  y-x and y+x take the one balanced pass
    _ext_add gives them (the once-carried class of field24's bounds);
    2d·T is a product, resting."""
    X, Y, Z, T = p
    return jnp.concatenate([_carry(Y - X), _carry(Y + X), Z,
                            _mul(T, two_d, pats, 0, 0)], axis=0)


def _cached_add(p, q, pats):
    """p + q for a cached-form q: _ext_add with q's operands formed
    ahead, 8 field multiplies for 9.  Every product meets the operand
    classes it meets in _ext_add."""
    X1, Y1, Z1, T1 = p
    ymx2, ypx2, Z2, t2d2 = q
    a = _mul(Y1 - X1, ymx2, pats, 1, 0)
    b = _mul(Y1 + X1, ypx2, pats, 1, 0)
    c = _mul(T1, t2d2, pats, 0, 0)
    d = _mul_const(_mul(Z1, Z2, pats, 0, 0), 2, passes=1)
    e = _carry(b - a)
    ff = _carry(d - c)
    g = _carry(d + c)
    h = _carry(b + a)
    return (_mul(e, ff, pats, 0, 0), _mul(g, h, pats, 0, 0),
            _mul(ff, g, pats, 0, 0), _mul(e, h, pats, 0, 0))


def _split(rows):
    return tuple(rows[i * LIMBS:(i + 1) * LIMBS] for i in range(4))


def _batch_kernel(a_ref, r_ref, dig_ref, sdig_ref, consts_ref, ok_ref,
                  held_ref, tab_ref, acc_ref, *, s_per_step):
    B = a_ref.shape[1]
    step = pl.program_id(0)
    d_col = consts_ref[0:LIMBS]
    two_d = consts_ref[LIMBS:2 * LIMBS]
    sqrt_m1 = consts_ref[2 * LIMBS:3 * LIMBS]
    four_p = consts_ref[3 * LIMBS:4 * LIMBS]
    pats = (consts_ref[4 * LIMBS:5 * LIMBS],
            consts_ref[5 * LIMBS:6 * LIMBS])
    zero = jnp.zeros((LIMBS, B), jnp.int32)
    one = jnp.concatenate(
        [jnp.ones((1, B), jnp.int32), zero[1:]], axis=0)
    ident = jnp.concatenate([zero, one, one, zero], axis=0)

    @pl.when(step == 0)
    def _():
        def clear(j, _):
            acc_ref[j] = ident
            return 0

        lax.fori_loop(0, _WINDOWS, clear, 0)
        for e in range(_ENTRIES):
            b_e = consts_ref[(6 + 4 * e) * LIMBS:(10 + 4 * e) * LIMBS]
            tab_ref[2, e] = jnp.broadcast_to(b_e, (4 * LIMBS, B))
        held_ref[:] = jnp.zeros(held_ref.shape, jnp.int32)

    # A and R side by side: one decompression over 2B lanes
    x, y, ok2 = _decompress(
        jnp.concatenate([a_ref[:], r_ref[:]], axis=1),
        d_col, sqrt_m1, four_p, pats)
    ok2 = ok2.astype(jnp.int32)
    lane_ok = (ok2[:, :B] & ok2[:, B:]) != 0
    ok_ref[:] = jnp.broadcast_to(lane_ok.astype(jnp.int32), (8, B))

    # i·(-A) | i·(-R) for i = 0..8, cached form, tables 0 and 1
    def store(e, p):
        c = _cached(p, two_d, pats)
        tab_ref[0, e] = c[:, :B]
        tab_ref[1, e] = c[:, B:]

    zero2 = jnp.concatenate([zero, zero], axis=1)
    one2 = jnp.concatenate([one, one], axis=1)
    nx = -x
    p1 = (nx, y, one2, _mul(nx, y, pats, 0, 1))   # y: raw byte digits
    store(0, (zero2, one2, one2, zero2))
    store(1, p1)

    def build(e, p):
        p = _ext_add(p, p1, two_d, pats)
        store(e, p)
        return p

    lax.fori_loop(2, _ENTRIES, build, p1)

    lane = lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def term(t, _):
        # t < 64: window t of c·(-A); t < 97: window t-64 of z·(-R);
        # then this step's share of S's windows, on lane 0 alone
        in_r = t >= _C_DIGITS
        in_s = t >= DIGIT_ROWS
        js = t - DIGIT_ROWS + step * s_per_step
        j = jnp.where(in_s, jnp.minimum(js, _WINDOWS - 1),
                      jnp.where(in_r, t - _C_DIGITS, t))
        table = jnp.where(in_s, 2, jnp.where(in_r, 1, 0))
        d_lane = dig_ref[pl.ds(jnp.minimum(t, DIGIT_ROWS - 1), 1)]
        d_s = sdig_ref[pl.ds(jnp.minimum(js, _WINDOWS - 1), 1)]
        d = jnp.where(
            in_s, jnp.where((lane == 0) & (js < _WINDOWS), d_s, 0),
            jnp.where(lane_ok, d_lane, 0))
        mag = jnp.abs(d)
        rows = [tab_ref[table, e] for e in range(_ENTRIES)]
        bit = 1
        while len(rows) > 2:                  # entries 0..7 by 3 bits
            cond = (mag & bit) != 0
            rows = [jnp.where(cond, rows[i + 1], rows[i])
                    for i in range(0, len(rows) - 1, 2)] + [rows[-1]]
            bit <<= 1
        sel = jnp.where(mag == 8, rows[1], rows[0])
        ymx, ypx, z, t2d = _split(sel)
        neg = d < 0
        q = (jnp.where(neg, ypx, ymx), jnp.where(neg, ymx, ypx), z,
             jnp.where(neg, -t2d, t2d))
        acc_ref[j] = jnp.concatenate(
            _cached_add(_split(acc_ref[j]), q, pats), axis=0)
        return 0

    lax.fori_loop(0, DIGIT_ROWS + s_per_step, term, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        def dbl(_, p):
            return _ext_double(p, pats)

        def horner(i, p):
            p = lax.fori_loop(0, 4, dbl, p)
            return _ext_add(p, _split(acc_ref[_WINDOWS - 2 - i]),
                            two_d, pats)

        p = lax.fori_loop(0, _WINDOWS - 1, horner,
                          _split(acc_ref[_WINDOWS - 1]))

        def fold(i, p):                       # every lane: the sum
            shift = B >> (i + 1)
            return _ext_add(p, tuple(pltpu.roll(c, shift, 1) for c in p),
                            two_d, pats)

        p = lax.fori_loop(0, B.bit_length() - 1, fold, p)
        X, Y, Z, _ = lax.fori_loop(0, 3, dbl, p)
        held = _is_zero(X, four_p) & _eq(Y, Z, four_p)
        held_ref[:] = jnp.broadcast_to(held.astype(jnp.int32),
                                       held_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def _pallas_batch(a_cols, r_cols, digits, s_digits, interpret=False,
                  block=BLOCK):
    """a_cols, r_cols: [32, n] int32 byte values; digits: [97, n]
    int32 signed digits (c's 64, then z's 33); s_digits: [64, 1] S's.
    Returns (held: bool scalar, decoded: [n] bool, A and R both
    decodable).  n must be a multiple of block, a power of two."""
    n = a_cols.shape[1]
    if n % block != 0 or block & (block - 1):
        raise ValueError(
            f"lane count {n} must be a multiple of block {block}, a "
            "power of two")
    grid = n // block
    kernel = functools.partial(_batch_kernel,
                               s_per_step=-(-_WINDOWS // grid))
    ok, held = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((8, n), jnp.int32),
                   jax.ShapeDtypeStruct((8, block), jnp.int32)),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((32, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((32, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((DIGIT_ROWS, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_WINDOWS, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BATCH_CONSTS_NP.shape[0], 1),
                         lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=(pl.BlockSpec((8, block), lambda i: (0, i),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((8, block), lambda i: (0, 0),
                                memory_space=pltpu.VMEM)),
        scratch_shapes=[
            pltpu.VMEM((3, _ENTRIES, 4 * LIMBS, block), jnp.int32),
            pltpu.VMEM((_WINDOWS, 4 * LIMBS, block), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a_cols, r_cols, digits, s_digits, jnp.asarray(_BATCH_CONSTS_NP))
    return held[0, 0] != 0, ok[0] != 0


def batch_cols(a_cols, r_cols, digits, s_digits, interpret=False,
               block=BLOCK):
    return _pallas_batch(a_cols, r_cols, digits, s_digits,
                         interpret=interpret, block=block)
