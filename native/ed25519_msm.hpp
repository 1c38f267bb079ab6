// ed25519 batch verification on the host CPU: random-linear-combination
// batch equation + Pippenger multi-scalar multiplication.
//
// This is the CPU analog of the reference's batch verifier
// (crypto/ed25519/ed25519.go:189-222 — curve25519-voi accumulates
// (pk, msg, sig) triples and verifies them in one multi-exponentiation
// with per-signature randomizers).  The engine's TPU kernel carries the
// same equation on-device; this path serves CPU-only hosts and the
// per-signature OpenSSL loop becomes the fallback that names invalid
// entries after a batch reject.
//
//   accept  iff  [8]( -(sum z_i s_i mod L)·B + sum z_i·R_i
//                      + sum (z_i k_i mod L)·A_i ) == identity
//
// with fresh odd 128-bit z_i, k_i = SHA-512(R||A||msg) mod L, and
// ZIP-215 semantics throughout: permissive A/R decoding (y >= p
// accepted, x=0 with sign=1 accepted), canonical-S required, cofactor
// cleared by the trailing three doublings.  Differentially tested
// against the pure-Python golden model (crypto/_ed25519_ref.py
// batch_verify) in tests/test_native.py.
//
// Field arithmetic: 5x51-bit limbs in uint64, products via unsigned
// __int128 (the standard radix-51 representation).  The unified
// twisted-Edwards addition is COMPLETE for ed25519 (a = -1 is a square
// mod p, d is non-square), so bucket accumulation never needs case
// analysis even for torsion or small-order inputs.
#pragma once

#include <array>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

#include "sha512.hpp"
#include "sha512_mb.hpp"

namespace ed25519_msm {

typedef unsigned __int128 u128;

// ---------------------------------------------------------------- fe

struct fe {
    uint64_t v[5];      // radix 2^51
};

static const uint64_t MASK51 = (uint64_t(1) << 51) - 1;

inline fe fe_zero() { return fe{{0, 0, 0, 0, 0}}; }
inline fe fe_one() { return fe{{1, 0, 0, 0, 0}}; }

inline fe fe_add(const fe& a, const fe& b) {
    fe r;
    for (int i = 0; i < 5; i++) r.v[i] = a.v[i] + b.v[i];
    return r;
}

// a - b + 8p, so the result is nonnegative for any b with limbs
// < 2^54 - 152 (the laziest value the point formulas produce is
// ~2^53.1).  8p limb-wise: limb0 = 2^54 - 152, others 2^54 - 8.
inline fe fe_sub(const fe& a, const fe& b) {
    fe r;
    r.v[0] = a.v[0] + 0x3FFFFFFFFFFF68ull - b.v[0];
    r.v[1] = a.v[1] + 0x3FFFFFFFFFFFF8ull - b.v[1];
    r.v[2] = a.v[2] + 0x3FFFFFFFFFFFF8ull - b.v[2];
    r.v[3] = a.v[3] + 0x3FFFFFFFFFFFF8ull - b.v[3];
    r.v[4] = a.v[4] + 0x3FFFFFFFFFFFF8ull - b.v[4];
    return r;
}

// one carry sweep: limbs -> < 2^52 (top folds at 19)
inline void fe_carry(fe& a) {
    uint64_t c;
    for (int i = 0; i < 4; i++) {
        c = a.v[i] >> 51;
        a.v[i] &= MASK51;
        a.v[i + 1] += c;
    }
    c = a.v[4] >> 51;
    a.v[4] &= MASK51;
    a.v[0] += c * 19;
}

inline fe fe_mul(const fe& a, const fe& b) {
    u128 t0, t1, t2, t3, t4;
    uint64_t a0 = a.v[0], a1 = a.v[1], a2 = a.v[2], a3 = a.v[3],
             a4 = a.v[4];
    uint64_t b0 = b.v[0], b1 = b.v[1], b2 = b.v[2], b3 = b.v[3],
             b4 = b.v[4];
    uint64_t b1_19 = b1 * 19, b2_19 = b2 * 19, b3_19 = b3 * 19,
             b4_19 = b4 * 19;
    t0 = (u128)a0 * b0 + (u128)a1 * b4_19 + (u128)a2 * b3_19 +
         (u128)a3 * b2_19 + (u128)a4 * b1_19;
    t1 = (u128)a0 * b1 + (u128)a1 * b0 + (u128)a2 * b4_19 +
         (u128)a3 * b3_19 + (u128)a4 * b2_19;
    t2 = (u128)a0 * b2 + (u128)a1 * b1 + (u128)a2 * b0 +
         (u128)a3 * b4_19 + (u128)a4 * b3_19;
    t3 = (u128)a0 * b3 + (u128)a1 * b2 + (u128)a2 * b1 +
         (u128)a3 * b0 + (u128)a4 * b4_19;
    t4 = (u128)a0 * b4 + (u128)a1 * b3 + (u128)a2 * b2 +
         (u128)a3 * b1 + (u128)a4 * b0;
    // u128 carry chain: lazy inputs reach ~2^55.3 per limb, so the
    // column sums stay under 5 * 2^55.3 * (19 * 2^55.3) ~ 2^117.2 and
    // the top carry times 19 can exceed 2^64 — keep it wide
    fe r;
    u128 c;
    r.v[0] = (uint64_t)t0 & MASK51; c = t0 >> 51;
    t1 += c;
    r.v[1] = (uint64_t)t1 & MASK51; c = t1 >> 51;
    t2 += c;
    r.v[2] = (uint64_t)t2 & MASK51; c = t2 >> 51;
    t3 += c;
    r.v[3] = (uint64_t)t3 & MASK51; c = t3 >> 51;
    t4 += c;
    r.v[4] = (uint64_t)t4 & MASK51; c = t4 >> 51;
    u128 f = c * 19 + r.v[0];
    r.v[0] = (uint64_t)f & MASK51;
    r.v[1] += (uint64_t)(f >> 51);
    return r;
}

inline fe fe_sq(const fe& a) { return fe_mul(a, a); }

// canonical little-endian bytes (fully reduced mod p)
inline void fe_tobytes(const fe& a, uint8_t out[32]) {
    fe t = a;
    fe_carry(t);
    fe_carry(t);
    // now t < 2^52 + eps per limb and the value is < 2*p + small;
    // subtract p while >= p (at most twice)
    for (int pass = 0; pass < 2; pass++) {
        // compare against p = 2^255 - 19 top-down
        static const uint64_t P[5] = {
            MASK51 - 18, MASK51, MASK51, MASK51, MASK51};
        bool ge = true;
        for (int i = 4; i >= 0; i--) {
            if (t.v[i] > P[i]) { ge = true; break; }
            if (t.v[i] < P[i]) { ge = false; break; }
        }
        if (!ge) break;
        // t -= p  (borrow-propagating)
        uint64_t borrow = 0;
        for (int i = 0; i < 5; i++) {
            uint64_t sub = P[i] + borrow;
            if (t.v[i] >= sub) {
                t.v[i] -= sub;
                borrow = 0;
            } else {
                t.v[i] = t.v[i] + (uint64_t(1) << 51) - sub;
                borrow = 1;
            }
        }
    }
    uint64_t buf[4];
    buf[0] = t.v[0] | (t.v[1] << 51);
    buf[1] = (t.v[1] >> 13) | (t.v[2] << 38);
    buf[2] = (t.v[2] >> 26) | (t.v[3] << 25);
    buf[3] = (t.v[3] >> 39) | (t.v[4] << 12);
    std::memcpy(out, buf, 32);
}

// 255-bit little-endian load (bit 255 must be masked by the caller)
inline fe fe_frombytes(const uint8_t in[32]) {
    uint64_t buf[4];
    std::memcpy(buf, in, 32);
    fe r;
    r.v[0] = buf[0] & MASK51;
    r.v[1] = ((buf[0] >> 51) | (buf[1] << 13)) & MASK51;
    r.v[2] = ((buf[1] >> 38) | (buf[2] << 26)) & MASK51;
    r.v[3] = ((buf[2] >> 25) | (buf[3] << 39)) & MASK51;
    r.v[4] = (buf[3] >> 12) & MASK51;
    return r;
}

inline bool fe_is_zero(const fe& a) {
    uint8_t b[32];
    fe_tobytes(a, b);
    uint8_t acc = 0;
    for (int i = 0; i < 32; i++) acc |= b[i];
    return acc == 0;
}

inline bool fe_eq(const fe& a, const fe& b) {
    return fe_is_zero(fe_sub(a, b));
}

inline fe fe_neg(const fe& a) { return fe_sub(fe_zero(), a); }

inline bool fe_parity(const fe& a) {
    uint8_t b[32];
    fe_tobytes(a, b);
    return b[0] & 1;
}

// a^(2^k) in place
inline fe fe_pow2k(fe a, int k) {
    while (k--) a = fe_sq(a);
    return a;
}

// a^((p-5)/8) = a^(2^252 - 3): the sqrt-chain core
inline fe fe_pow22523(const fe& a) {
    fe x2 = fe_sq(a);                       // 2
    fe x4 = fe_sq(x2);                      // 4
    fe x8 = fe_sq(x4);                      // 8
    fe z9 = fe_mul(a, x8);                  // 9
    fe z11 = fe_mul(x2, z9);                // 11
    fe z22 = fe_sq(z11);                    // 22
    fe z_5_0 = fe_mul(z9, z22);             // 2^5 - 2^0
    fe z_10_0 = fe_mul(fe_pow2k(z_5_0, 5), z_5_0);
    fe z_20_0 = fe_mul(fe_pow2k(z_10_0, 10), z_10_0);
    fe z_40_0 = fe_mul(fe_pow2k(z_20_0, 20), z_20_0);
    fe z_50_0 = fe_mul(fe_pow2k(z_40_0, 10), z_10_0);
    fe z_100_0 = fe_mul(fe_pow2k(z_50_0, 50), z_50_0);
    fe z_200_0 = fe_mul(fe_pow2k(z_100_0, 100), z_100_0);
    fe z_250_0 = fe_mul(fe_pow2k(z_200_0, 50), z_50_0);
    return fe_mul(fe_pow2k(z_250_0, 2), a); // 2^252 - 3
}

// ---------------------------------------------------------------- ge

struct ge {              // extended twisted Edwards (a = -1)
    fe X, Y, Z, T;
};

// d and sqrt(-1) constants (little-endian canonical byte form)
static const uint8_t D_BYTES[32] = {
    0xa3, 0x78, 0x59, 0x13, 0xca, 0x4d, 0xeb, 0x75,
    0xab, 0xd8, 0x41, 0x41, 0x4d, 0x0a, 0x70, 0x00,
    0x98, 0xe8, 0x79, 0x77, 0x79, 0x40, 0xc7, 0x8c,
    0x73, 0xfe, 0x6f, 0x2b, 0xee, 0x6c, 0x03, 0x52};
static const uint8_t SQRTM1_BYTES[32] = {
    0xb0, 0xa0, 0x0e, 0x4a, 0x27, 0x1b, 0xee, 0xc4,
    0x78, 0xe4, 0x2f, 0xad, 0x06, 0x18, 0x43, 0x2f,
    0xa7, 0xd7, 0xfb, 0x3d, 0x99, 0x00, 0x4d, 0x2b,
    0x0b, 0xdf, 0xc1, 0x4f, 0x80, 0x24, 0x83, 0x2b};
// compressed basepoint: y = 4/5, sign 0
static const uint8_t B_BYTES[32] = {
    0x58, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66,
    0x66, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66,
    0x66, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66,
    0x66, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66, 0x66};

inline ge ge_identity() {
    return ge{fe_zero(), fe_one(), fe_one(), fe_zero()};
}

inline const fe& fe_d2() {
    static const fe d2 = fe_add(fe_frombytes(D_BYTES),
                                fe_frombytes(D_BYTES));
    return d2;
}

// unified extended addition (complete for a = -1, d non-square)
inline ge ge_add(const ge& p, const ge& q) {
    const fe& d2 = fe_d2();
    fe a = fe_mul(fe_sub(p.Y, p.X), fe_sub(q.Y, q.X));
    fe b = fe_mul(fe_add(p.Y, p.X), fe_add(q.Y, q.X));
    fe c = fe_mul(fe_mul(p.T, q.T), d2);
    fe dd = fe_add(fe_mul(p.Z, q.Z), fe_mul(p.Z, q.Z));
    fe e = fe_sub(b, a);
    fe f = fe_sub(dd, c);
    fe g = fe_add(dd, c);
    fe h = fe_add(b, a);
    return ge{fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h)};
}

inline ge ge_double(const ge& p) {
    fe a = fe_sq(p.X);
    fe b = fe_sq(p.Y);
    fe zz = fe_sq(p.Z);
    fe c = fe_add(zz, zz);
    fe e = fe_sub(fe_sub(fe_sq(fe_add(p.X, p.Y)), a), b);
    fe g = fe_sub(b, a);
    fe f = fe_sub(g, c);
    fe h = fe_neg(fe_add(a, b));
    return ge{fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h)};
}

// ZIP-215 permissive decompression: accepts y >= p and x = 0 with
// sign = 1; rejects only encodings with no curve point.
inline bool ge_decompress(const uint8_t s[32], ge* out) {
    uint8_t yb[32];
    std::memcpy(yb, s, 32);
    int sign = yb[31] >> 7;
    yb[31] &= 0x7F;
    fe y = fe_frombytes(yb);
    fe yy = fe_sq(y);
    fe u = fe_sub(yy, fe_one());
    fe v = fe_add(fe_mul(yy, fe_frombytes(D_BYTES)), fe_one());
    // x = u v^3 (u v^7)^((p-5)/8)
    fe v3 = fe_mul(fe_sq(v), v);
    fe v7 = fe_mul(fe_sq(v3), v);
    fe x = fe_mul(fe_mul(u, v3), fe_pow22523(fe_mul(u, v7)));
    fe vxx = fe_mul(v, fe_sq(x));
    // comparison operand: u is a lazy sub result whose limbs
    // (~2^54 + 2^51) exceed BOTH fe_neg's and fe_eq's fe_sub
    // subtrahend bound (2^54 - 152) — one carry sweep brings the
    // limbs under 2^52, inside the proven precondition, so neither
    // the u == vxx test nor the vxx + u == 0 test relies on uint64
    // wrap cancellation.  (u itself stays lazy for the fe_mul calls
    // above: fe_mul's documented input bound is ~2^55.)
    fe un = u;
    fe_carry(un);
    if (!fe_eq(vxx, un)) {
        if (fe_is_zero(fe_add(vxx, un))) {
            x = fe_mul(x, fe_frombytes(SQRTM1_BYTES));
        } else {
            return false;
        }
    }
    if ((int)fe_parity(x) != sign) x = fe_neg(x);
    out->X = x;
    out->Y = y;
    out->Z = fe_one();
    out->T = fe_mul(x, y);
    return true;
}

// [8]p == identity?  (three doublings, then X == 0 && Y == Z)
inline bool ge_is_identity_cofactored(ge p) {
    p = ge_double(ge_double(ge_double(p)));
    return fe_is_zero(p.X) && fe_eq(p.Y, p.Z);
}

// ------------------------------------------------------------ scalars

// 256x256 -> 512-bit product, 64-bit limbs little-endian (unreduced:
// a caller summing many products reduces once, with reduce_mod_l)
inline void sc_mul_wide(const uint8_t a[32], const uint8_t b[32],
                        uint64_t prod[8]) {
    uint64_t al[4], bl[4];
    std::memcpy(al, a, 32);
    std::memcpy(bl, b, 32);
    std::memset(prod, 0, 64);
    for (int i = 0; i < 4; i++) {
        u128 carry = 0;
        for (int j = 0; j < 4; j++) {
            u128 t = (u128)al[i] * bl[j] + prod[i + j] + carry;
            prod[i + j] = (uint64_t)t;
            carry = t >> 64;
        }
        prod[i + 4] = (uint64_t)carry;
    }
}

// the product mod L via the existing sha512::reduce_mod_l 512-bit
// reducer
inline void sc_mul(const uint8_t a[32], const uint8_t b[32],
                   uint8_t out[32]) {
    uint64_t prod[8];
    sc_mul_wide(a, b, prod);
    uint8_t wide[64];
    std::memcpy(wide, prod, 64);
    sha512::reduce_mod_l(wide, out);
}

// L little-endian
static const uint8_t L_BYTES[32] = {
    0xed, 0xd3, 0xf5, 0x5c, 0x1a, 0x63, 0x12, 0x58,
    0xd6, 0x9c, 0xf7, 0xa2, 0xde, 0xf9, 0xde, 0x14,
    0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00,
    0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x10};

// out = (a + b) mod L  (a, b < L)
inline void sc_add(const uint8_t a[32], const uint8_t b[32],
                   uint8_t out[32]) {
    uint64_t al[4], bl[4], ll[4], r[4];
    std::memcpy(al, a, 32);
    std::memcpy(bl, b, 32);
    std::memcpy(ll, L_BYTES, 32);
    unsigned char carry = 0;
    for (int i = 0; i < 4; i++) {
        u128 t = (u128)al[i] + bl[i] + carry;
        r[i] = (uint64_t)t;
        carry = (unsigned char)(t >> 64);
    }
    // subtract L if >= L
    bool ge = carry != 0;
    if (!ge) {
        ge = true;
        for (int i = 3; i >= 0; i--) {
            if (r[i] > ll[i]) { ge = true; break; }
            if (r[i] < ll[i]) { ge = false; break; }
        }
    }
    if (ge) {
        unsigned char borrow = 0;
        for (int i = 0; i < 4; i++) {
            u128 t = (u128)r[i] - ll[i] - borrow;
            r[i] = (uint64_t)t;
            borrow = (unsigned char)((t >> 64) & 1);
        }
    }
    std::memcpy(out, r, 32);
}

// out = (L - a) mod L   (a < L)
inline void sc_neg(const uint8_t a[32], uint8_t out[32]) {
    bool zero = true;
    for (int i = 0; i < 32; i++)
        if (a[i]) { zero = false; break; }
    if (zero) {
        std::memset(out, 0, 32);
        return;
    }
    uint64_t al[4], ll[4], r[4];
    std::memcpy(al, a, 32);
    std::memcpy(ll, L_BYTES, 32);
    unsigned char borrow = 0;
    for (int i = 0; i < 4; i++) {
        u128 t = (u128)ll[i] - al[i] - borrow;
        r[i] = (uint64_t)t;
        borrow = (unsigned char)((t >> 64) & 1);
    }
    std::memcpy(out, r, 32);
}

// s < L check (canonical S, ZIP-215 requirement)
inline bool sc_is_canonical(const uint8_t s[32]) {
    for (int i = 31; i >= 0; i--) {
        if (s[i] < L_BYTES[i]) return true;
        if (s[i] > L_BYTES[i]) return false;
    }
    return false;   // s == L
}

// ------------------------------------------------------------ msm

// c-bit digit w of a 256-bit little-endian scalar
inline uint32_t sc_digit(const uint8_t s[32], int c, int w) {
    int bit = c * w;
    int byte = bit >> 3, off = bit & 7;
    uint64_t chunk = 0;
    int avail = 32 - byte;
    std::memcpy(&chunk, s + byte, avail >= 8 ? 8 : avail);
    return (uint32_t)((chunk >> off) & ((uint64_t(1) << c) - 1));
}

// Pippenger bucket MSM over (points, 256-bit scalars); window width
// adapts to n so small batches skip the bucket-sweep fixed cost.
inline ge msm(const ge* pts, const uint8_t (*scalars)[32], size_t n) {
    int c = n < 8 ? 4 : n < 64 ? 6 : n < 512 ? 8 : n < 4096 ? 10 : 12;
    int windows = (256 + c - 1) / c;
    size_t nbuckets = size_t(1) << c;
    std::vector<ge> bucket(nbuckets);
    std::vector<uint8_t> used(nbuckets);
    ge acc = ge_identity();
    for (int w = windows - 1; w >= 0; w--) {
        if (w != windows - 1)
            for (int k = 0; k < c; k++) acc = ge_double(acc);
        std::memset(used.data(), 0, nbuckets);
        for (size_t i = 0; i < n; i++) {
            uint32_t d = sc_digit(scalars[i], c, w);
            if (!d) continue;
            if (used[d]) {
                bucket[d] = ge_add(bucket[d], pts[i]);
            } else {
                bucket[d] = pts[i];
                used[d] = 1;
            }
        }
        // bucket sweep: sum_b b * bucket[b] via the running-sum trick;
        // the adds before the first occupied bucket are skipped (the
        // running sum is still the identity there)
        ge running = ge_identity();
        ge sum = ge_identity();
        bool run_any = false, sum_any = false;
        for (size_t b = nbuckets - 1; b >= 1; b--) {
            if (used[b]) {
                running = run_any ? ge_add(running, bucket[b])
                                  : bucket[b];
                run_any = true;
            }
            if (run_any) {
                sum = sum_any ? ge_add(sum, running) : running;
                sum_any = true;
            }
        }
        if (sum_any) acc = ge_add(acc, sum);
    }
    return acc;
}

// ------------------------------------------------------- batch verify

struct BatchItem {
    const uint8_t* pub;      // 32
    const uint8_t* msg;
    size_t msglen;
    const uint8_t* sig;      // 64
};

// ------------------------------------------------- pubkey decompress cache
//
// Validators repeat across blocks, so the A-point decompression (the
// sqrt chain, ~265 field muls) is the same work every height — the
// reference keeps an LRU of expanded pubkeys for exactly this reason
// (crypto/ed25519/ed25519.go:62-68, size 4096).  Here: a sharded
// direct-mapped cache of decompressed A points (32768 slots, ~6 MB —
// sized so the north-star 10k-validator set fits with headroom);
// R points are per-signature nonces and never repeat.  Purely a
// speed memo: entries are only ever (pub -> its unique decompressed
// point), so a stale or evicted entry just costs a recompute.

struct PubCacheSlot {
    bool used = false;
    uint8_t pub[32];
    fe x, y;            // affine (Z = 1; T = x*y rebuilt on get —
                        // one mul instead of 80 more bytes per slot,
                        // so a hit touches 2 cachelines, not 4)
};

struct PubCache {
    // 32k slots (~4 MB): covers the north-star 10k-validator set with
    // headroom, so steady-state heights re-verify every validator
    // from the cache; typical sets (hundreds) always fit
    static const size_t SLOTS = 32768;
    static const size_t SHARDS = 16;
    std::vector<PubCacheSlot> slots;
    std::mutex mu[SHARDS];

    PubCache() : slots(SLOTS) {}

    static size_t slot_of(const uint8_t pub[32]) {
        uint64_t h;
        std::memcpy(&h, pub, 8);
        h *= 0x9E3779B97F4A7C15ull;
        return size_t(h >> 49) & (SLOTS - 1);   // 15 bits
    }

    bool get(const uint8_t pub[32], ge* out) {
        size_t s = slot_of(pub);
        std::lock_guard<std::mutex> g(mu[s % SHARDS]);
        PubCacheSlot& sl = slots[s];
        if (!sl.used || std::memcmp(sl.pub, pub, 32) != 0)
            return false;
        out->X = sl.x;
        out->Y = sl.y;
        out->Z = fe_one();
        out->T = fe_mul(sl.x, sl.y);
        return true;
    }

    void put(const uint8_t pub[32], const ge& pt) {
        // decompressed points are affine (Z = 1) by construction
        size_t s = slot_of(pub);
        std::lock_guard<std::mutex> g(mu[s % SHARDS]);
        PubCacheSlot& sl = slots[s];
        std::memcpy(sl.pub, pub, 32);
        sl.x = pt.X;
        sl.y = pt.Y;
        sl.used = true;
    }
};

inline PubCache& pub_cache() {
    static PubCache c;
    return c;
}

inline bool decompress_pub_cached(const uint8_t pub[32], ge* out) {
    PubCache& c = pub_cache();
    if (c.get(pub, out)) return true;
    if (!ge_decompress(pub, out)) return false;
    c.put(pub, *out);
    return true;
}

// thread-count default shared with the binding: hardware concurrency
// clamped to 8 (the same clamp the prep pipeline uses)
inline int default_threads() {
    unsigned hw = std::thread::hardware_concurrency();
    return hw > 8 ? 8 : (hw ? int(hw) : 1);
}

// Fan a [0, n) range out over up to nt threads (>= min_per items
// each).  Worker exceptions are caught and reported via the return
// value (false = some worker failed); a failed thread SPAWN runs that
// chunk inline instead.  fn(tid, lo, hi) must only write state that
// is disjoint per index range (or per tid).
template <typename F>
inline bool fan_out(size_t n, size_t min_per, int nt, const F& fn) {
    if (nt > 1 && n / size_t(nt) < min_per)
        nt = int(n / min_per ? n / min_per : 1);
    if (nt > 16) nt = 16;
    if (nt <= 1) {
        fn(0, size_t(0), n);
        return true;
    }
    std::atomic<bool> failed(false);
    auto body = [&](int tid, size_t lo, size_t hi) {
        try {
            fn(tid, lo, hi);
        } catch (...) {
            failed.store(true);
        }
    };
    std::vector<std::thread> ts;
    size_t chunk = (n + size_t(nt) - 1) / size_t(nt);
    for (int t = 0; t < nt; t++) {
        size_t lo = size_t(t) * chunk;
        size_t hi = lo + chunk < n ? lo + chunk : n;
        if (lo >= hi) break;
        try {
            ts.emplace_back(body, t, lo, hi);
        } catch (...) {
            body(t, lo, hi);    // spawn failed: run inline
        }
    }
    for (auto& th : ts) th.join();
    return !failed.load();
}

// 1 = batch equation holds (all signatures valid with overwhelming
// probability); 0 = reject or malformed input (caller falls back
// per-signature).  z: 16 bytes per item (random; bit 0 forced odd).
// nthreads <= 1 runs serial; otherwise the per-item preparation
// (decompress + SHA-512 + scalar muls) and the MSM both fan out over
// range chunks, each MSM thread computing a partial result that is
// combined with plain group additions — the GIL is already released
// by the binding, so worker threads scale on multi-core hosts.
// Never throws: any internal failure (allocation, worker exception)
// retries serially, and a top-level failure rejects the batch, which
// just routes the caller to the per-signature path.
inline int batch_verify_inner(const std::vector<BatchItem>& items,
                              const uint8_t* z, int nthreads) {
    size_t n = items.size();
    if (n == 0) return 1;
    size_t total = 2 * n + 1;
    std::vector<ge> pts(total);
    std::vector<uint8_t> scal(total * 32);      // 32 bytes per point
    std::vector<std::array<uint8_t, 32>> zs(n); // z_i * s_i
    std::vector<uint8_t> bad(n, 0);

    auto prepare = [&](int, size_t lo, size_t hi) {
        uint8_t digest[64], k[32], zk[32], si[32];
        for (size_t i = lo; i < hi; i++) {
            const BatchItem& it = items[i];
            ge A, R;
            if (!sc_is_canonical(it.sig + 32) ||
                !decompress_pub_cached(it.pub, &A) ||
                !ge_decompress(it.sig, &R)) {
                bad[i] = 1;
                continue;
            }
            uint8_t zi[32] = {0};
            std::memcpy(zi, z + 16 * i, 16);
            zi[0] |= 1;
            // k_i = SHA-512(R || A || msg) mod L
            sha512::Ctx c;
            sha512::init(&c);
            sha512::update(&c, it.sig, 32);
            sha512::update(&c, it.pub, 32);
            sha512::update(&c, it.msg, it.msglen);
            sha512::final(&c, digest);
            sha512::reduce_mod_l(digest, k);
            std::memcpy(si, it.sig + 32, 32);
            sc_mul(zi, si, zs[i].data());
            sc_mul(zi, k, zk);
            pts[2 * i] = R;
            std::memcpy(&scal[(2 * i) * 32], zi, 32);
            pts[2 * i + 1] = A;
            std::memcpy(&scal[(2 * i + 1) * 32], zk, 32);
        }
    };
    if (!fan_out(n, 32, nthreads, prepare)) {
        if (nthreads > 1)
            return batch_verify_inner(items, z, 1);
        return 0;
    }
    for (size_t i = 0; i < n; i++)
        if (bad[i]) return 0;

    uint8_t s_sum[32] = {0};
    for (size_t i = 0; i < n; i++)
        sc_add(s_sum, zs[i].data(), s_sum);
    ge Bp;
    ge_decompress(B_BYTES, &Bp);
    uint8_t neg_s[32];
    sc_neg(s_sum, neg_s);
    pts[2 * n] = Bp;
    std::memcpy(&scal[(2 * n) * 32], neg_s, 32);

    auto scal_at = [&](size_t i) {
        return reinterpret_cast<const uint8_t(*)[32]>(&scal[i * 32]);
    };
    int nt = nthreads;
    if (nt > 1 && total / size_t(nt) < 128) nt = 1;
    if (nt <= 1)
        return ge_is_identity_cofactored(
                   msm(pts.data(), scal_at(0), total))
                   ? 1
                   : 0;
    size_t npart = size_t(nt);
    std::vector<ge> part(npart, ge_identity());
    bool ok = fan_out(total, 128, nt,
                      [&](int tid, size_t lo, size_t hi) {
        part[size_t(tid)] = msm(pts.data() + lo, scal_at(lo), hi - lo);
    });
    if (!ok)
        return batch_verify_inner(items, z, 1);
    ge r = part[0];
    for (size_t t = 1; t < npart; t++) r = ge_add(r, part[t]);
    return ge_is_identity_cofactored(r) ? 1 : 0;
}

inline int batch_verify(const std::vector<BatchItem>& items,
                        const uint8_t* z, int nthreads = 1) {
    try {
        return batch_verify_inner(items, z, nthreads);
    } catch (...) {
        return 0;       // reject -> caller's per-signature fallback
    }
}

// ===================================================================
// Tile kernel (KERNEL_NOTES round 6): the per-tile entry behind the
// overlapped verification pipeline (crypto/pipeline.py).  The legacy
// batch_verify above is preserved byte-for-byte as the monolithic
// comparison arm (perf_lab ed25519_pipelined_dispatch) and the
// fallback for modules built before the tile entries existed; the
// kernel-geometry improvements below are tile-path only until the
// round-7 unification pass:
//
//   * dedicated squaring (fe_sqr: 15 wide products vs fe_mul's 25)
//     through the decompression sqrt chain — the chain is ~95%
//     squarings, and R-point decompression is ~1/3 of the e2e path;
//   * signed-digit Pippenger windows (digits in (-2^(c-1), 2^(c-1)]):
//     half the buckets, so the per-window sweep — the cost tiling
//     MULTIPLIES, one sweep per tile instead of one per batch — is
//     halved, which is what makes a tiled pass cheaper than the
//     monolithic MSM instead of ~10% dearer;
//   * mixed addition for bucket accumulation (decompressed inputs are
//     affine, Z = 1: one field mul saved per point add);
//   * a packed-blob calling convention (pubs/msgs/lens/sigs as four
//     contiguous buffers) so a 10k-sig burst does not pay 30k
//     PyObject extractions per dispatch.

inline fe fe_sqr(const fe& a) {
    uint64_t a0 = a.v[0], a1 = a.v[1], a2 = a.v[2], a3 = a.v[3],
             a4 = a.v[4];
    uint64_t a1_2 = a1 * 2, a3_19 = a3 * 19, a4_19 = a4 * 19;
    u128 t0 = (u128)a0 * a0 + (u128)a1_2 * a4_19 +
              (u128)(a2 * 2) * a3_19;
    u128 t1 = (u128)a0 * (a1 * 2) + (u128)(a2 * 2) * a4_19 +
              (u128)a3 * a3_19;
    u128 t2 = (u128)a0 * (a2 * 2) + (u128)a1 * a1 +
              (u128)(a3 * 2) * a4_19;
    u128 t3 = (u128)a0 * (a3 * 2) + (u128)a1_2 * a2 +
              (u128)a4 * a4_19;
    u128 t4 = (u128)a0 * (a4 * 2) + (u128)a1_2 * a3 +
              (u128)a2 * a2;
    fe r;
    u128 c;
    r.v[0] = (uint64_t)t0 & MASK51; c = t0 >> 51;
    t1 += c;
    r.v[1] = (uint64_t)t1 & MASK51; c = t1 >> 51;
    t2 += c;
    r.v[2] = (uint64_t)t2 & MASK51; c = t2 >> 51;
    t3 += c;
    r.v[3] = (uint64_t)t3 & MASK51; c = t3 >> 51;
    t4 += c;
    r.v[4] = (uint64_t)t4 & MASK51; c = t4 >> 51;
    u128 f = c * 19 + r.v[0];
    r.v[0] = (uint64_t)f & MASK51;
    r.v[1] += (uint64_t)(f >> 51);
    return r;
}

inline fe fe_pow2k_sqr(fe a, int k) {
    while (k--) a = fe_sqr(a);
    return a;
}

inline fe fe_pow22523_sqr(const fe& a) {
    fe x2 = fe_sqr(a);
    fe x4 = fe_sqr(x2);
    fe x8 = fe_sqr(x4);
    fe z9 = fe_mul(a, x8);
    fe z11 = fe_mul(x2, z9);
    fe z22 = fe_sqr(z11);
    fe z_5_0 = fe_mul(z9, z22);
    fe z_10_0 = fe_mul(fe_pow2k_sqr(z_5_0, 5), z_5_0);
    fe z_20_0 = fe_mul(fe_pow2k_sqr(z_10_0, 10), z_10_0);
    fe z_40_0 = fe_mul(fe_pow2k_sqr(z_20_0, 20), z_20_0);
    fe z_50_0 = fe_mul(fe_pow2k_sqr(z_40_0, 10), z_10_0);
    fe z_100_0 = fe_mul(fe_pow2k_sqr(z_50_0, 50), z_50_0);
    fe z_200_0 = fe_mul(fe_pow2k_sqr(z_100_0, 100), z_100_0);
    fe z_250_0 = fe_mul(fe_pow2k_sqr(z_200_0, 50), z_50_0);
    return fe_mul(fe_pow2k_sqr(z_250_0, 2), a);
}

// ZIP-215 permissive decompression through the fe_sqr chain —
// identical acceptance set to ge_decompress (differentially tested
// in tests/test_verify_pipeline.py), ~17% faster.
inline bool ge_decompress_fast(const uint8_t s[32], ge* out) {
    uint8_t yb[32];
    std::memcpy(yb, s, 32);
    int sign = yb[31] >> 7;
    yb[31] &= 0x7F;
    fe y = fe_frombytes(yb);
    fe yy = fe_sqr(y);
    fe u = fe_sub(yy, fe_one());
    fe v = fe_add(fe_mul(yy, fe_frombytes(D_BYTES)), fe_one());
    fe v3 = fe_mul(fe_sqr(v), v);
    fe v7 = fe_mul(fe_sqr(v3), v);
    fe x = fe_mul(fe_mul(u, v3), fe_pow22523_sqr(fe_mul(u, v7)));
    fe vxx = fe_mul(v, fe_sqr(x));
    fe un = u;                  // same carry rationale as ge_decompress
    fe_carry(un);
    if (!fe_eq(vxx, un)) {
        if (fe_is_zero(fe_add(vxx, un))) {
            x = fe_mul(x, fe_frombytes(SQRTM1_BYTES));
        } else {
            return false;
        }
    }
    if ((int)fe_parity(x) != sign) x = fe_neg(x);
    out->X = x;
    out->Y = y;
    out->Z = fe_one();
    out->T = fe_mul(x, y);
    return true;
}

inline bool decompress_pub_cached_fast(const uint8_t pub[32],
                                       ge* out) {
    PubCache& c = pub_cache();
    if (c.get(pub, out)) return true;
    if (!ge_decompress_fast(pub, out)) return false;
    c.put(pub, *out);
    return true;
}

// Staged A-point record: affine x || y as raw limb structs (80
// bytes, process-internal representation — the blob never leaves the
// process) + 1 validity byte.  Invalid encodings mark 0 and the
// verify pass rejects them itself.
static const size_t STAGED_REC = 2 * sizeof(fe) + 1;

// Resolve a blob of pubkeys to decompressed A points — the
// pipeline's staging phase runs this for tile i+1 while tile i's MSM
// executes on the kernel worker.  Each key is resolved exactly once
// per tile (cache hit, or decompress + cache fill) and the points
// travel to the verify pass in the staged blob, so a direct-mapped
// collision never costs a second decompression in the kernel.
inline void stage_pubs(const uint8_t* pubs, size_t n, uint8_t* out) {
    PubCache& c = pub_cache();
    ge pt;
    for (size_t i = 0; i < n; i++) {
        const uint8_t* pub = pubs + i * 32;
        uint8_t* rec = out + i * STAGED_REC;
        bool ok = c.get(pub, &pt);
        if (!ok) {
            ok = ge_decompress_fast(pub, &pt);
            if (ok) c.put(pub, pt);
        }
        if (ok) {
            std::memcpy(rec, &pt.X, sizeof(fe));
            std::memcpy(rec + sizeof(fe), &pt.Y, sizeof(fe));
            rec[2 * sizeof(fe)] = 1;
        } else {
            rec[2 * sizeof(fe)] = 0;
        }
    }
}

// cached ("niels") form of an affine point: (Y-X, Y+X, 2d*T).  The
// mixed addition below consumes it with 7 field muls — one fewer
// than the unified extended add (the 2d*T product is precomputed
// once per point instead of once per bucket add), and negation is an
// index swap plus one cheap limb negation.
struct nge {
    fe ymx, ypx, t2d;
};

inline nge ge_to_niels(const ge& p) {        // p affine (Z = 1)
    return nge{fe_sub(p.Y, p.X), fe_add(p.Y, p.X),
               fe_mul(p.T, fe_d2())};
}

// unified mixed addition p + q with q in cached affine form; sign<0
// adds -q (swap the Y±X products, negate 2dT).  Complete for a = -1.
inline ge ge_madd(const ge& p, const nge& q, int sign) {
    fe a, b, c;
    if (sign > 0) {
        a = fe_mul(fe_sub(p.Y, p.X), q.ymx);
        b = fe_mul(fe_add(p.Y, p.X), q.ypx);
        c = fe_mul(p.T, q.t2d);
    } else {
        a = fe_mul(fe_sub(p.Y, p.X), q.ypx);
        b = fe_mul(fe_add(p.Y, p.X), q.ymx);
        c = fe_neg(fe_mul(p.T, q.t2d));
    }
    fe dd = fe_add(p.Z, p.Z);
    fe e = fe_sub(b, a);
    fe f = fe_sub(dd, c);
    fe g = fe_add(dd, c);
    fe h = fe_add(b, a);
    return ge{fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h)};
}

inline ge ge_neg_affine(const ge& p) {
    return ge{fe_neg(p.X), p.Y, p.Z, fe_neg(p.T)};
}

// signed-digit window width for an npts-point tile MSM (measured on
// the 1-vCPU rig: c=11 wins for full tiles >= ~4k points, c=10 for
// balanced ~3.3k-signature tiles and the partial tail; the ladder
// tracks the legacy msm() shape at small n where signed/unsigned
// behave alike)
inline int tile_window_c(size_t npts) {
    return npts < 8 ? 4 : npts < 64 ? 6 : npts < 512 ? 8
         : npts < 2048 ? 9 : npts < 8192 ? 10 : npts < 24576 ? 11
         : 12;
}

// Pippenger MSM with signed c-bit digits over AFFINE points
// (Z = 1 — decompressed inputs).  Digits lie in
// (-2^(c-1), 2^(c-1)]: half the buckets of the unsigned form, so the
// per-window bucket sweep — the fixed cost a tiled pass pays once
// per tile — is halved; bucket accumulation runs on the cached
// (niels) form at 7 muls per add.  Scalars must be < 2^253
// (everything mod L is), which keeps the top window's carry in
// range.
inline ge msm_signed(const ge* pts, const uint8_t (*scalars)[32],
                     size_t n, int c) {
    int windows = (256 + c - 1) / c;
    size_t nbuckets = size_t(1) << (c - 1);
    std::vector<ge> bucket(nbuckets);
    std::vector<uint8_t> used(nbuckets);
    std::vector<int16_t> dig(n * size_t(windows));
    std::vector<nge> npts(n);
    for (size_t i = 0; i < n; i++) {
        npts[i] = ge_to_niels(pts[i]);
        int carry = 0;
        for (int w = 0; w < windows; w++) {
            int v = int(sc_digit(scalars[i], c, w)) + carry;
            if (v > (1 << (c - 1))) {
                v -= (1 << c);
                carry = 1;
            } else {
                carry = 0;
            }
            dig[i * size_t(windows) + w] = int16_t(v);
        }
    }
    ge acc = ge_identity();
    for (int w = windows - 1; w >= 0; w--) {
        if (w != windows - 1)
            for (int k = 0; k < c; k++) acc = ge_double(acc);
        std::memset(used.data(), 0, nbuckets);
        for (size_t i = 0; i < n; i++) {
            int d = dig[i * size_t(windows) + w];
            if (!d) continue;
            size_t b = size_t(d > 0 ? d : -d) - 1;
            if (used[b]) {
                bucket[b] = ge_madd(bucket[b], npts[i], d);
            } else {
                bucket[b] = d > 0 ? pts[i] : ge_neg_affine(pts[i]);
                used[b] = 1;
            }
        }
        ge running = ge_identity();
        ge sum = ge_identity();
        bool run_any = false, sum_any = false;
        for (size_t b = nbuckets; b >= 1; b--) {
            if (used[b - 1]) {
                running = run_any ? ge_add(running, bucket[b - 1])
                                  : bucket[b - 1];
                run_any = true;
            }
            if (run_any) {
                sum = sum_any ? ge_add(sum, running) : running;
                sum_any = true;
            }
        }
        if (sum_any) acc = ge_add(acc, sum);
    }
    return acc;
}

struct TileView {            // one signature in the packed-blob layout
    const uint8_t* pub;      // 32
    const uint8_t* msg;
    size_t msglen;
    const uint8_t* sig;      // 64
};

// k_i = SHA-512(R || A || msg) mod L for every item, through the
// 8-way multi-buffer hasher where the CPU has it (vote sign-bytes in
// a tile are uniform-length, so grouping stays trivial); scalar
// SHA-512 otherwise.
inline void tile_k_scalars(const std::vector<TileView>& items,
                           uint8_t (*ks)[32]) {
    size_t n = items.size();
    size_t i = 0;
#if COMETBFT_SHA512MB_X86
    if (sha512mb::available()) {
        std::vector<uint8_t> scratch;
        uint8_t digests[8][64];
        while (i + 8 <= n) {
            size_t nb = sha512mb::block_count(64 + items[i].msglen);
            bool uniform = nb <= 128;
            for (size_t l = 1; uniform && l < 8; l++)
                uniform = sha512mb::block_count(
                    64 + items[i + l].msglen) == nb;
            if (!uniform) break;    // ragged tail: scalar below
            size_t slot = nb * 128;
            scratch.assign(slot * 8, 0);
            const uint8_t* base[8];
            for (size_t l = 0; l < 8; l++) {
                uint8_t* buf = scratch.data() + l * slot;
                const TileView& it = items[i + l];
                std::memcpy(buf, it.sig, 32);
                std::memcpy(buf + 32, it.pub, 32);
                std::memcpy(buf + 64, it.msg, it.msglen);
                sha512mb::write_padding(buf, 64 + it.msglen, nb);
                base[l] = buf;
            }
            sha512mb::hash8(base, nb, digests);
            for (size_t l = 0; l < 8; l++)
                sha512::reduce_mod_l(digests[l], ks[i + l]);
            i += 8;
        }
    }
#endif
    uint8_t digest[64];
    for (; i < n; i++) {
        const TileView& it = items[i];
        sha512::Ctx c;
        sha512::init(&c);
        sha512::update(&c, it.sig, 32);
        sha512::update(&c, it.pub, 32);
        sha512::update(&c, it.msg, it.msglen);
        sha512::final(&c, digest);
        sha512::reduce_mod_l(digest, ks[i]);
    }
}

// One pipeline tile: same RLC batch equation and ZIP-215 semantics as
// batch_verify_inner, through the tile-kernel geometry (cached
// fe_sqr decompression, signed-digit MSM, cached-form bucket adds).
// 1 = the tile's batch equation holds; 0 = reject or malformed input
// (the caller bisects WITHIN the tile).  Single-threaded by design:
// tile-level concurrency belongs to the pipeline's worker threads,
// not nested fan-out.
inline int batch_verify_tile_inner(const std::vector<TileView>& items,
                                   const uint8_t* z,
                                   const uint8_t* staged) {
    size_t n = items.size();
    if (n == 0) return 1;
    size_t total = 2 * n + 1;
    std::vector<ge> pts(total);
    std::vector<uint8_t> scal(total * 32);
    std::vector<std::array<uint8_t, 32>> ks(n);
    tile_k_scalars(items,
                   reinterpret_cast<uint8_t(*)[32]>(ks[0].data()));
    uint8_t s_sum[32] = {0};
    uint8_t zk[32], si[32], zs[32];
    for (size_t i = 0; i < n; i++) {
        const TileView& it = items[i];
        ge A, R;
        bool a_ok;
        if (staged != nullptr) {
            // staging resolved this A point already (valid byte 0 =
            // undecompressable pubkey)
            const uint8_t* rec = staged + i * STAGED_REC;
            a_ok = rec[2 * sizeof(fe)] != 0;
            if (a_ok) {
                std::memcpy(&A.X, rec, sizeof(fe));
                std::memcpy(&A.Y, rec + sizeof(fe), sizeof(fe));
                A.Z = fe_one();
                A.T = fe_mul(A.X, A.Y);
            }
        } else {
            a_ok = decompress_pub_cached_fast(it.pub, &A);
        }
        if (!sc_is_canonical(it.sig + 32) || !a_ok ||
            !ge_decompress_fast(it.sig, &R))
            return 0;
        uint8_t zi[32] = {0};
        std::memcpy(zi, z + 16 * i, 16);
        zi[0] |= 1;
        std::memcpy(si, it.sig + 32, 32);
        sc_mul(zi, si, zs);
        sc_add(s_sum, zs, s_sum);
        sc_mul(zi, ks[i].data(), zk);
        pts[2 * i] = R;
        std::memcpy(&scal[(2 * i) * 32], zi, 32);
        pts[2 * i + 1] = A;
        std::memcpy(&scal[(2 * i + 1) * 32], zk, 32);
    }
    ge Bp;
    ge_decompress_fast(B_BYTES, &Bp);
    uint8_t neg_s[32];
    sc_neg(s_sum, neg_s);
    pts[2 * n] = Bp;
    std::memcpy(&scal[(2 * n) * 32], neg_s, 32);
    const uint8_t(*sc)[32] =
        reinterpret_cast<const uint8_t(*)[32]>(scal.data());
    ge r = msm_signed(pts.data(), sc, total, tile_window_c(total));
    return ge_is_identity_cofactored(r) ? 1 : 0;
}

inline int batch_verify_tile(const std::vector<TileView>& items,
                             const uint8_t* z,
                             const uint8_t* staged = nullptr) {
    try {
        return batch_verify_tile_inner(items, z, staged);
    } catch (...) {
        return 0;       // reject -> caller's per-signature fallback
    }
}

}  // namespace ed25519_msm
