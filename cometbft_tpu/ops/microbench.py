"""Per-primitive TPU microbenchmarks — the kernel floor analysis.

Op-shaving on the 24-limb kernel is nearly exhausted; what's missing
is a HARDWARE-CALIBRATED floor — measured per-primitive throughput
that either names the structural win or proves the single-chip target
unreachable.  Each benchmark here is a tiny Pallas kernel that runs K
repetitions of ONE primitive from the production kernel
(ed25519_pallas.py — same code objects, not copies) over the same
[24, B] limb-major slabs, so a chip run yields the real cost of: a
carry pass, a field multiply, a doubling, the two addition forms, the
window-table select, and one full ladder window.

``run_suite`` compiles each kernel with the installed JAX (the
persistent compile cache, placed by ops/device.py, carries them from
one process to the next), times it on the TPU and returns one record
per primitive; it writes nothing.

Values flowing through the primitives are arbitrary bounded limb
vectors, not curve points — primitive cost is data-independent (no
data-dependent control flow exists under jit), and the chained carry
discipline keeps magnitudes inside the proven int32 bounds either way.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import device
from . import ed25519_pallas as ep

LIMBS = ep.LIMBS
BLOCK = ep.BLOCK
M_DEFAULT = 16384

# repetitions per primitive, sized so each run lands ~20-40 ms at
# m=16384 given the r4 measured kernel time (116 ms / ~3770 muls)
REPS = {
    "noop": 1,
    "carry": 4096,
    "mul": 1024,
    "sqr": 1024,
    "double": 128,
    "add": 128,
    "madd": 128,
    "select16": 512,
    "window": 16,
}


def _where_tree(w, rows):
    """16-entry select as a 4-level binary where-tree (the production
    kernel's select form — ed25519_pallas._kernel)."""
    bit = 1
    while len(rows) > 1:
        cond = (w & bit) != 0
        rows = [jnp.where(cond, rows[i + 1], rows[i])
                for i in range(0, len(rows), 2)]
        bit <<= 1
    return rows[0]


def _unpack_consts(consts_ref):
    d_col = consts_ref[0:LIMBS]
    two_d = consts_ref[LIMBS:2 * LIMBS]
    sqrt_m1 = consts_ref[2 * LIMBS:3 * LIMBS]
    four_p = consts_ref[3 * LIMBS:4 * LIMBS]
    pats = (consts_ref[4 * LIMBS:5 * LIMBS],
            consts_ref[5 * LIMBS:6 * LIMBS])
    b_tab = consts_ref[6 * LIMBS:].reshape(16, 3, LIMBS, 1)
    return d_col, two_d, sqrt_m1, four_p, pats, b_tab


def _make_kernel(op: str, reps: int):
    """A Pallas kernel running `reps` iterations of one primitive.
    x_ref: [32, B] int32 byte columns (seed data); consts_ref: the
    production kernel's packed constant block; out_ref: [8, B]."""

    def kernel(x_ref, consts_ref, out_ref):
        B = x_ref.shape[1]
        _d, two_d, _s, _fp, pats, b_tab = _unpack_consts(consts_ref)
        x = ep._norm(ep._from_bytes(x_ref[:]), 2)        # resting seed
        y = ep._norm(x + x, 2)
        one = jnp.concatenate(
            [jnp.ones((1, B), jnp.int32),
             jnp.zeros((LIMBS - 1, B), jnp.int32)], axis=0)
        t = ep._mul(x, y, pats, 0, 0)
        p = (x, y, one, t)

        if op == "noop":
            out_ref[:] = x[0:8]
            return
        if op == "carry":
            v = lax.fori_loop(0, reps, lambda _, u: ep._carry(u), x)
            out_ref[:] = v[0:8]
            return
        if op == "mul":
            def body(_, st):
                u, w = st
                return (ep._mul_nn(u, w, pats), u)
            u, _w = lax.fori_loop(0, reps, body, (x, y))
            out_ref[:] = u[0:8]
            return
        if op == "sqr":
            sqr = ep._make_sqr(pats)
            v = lax.fori_loop(0, reps, lambda _, u: sqr(u), x)
            out_ref[:] = v[0:8]
            return
        if op == "double":
            q = lax.fori_loop(
                0, reps, lambda _, u: ep._ext_double(u, pats), p)
            out_ref[:] = q[0][0:8]
            return
        if op == "add":
            def body(_, u):
                return ep._ext_add(u, p, two_d, pats)
            q = lax.fori_loop(0, reps, body, p)
            out_ref[:] = q[0][0:8]
            return
        if op == "madd":
            entry = (b_tab[3, 0], b_tab[3, 1], b_tab[3, 2])

            def body(_, u):
                return ep._madd_affine(u, entry, pats)
            q = lax.fori_loop(0, reps, body, p)
            out_ref[:] = q[0][0:8]
            return
        if op == "select16":
            w0 = x_ref[0:1] & 0xF

            def body(j, acc):
                w = (w0 + j) & 0xF
                sel = _where_tree(
                    w, [b_tab[i, 0] for i in range(16)])
                return acc + sel
            v = lax.fori_loop(0, reps, body,
                              jnp.zeros((LIMBS, B), jnp.int32))
            out_ref[:] = v[0:8]
            return
        if op == "window":
            # one full ladder window: 4 doublings + B-table madd +
            # lane-table ext_add, with both where-tree selects — the
            # lane table is stood in by 16 copies of p (same select
            # cost, no scratch build)
            w0 = x_ref[0:1] & 0xF
            lane_rows = [jnp.concatenate(p, axis=0)] * 16

            def body(j, acc):
                for i in range(4):
                    acc = ep._ext_double(acc, pats, need_t=(i == 3))
                w = (w0 + j) & 0xF
                bsel = tuple(_where_tree(
                    w, [b_tab[i, cix] for i in range(16)])
                    for cix in range(3))
                acc = ep._madd_affine(acc, bsel, pats)
                lsel = _where_tree(w, lane_rows)
                q = (lsel[0:LIMBS], lsel[LIMBS:2 * LIMBS],
                     lsel[2 * LIMBS:3 * LIMBS], lsel[3 * LIMBS:])
                return ep._ext_add(acc, q, two_d, pats)
            q = lax.fori_loop(0, reps, body, p)
            out_ref[:] = q[0][0:8]
            return
        raise ValueError(f"unknown op {op}")

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("op", "reps", "block", "interpret"))
def _bench_call(x_cols, op: str, reps: int, block: int = BLOCK,
                interpret: bool = False):
    n = x_cols.shape[1]
    grid = n // block
    return pl.pallas_call(
        _make_kernel(op, reps),
        out_shape=jax.ShapeDtypeStruct((8, n), jnp.int32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((32, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ep._CONSTS_NP.shape[0], 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((8, block), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x_cols, jnp.asarray(ep._CONSTS_NP))


def run_suite(m: int = M_DEFAULT, reps_timing: int = 5) -> list[dict]:
    """Compile and time every microbench on the TPU (NoTpuError
    without one: compiled Pallas kernels run nowhere else).  Returns
    one record per primitive, each naming the device it ran on."""
    dev = device.require_tpu()
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 256, (32, m), dtype=np.int32))
    x.block_until_ready()
    out = []
    for op, k in REPS.items():
        t_first = time.perf_counter()
        np.asarray(_bench_call(x, op=op, reps=k))    # compile + warm
        first_s = time.perf_counter() - t_first
        ts = []
        for _ in range(reps_timing):
            t0 = time.perf_counter()
            np.asarray(_bench_call(x, op=op, reps=k))
            ts.append((time.perf_counter() - t0) * 1000.0)
        med = float(np.median(ts))
        out.append({
            "metric": f"mb_{op}", "bucket": m, "value_ms": med,
            "reps": k, "per_op_us": med * 1000.0 / k,
            "first_call_s": first_s, "runs_ms": ts,
            "device": dev.summary()})
    return out
