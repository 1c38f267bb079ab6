"""Sharded-dispatch model for the 10k north star.

The < 5 ms claim for the 10k commit has always rested on 8-way
sharding.  This module pins down what a CPU host CAN prove and
derives the sharded estimate from the one single-chip number on
record (round 4's device-only measurement, taken before the current
kernel source and not reproduced since), with every assumption stated
in the artifact — a model, not a measurement:

  * geometry: the production verify_sharded padding/rounding for
    m = 10240 over ndev devices (per-shard lanes, pallas grid steps);
  * collective structure: the shard_map'ed verify + tally steps are
    LOWERED on the virtual CPU mesh and the StableHLO is scanned —
    the verify path must contain NO cross-device collective (it is
    embarrassingly lane-parallel) and the tally must contain exactly
    the psum all-reduce;
  * execution: the sharded dispatch RUNS on the virtual mesh at a
    reduced lane count (the full 10k xla-kernel run costs ~7 min of
    serial CPU — the driver's dryrun budget forbids it; geometry and
    collectives don't change with lane count);
  * timing model: sharded_ms = per_shard_lanes x measured_us_per_lane
    + overhead_ms, with measured_us_per_lane = device_ms / bucket from
    the best single-chip hardware record, overhead bounded by the
    dispatch/launch cost measured on the same record's runs.

Run:  python -m cometbft_tpu.parallel.report   (writes SHARDING_10K.json)
"""
from __future__ import annotations

import json
import os
import sys

N_STAR = 10_000
BUCKET = 10_240
NDEV = 8

# Round-4 measurement (KERNEL_NOTES.md "MEASURED on TPU v5e-1"): the
# 24-limb pallas kernel, device-only, m=16384 — the model's only
# calibration, older than the current kernel source.
R4_MEASURED = {"device_ms": 116.0, "bucket": 16384,
               "source": "round-4 measurement (KERNEL_NOTES.md), "
                         "older than the current kernel source"}


def _collectives(hlo: str) -> list[str]:
    ops = []
    for marker in ("all-reduce", "all_reduce", "all-gather",
                   "all_gather", "collective-permute",
                   "collective_permute", "reduce-scatter",
                   "reduce_scatter", "all-to-all", "all_to_all"):
        if marker in hlo:
            ops.append(marker.replace("_", "-"))
    return sorted(set(ops))


def sharded_10k_report(ndev: int = NDEV, m: int = BUCKET,
                       run_lanes: int = 2048) -> dict:
    import numpy as np
    import jax

    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={ndev}").strip()
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ..ops import ed25519_jax as ej
    from ..ops.ed25519_pallas import BLOCK
    from . import mesh as pmesh

    # --- geometry (mirrors verify_sharded's rounding) ---------------
    shard = -(-m // ndev)
    shard_pallas = -(-shard // BLOCK) * BLOCK
    geometry = {
        "n_signatures": N_STAR, "bucket": m, "devices": ndev,
        "per_shard_lanes": shard_pallas,
        "pallas_grid_steps_per_shard": shard_pallas // BLOCK,
        "block": BLOCK,
        "padded_total": shard_pallas * ndev,
    }

    # --- collective structure from the lowered shard_map ------------
    mesh = pmesh.make_mesh(ndev)
    a = jnp.zeros((shard_pallas * ndev, 32), jnp.uint8)
    w = jnp.zeros((shard_pallas * ndev, 64), jnp.uint8)
    verify_fn = pmesh._sharded_verify_fn(ndev, "xla", False, 0)
    verify_hlo = verify_fn.lower(a, a, w, w).as_text()
    tally_fn = pmesh.sharded_verify_tally(mesh)
    tally_hlo = tally_fn.lower(a, a, w, w).as_text()
    collectives = {
        "verify_path": _collectives(verify_hlo),
        "tally_path": _collectives(tally_hlo),
    }

    # --- execution on the virtual mesh at reduced lanes -------------
    from ..crypto import _ed25519_ref as ref
    items, golden = [], []
    for i in range(run_lanes // 256):
        seed = bytes([i + 1]) * 32
        pub = ref.public_key(seed)
        msg = b"shard-%d" % i
        sig = ref.sign(seed, msg)
        if i % 4 == 3:
            sig = sig[:32] + bytes(32)
        items.append((pub, msg, sig))
        golden.append(ref.verify(pub, msg, sig))
    wire, pre_bad = ej.prep_arrays(items, run_lanes)
    import numpy as _np
    ok = _np.array(pmesh.verify_sharded(*ej.wire_views(wire),
                                        ndev=ndev, kernel="xla"))
    ok = ok[:len(items)]
    ok[pre_bad[:len(items)]] = False
    executed = bool(list(ok) == golden)

    # --- timing model from measured numbers -------------------------
    cal = dict(R4_MEASURED)
    us_per_lane = cal["device_ms"] * 1000.0 / cal["bucket"]
    # dispatch overhead: bounded by the spread of the measured runs
    # (launch + sync, single chip); use 0.5 ms/chip as the stated cap
    overhead_ms = 0.5
    sharded_ms = geometry["per_shard_lanes"] * us_per_lane / 1000.0 \
        + overhead_ms
    single_ms = BUCKET * us_per_lane / 1000.0
    model = {
        "calibration": cal,
        "us_per_lane_measured": round(us_per_lane, 3),
        "assumptions": [
            "perfect lane scaling (the verify path has no cross-"
            "device collective - checked above; lanes are fully "
            "data-parallel at [24,128] slab granularity)",
            f"per-chip dispatch overhead <= {overhead_ms} ms "
            "(launch + output sync; the mask all-gather is 1 byte/"
            "lane = 1.3 kB/chip, negligible on ICI)",
            "every chip runs the same kernel the single-chip "
            "measurement ran (smaller grid)",
        ],
        "single_chip_10240_ms": round(single_ms, 1),
        "sharded_8way_ms": round(sharded_ms, 1),
        "north_star_ms": 5.0,
        "verdict": (
            "MEETS < 5 ms" if sharded_ms < 5.0 else
            f"MISSES < 5 ms at {sharded_ms:.1f} ms with the measured "
            f"kernel: needs ~{sharded_ms / 5.0:.1f}x more chips or "
            "kernel speedup (see KERNEL_NOTES round-5 floor "
            "analysis)"),
    }
    return {"geometry": geometry, "collectives": collectives,
            "executed_reduced": {"lanes": run_lanes, "ok": executed},
            "timing_model": model}


def main() -> int:
    rep = sharded_10k_report()
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "SHARDING_10K.json")
    with open(out, "w") as f:
        json.dump(rep, f, indent=1)
        f.write("\n")
    print(json.dumps(rep["timing_model"], indent=1), file=sys.stderr)
    print(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
