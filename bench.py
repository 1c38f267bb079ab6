"""10k ed25519 signatures through the BatchVerifier seam, on the chip.

One process, one chip: the device gate (ops/device.py) must find a
TPU or this exits non-zero without printing a number — no CPU time is
ever written under a device metric's name.  The seeded workload goes
through ``crypto.batch.create_batch_verifier`` exactly as
types/validation.py's batch path drives it, and the result line names
the device it ran on.  A TPU breaker that is not closed afterwards
means some batch fell back to the CPU: that is a failure, not a
number.

A placeholder until the benchmark proper (ROADMAP S1) replaces it; it
claims nothing.  Diagnostics go to stderr; stdout carries one JSON
line.
"""
import argparse
import json
import os
import statistics
import sys
import time

N = 10_000          # the north-star validator count (BASELINE.json)
REPS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    from cometbft_tpu.ops import device
    dev = device.require_tpu()

    from cometbft_tpu.crypto import _native_loader, ed25519
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.libs.breaker import CLOSED
    from cometbft_tpu.ops import ed25519_jax
    from cometbft_tpu.tools import benchmarks

    if _native_loader.load(allow_build=True) is None:
        raise RuntimeError("native module did not build")
    backend = crypto_batch.get_backend()
    if backend != "tpu":
        raise RuntimeError(f"crypto backend resolved to {backend!r}")
    items = [(ed25519.Ed25519PubKey(pub), msg, sig) for pub, msg, sig
             in benchmarks.seeded_sig_items(N, args.seed)]
    t0 = time.perf_counter()
    ed25519_jax.warmup(N)
    print(f"[bench] warm-up {time.perf_counter() - t0:.1f} s "
          f"(compile cache: {dev.cache_dir})", file=sys.stderr)

    runs_ms = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        bv = crypto_batch.create_batch_verifier(items[0][0])
        for pub, msg, sig in items:
            bv.add(pub, msg, sig)
        ok, mask = bv.verify()
        runs_ms.append((time.perf_counter() - t0) * 1000.0)
        if not ok or len(mask) != N:
            raise RuntimeError("an honest signature was rejected")
    breaker = crypto_batch.tpu_breaker().state
    if breaker != CLOSED:
        raise RuntimeError(f"TPU breaker is {breaker}: a batch fell "
                           f"back to the CPU verifier")
    print(json.dumps({
        "metric": "batch_verify_p50",
        "value": statistics.median(runs_ms), "unit": "ms",
        "runs_ms": runs_ms, "n": N, "seed": args.seed,
        "backend": backend,
        "device": dev.summary(),
        "claim": None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
