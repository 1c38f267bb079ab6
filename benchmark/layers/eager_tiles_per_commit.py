"""Tiles of a commit that went to the device before the commit's walk
had ended: the median over the device's `batch_verify` spans of their
`kernel_execute` children that carry `eager` (set by
ops/ed25519_jax.TilePipeline.feed for a tile fed from
BatchVerifier.add).  0 where the verifier waits for verify() — every
batch below one tile, and every program before the streamed seam; 1 at
valset-10k (6,667 signatures: 4,096 fed from add, 2,571 by verify)."""
from benchmark.lib import probes, spantree, stats


def read(obs):
    kids = spantree.children(obs.spans)
    return stats.median(
        sum(1 for e in kids.get(bv["id"], ())
            if e["name"] == "kernel_execute" and probes.attr(e, "eager"))
        for bv in obs.spans
        if bv["name"] == "batch_verify" and bv.get("id")
        and probes.attr(bv, "backend") == "tpu")
