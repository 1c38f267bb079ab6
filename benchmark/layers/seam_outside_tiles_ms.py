"""What a tiled `batch_verify` span spends outside its tiles: its
duration less the `host_prep` of tile 0 (no kernel overlaps it; since
PR 28 it lies inside the commit's walk, on the walking thread) less
the union of its `kernel_execute` spans (tile 0's launch to the last
tile's settle; the later tiles' `host_prep` lies inside it), median
over the tiled batches.  It holds the verifier wrappers' hand-over of
the items, the plan, the mask's hand-back and the items' release
(since PR 27 the spans `item_handover`, `mask_handback` and
`item_release`)."""
from benchmark.lib import spantree, stats, tiled


def read(obs):
    outside = []
    for bv, preps, tiles in tiled.batches(obs.spans):
        lo, hi = spantree.interval(bv)
        first_prep = preps[0]["dur_ns"] if preps else 0
        outside.append((bv["dur_ns"] - first_prep
                        - spantree.coverage_ns(tiles, lo, hi)) / 1e6)
    return stats.median(outside)
