"""The Python walk's share of a commit verification: the median over
the requests of `commit_walk` / the `commit_verify` span that holds
it, in per cent.  Since PR 28 a full tile is dispatched from
``BatchVerifier.add`` inside the walk, and since PR 30 tile 0's kernel
runs under the walk's last milliseconds: this is the share of a
request in which the host still has signatures to hand over, not a
share the device waits through (PERF.md section 3)."""
from benchmark.lib import spantree, stats


def read(obs):
    ids = spantree.by_id(obs.spans)
    return stats.median(
        100.0 * walk["dur_ns"] / ids[walk["parent"]]["dur_ns"]
        for walk in spantree.under(obs.spans, "commit_walk",
                                   "commit_verify")
        if ids[walk["parent"]]["dur_ns"] > 0)
