"""The Python walk's share of a commit verification: the median over
the requests of `commit_walk` / the `commit_verify` span that holds
it, in per cent.  The walk ends before the first tile is dispatched,
so this is the part of a request during which the device has nothing
to do."""
from benchmark.lib import spantree, stats


def read(obs):
    ids = spantree.by_id(obs.spans)
    return stats.median(
        100.0 * walk["dur_ns"] / ids[walk["parent"]]["dur_ns"]
        for walk in spantree.under(obs.spans, "commit_walk",
                                   "commit_verify")
        if ids[walk["parent"]]["dur_ns"] > 0)
