"""Median `entries` of a `vote_preverify` span: the signatures one
read-ahead hands the seam in one batch.  The guard that the traffic is
what the cell names (49 late precommits + 150 prevotes + 101 precommits = 300
at 150 validators)."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.median_attr(obs.spans, "vote_preverify", "entries")
