"""Median `ext` of a `vote_preverify` span: the extension signatures one
read-ahead hands the seam beside the vote signatures (two a non-nil
precommit, the late precommits of the last commit included).  The guard
that the traffic is what the cell names: (19 late + 41 on time) x 2 =
120 at 60 validators."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.median_attr(obs.spans, "vote_preverify", "ext")
