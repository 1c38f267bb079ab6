"""Handling a replayed height's records one by one: the `vote_tally`
spans below the heights a playback committed, less the `finalize_commit`
that runs inside the one whose precommit completed +2/3, in ms a
height: proposal, parts, and every vote through `VoteSet.add_vote` on the
memo."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.per_height_ms(obs.spans, "vote_tally",
                                     less="finalize_commit")
