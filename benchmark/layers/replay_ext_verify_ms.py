"""Median over the committed heights of the summed `vote_extension`
spans below each: what a height's precommits of the height in hand
spend in `_add_vote`'s extension block (both extension signatures on
the memo, the pipeline barrier, the app's VerifyVoteExtension)."""
from benchmark.lib import replayext


def read(obs):
    return replayext.per_height_median_ms(obs.spans, "vote_extension")
