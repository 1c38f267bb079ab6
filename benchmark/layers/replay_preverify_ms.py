"""The pre-verification barrier of a replayed height: the `vote_preverify`
spans (sign bytes and memo keys of the read-ahead's votes, the seam's
batch on the device, the verdicts memoised) below the heights a playback
committed, in ms a height."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.per_height_ms(obs.spans, "vote_preverify")
