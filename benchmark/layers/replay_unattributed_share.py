"""Share of the committed `replay_height` spans' time that their
`wal_read`, `vote_preverify` and `vote_tally` children do not cover: the
playback's own steps between them."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.unattributed_share(
        obs.spans, "wal_read", "vote_preverify", "vote_tally")
