"""Finalising a replayed height: the `finalize_commit` spans (validate the
block with its LastCommit on the device, save it with the seen commit,
apply it) below the heights a playback committed, in ms a height."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.per_height_ms(obs.spans, "finalize_commit")
