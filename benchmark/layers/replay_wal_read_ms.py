"""Reading a height off the WAL: the `wal_read` spans (frames, CRC, JSON,
`message_from_wal`) below the heights a playback committed, in ms a
height."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.per_height_ms(obs.spans, "wal_read")
