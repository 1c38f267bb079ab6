"""Per synced height, the time the blocksync loop had nothing to apply:
the sum of the `sync_wait` spans (wait_apply parked, or woke to fewer
than two blocks) over the heights applied."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_ms(obs.spans, "sync_wait")
