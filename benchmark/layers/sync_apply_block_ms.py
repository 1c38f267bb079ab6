"""Per synced height, the time inside BlockExecutor._apply_block: the
sum of the window's `apply_block` spans over the heights applied
(FinalizeBlock, saving its response, the state update, the app's
Commit, the state save, events)."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_ms(obs.spans, "apply_block")
