"""Per synced height, the kvstore state tree's Merkle root: the sum of
the `state_root` spans (statetree working_root) over the heights
applied."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_ms(obs.spans, "state_root")
