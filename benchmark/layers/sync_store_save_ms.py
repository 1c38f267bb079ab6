"""Per synced height, the block store's write of the block (meta, parts,
commits): the sum of the `store_save_block` spans over the heights
applied."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_ms(obs.spans, "store_save_block")
