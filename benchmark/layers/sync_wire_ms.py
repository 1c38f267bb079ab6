"""Per synced height, the wire work on the syncing node: decoding a
block response into a Block and handing it to the pool
(`block_decode`) plus cutting the block into its part set and hashing
it (`part_set`), summed over the heights applied."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_ms(obs.spans, "block_decode", "part_set")
