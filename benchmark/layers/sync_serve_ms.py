"""Per synced height, the SOURCE peer's load, encode and send of a block
(`block_serve`): the benchmark's source shares the interpreter with
the node under test, and this is its share made visible."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_ms(obs.spans, "block_serve")
