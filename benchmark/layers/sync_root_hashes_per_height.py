"""Leaf and inner hashes the state tree computed per synced height: the
`hashes` counts of the `state_root` spans over the heights applied.
What an incremental root would cut from O(keys) to O(changed x log
keys)."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_count(obs.spans, "state_root", "hashes")
