"""Hops a request was refused for lack of trust and bisected: the median
over the `light_sync` spans of their `light_hop` children whose
`outcome` is `cant_trust`.  3 at light-1k.skip (257 and 129 from 1,
257 from 129)."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.hops_per_sync(obs.spans, "cant_trust")
