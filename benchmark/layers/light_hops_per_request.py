"""Verified hops a request took: the median over the `light_sync` spans
of their `light_hop` children whose `outcome` is `verified`.  4 at
light-1k.skip (65, 129, 193, 257): the guard that the traffic is the
bisection the cell names."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.hops_per_sync(obs.spans, "verified")
