"""Median `light_store_read` span: what a request reads from the
TrustedStore before its first fetch (a miss at the target, then the
trusted block to start from, decoded with its whole validator set)."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.median_ms(obs.spans, "light_store_read")
