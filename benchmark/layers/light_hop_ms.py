"""Median `light_hop` span that verified its candidate: header checks,
the trusting check by address, the light check by index, two batches
through the seam."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.median_ms(obs.spans, "light_hop",
                                outcome="verified")
