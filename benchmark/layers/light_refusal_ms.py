"""Median `light_hop` span refused for lack of trust (`cant_trust`):
header checks and a whole walk of the commit by address, no
dispatch: a verdict that needs no signature."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.median_ms(obs.spans, "light_hop",
                                outcome="cant_trust")
