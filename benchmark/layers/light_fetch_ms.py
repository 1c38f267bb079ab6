"""Median `light_fetch` span: one light block from the provider.  The
benchmark's provider shares the interpreter with the client under test
and decodes a fresh block a fetch; this is its share made visible."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.median_ms(obs.spans, "light_fetch")
