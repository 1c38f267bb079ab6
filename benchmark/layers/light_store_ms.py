"""Median `light_store_save` span: TrustedStore.save_light_block of one
verified light block (its validators and signatures through the
generic encoder)."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.median_ms(obs.spans, "light_store_save")
