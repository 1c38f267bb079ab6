"""Median `h2d` span under a warm `kernel_execute`: the one
jax.device_put of a dispatch's packed ``[bucket, 192]`` uint8 wire
buffer (four calls before PR 26); it returns before the copy
finishes."""
from benchmark.lib import spantree


def read(obs):
    return spantree.median_under_ms(obs.spans, "h2d", "kernel_execute",
                                    warm_only=True)
