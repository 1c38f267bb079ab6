"""Median `h2d` span under a warm `kernel_execute`:
the four jax.device_put calls of one dispatch (they return before the
copies finish)."""
from benchmark.lib import spantree


def read(obs):
    return spantree.median_under_ms(obs.spans, "h2d", "kernel_execute",
                                    warm_only=True)
