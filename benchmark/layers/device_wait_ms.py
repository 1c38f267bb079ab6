"""Median `device_wait` span under a warm `kernel_execute`:
the mask's copy back queued (copy_to_host_async), then
block_until_ready (what is left of the transfers, the kernel, the
runtime's completion notice)."""
from benchmark.lib import spantree


def read(obs):
    return spantree.median_under_ms(obs.spans, "device_wait", "kernel_execute",
                                    warm_only=True)
