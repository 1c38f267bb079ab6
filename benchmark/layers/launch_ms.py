"""Median `launch` span under a warm `kernel_execute`:
the jitted kernel call until it returns (argument handling and
enqueue; the kernel runs on)."""
from benchmark.lib import spantree


def read(obs):
    return spantree.median_under_ms(obs.spans, "launch", "kernel_execute",
                                    warm_only=True)
