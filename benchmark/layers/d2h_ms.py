"""Median `d2h` span under a warm `kernel_execute`:
np.asarray of the finished mask, what is left of its copy back to
the host (queued behind the kernel before the wait)."""
from benchmark.lib import spantree


def read(obs):
    return spantree.median_under_ms(obs.spans, "d2h", "kernel_execute",
                                    warm_only=True)
