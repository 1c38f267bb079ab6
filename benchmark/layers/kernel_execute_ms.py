"""Median warm kernel_execute span: transfer in, kernel, mask back, on
the host's clock (the span ends in the forced mask read)."""
from benchmark.lib import probes


def read(obs):
    return probes.median_span_ms(obs.spans, "kernel_execute",
                                 warm_only=True)
