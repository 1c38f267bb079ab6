"""Median host_prep span (ops/ed25519_jax.prep_arrays -> native/):
length and canonical-S checks, SHA-512 mod L, window split, padding."""
from benchmark.lib import probes


def read(obs):
    return probes.median_span_ms(obs.spans, "host_prep")
