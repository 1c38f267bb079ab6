"""Median batch_verify span: one batch through the crypto seam
(crypto/batch.py), host prep, transfer, kernel and mask included."""
from benchmark.lib import probes


def read(obs):
    return probes.median_span_ms(obs.spans, "batch_verify")
