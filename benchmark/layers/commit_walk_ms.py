"""Median `commit_walk` span: the signature walk of one commit
verification up to the seam call (validator lookup, sign-bytes,
BatchVerifier.add)."""
from benchmark.lib import probes


def read(obs):
    return probes.median_span_ms(obs.spans, "commit_walk")
