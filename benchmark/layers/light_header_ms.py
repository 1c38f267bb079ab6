"""Median `header_checks` span (light/verifier
_verify_new_header_and_vals): SignedHeader.validate_basic, the header's
hash, and the hash of the candidate's whole validator set, once an
attempt."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.median_ms(obs.spans, "header_checks")
