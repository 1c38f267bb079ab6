"""Batches a request sent through the crypto seam: the median over the
`light_sync` spans of the `batch_verify` spans anywhere below them.  8
at light-1k.skip: two checks a verified hop, none a refusal.  Batching
across hops or checks lowers it."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.per_sync(
        obs.spans, lambda ev: ev["name"] == "batch_verify")
