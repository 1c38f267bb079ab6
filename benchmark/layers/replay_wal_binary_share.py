"""Share of the window's WAL records read from a binary payload:
100 × Δbinary / Δ(binary + json) of
`cometbft_consensus_wal_records_total{codec}` (consensus/wal.py, counted
where a record is decoded).  None where the program has no such counter
or decoded no record in the window."""
from benchmark.lib import probes

FAMILY = "cometbft_consensus_wal_records_total"


def read(obs):
    decoded = sum(probes.select(obs.metrics, FAMILY).values())
    if decoded <= 0:
        return None
    return 100.0 * probes.total(obs.metrics, FAMILY, codec="binary") \
        / decoded
