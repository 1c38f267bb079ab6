"""`commit_verify_ms` in a live-net cell, where it should move the tx commit
latencies: the same reading, under a name of its own because a
per-layer metric names one end-to-end metric."""
from benchmark.layers.commit_verify_ms import read  # noqa: F401
