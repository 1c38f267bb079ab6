"""`kernel_execute_ms` in a catch-up cell, where it should move
sync_heights_per_s: the same reading, under a name of its own because
a per-layer metric names one end-to-end metric."""
from benchmark.layers.kernel_execute_ms import read  # noqa: F401
