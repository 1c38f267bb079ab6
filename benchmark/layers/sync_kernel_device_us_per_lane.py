"""`kernel_device_us_per_lane` in a catch-up cell, where it should move
sync_heights_per_s: the same reading, under a name of its own because
a per-layer metric names one end-to-end metric."""
from benchmark.layers.kernel_device_us_per_lane import read  # noqa: F401
