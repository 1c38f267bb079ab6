"""Lanes computed for nothing: 1 - sum(batch) / sum(bucket) over the
warm kernel_execute spans."""
from benchmark.lib import probes


def read(obs):
    warm = [ev for ev in obs.crypto("kernel_execute")
            if probes.attr(ev, "warm")]
    lanes = sum(probes.attr(ev, "bucket", 0) for ev in warm)
    if not lanes:
        return None
    used = sum(probes.attr(ev, "batch", 0) for ev in warm)
    return 100.0 * (1.0 - used / lanes)
