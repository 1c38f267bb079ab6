"""Share of the traced sub-window in which no operation ran on the
device: 1 - union of device-op intervals / window (profiler trace)."""
from benchmark.lib import profile


def read(obs):
    return None if obs.trace is None else profile.idle_share(obs.trace)
