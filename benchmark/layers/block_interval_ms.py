"""Median gap between consecutive blocks as the observer node saw them
commit."""
from benchmark.lib import stats


def read(obs):
    t = obs.samples.get("block_ns") or []
    return stats.median([(b - a) / 1e6 for a, b in zip(t, t[1:])])
