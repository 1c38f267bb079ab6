"""How late the open-loop generator ran: send time minus due time, 95th
percentile.  A starved generator must not be read as a fast server."""
from benchmark.lib import stats


def read(obs):
    return stats.percentile(obs.samples.get("late_ms", ()), 95)
