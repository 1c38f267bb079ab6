"""95th percentile of the node's event-loop lag sampler
(libs/health.py, node_event_loop_lag_seconds) over the window.  All
nodes of an in-process net share one loop, so any node's reads it."""
from benchmark.lib import probes


def read(obs):
    return probes.hist_quantile_ms(
        obs.metrics, "cometbft_node_event_loop_lag_seconds", 0.95)
