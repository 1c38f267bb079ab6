"""Per-height time of the blocksync loop outside commit verification:
the mean interval between consecutive block saves on the syncing
node's store, minus the window's mean commit-verification time per
height (two verifications a height: the light one on the next block's
LastCommit, the strict one in validate_block)."""
from benchmark.lib import probes


def read(obs):
    saves = obs.samples.get("save_ns") or []
    if len(saves) < 2:
        return None
    gaps = len(saves) - 1
    interval_ms = (saves[-1] - saves[0]) / 1e6 / gaps
    verify_s = probes.total(
        obs.metrics, "cometbft_consensus_commit_verify_seconds_sum")
    return max(0.0, interval_ms - verify_s * 1e3 / gaps)
