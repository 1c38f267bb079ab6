"""Mean time of one commit verification (types/validation.verify_commit*):
the consensus_commit_verify_seconds histogram's delta over the window,
sum over count, every kind."""
from benchmark.lib import probes


def read(obs):
    return probes.hist_mean_ms(
        obs.metrics, "cometbft_consensus_commit_verify_seconds")
