"""Seconds set-up waited for the cell's chain after its own shape
set-up: a child process fabricates the chain from the seed meanwhile,
so this is what fabrication adds to setup_s, not what it costs."""


def read(obs):
    return obs.laps.get("chain")
