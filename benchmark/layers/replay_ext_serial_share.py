"""Share of the extension signature checks of the window that were
verified one by one on the CPU: 100 x serial / (memo + serial) of
`cometbft_consensus_vote_extension_verify_total`.  It must read the
forged share (the confirmations of the extension lanes the batch
refused, one a forged extension signature); anything above it is a
batch that left the device path."""
from benchmark.lib import replayext


def read(obs):
    return replayext.serial_share(obs.metrics)
