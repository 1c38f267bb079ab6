"""Share of a playback's votes whose signature was verified one by one
on the CPU: 100 x `serial_verifies` / `votes` over the `vote_tally`
spans.  It must read the forged share (one vote in `forged_one_in`
heights: the confirmation of the lane the batch refused); anything above
it is a batch that left the device path."""
from benchmark.lib import replayspans


def read(obs):
    return replayspans.share(obs.spans, "vote_tally", "serial_verifies",
                             "votes")
