"""Median over the committed heights of the `consensus/process_proposal`
ABCI span below each: the app's ProcessProposal, which re-verifies the
injected commit info of the height before (`oracle_validate`, its
extension signatures in one batch of the seam) before the node
prevotes."""
from benchmark.lib import replayext


def read(obs):
    return replayext.per_height_median_ms(obs.spans,
                                          "consensus/process_proposal")
