"""What a request still waits for once its Python walk has ended: the
end of a `commit_verify` span less the end of the `commit_walk` span
it holds, median over the requests.  A verifier that starts on the
device before the walk ends (tiles fed from BatchVerifier.add) shows
here and not in `seam_ms`, whose span then overlaps the walk."""
from benchmark.lib import spantree, stats


def read(obs):
    ids = spantree.by_id(obs.spans)
    return stats.median(
        (spantree.interval(ids[walk["parent"]])[1]
         - spantree.interval(walk)[1]) / 1e6
        for walk in spantree.under(obs.spans, "commit_walk",
                                   "commit_verify"))
