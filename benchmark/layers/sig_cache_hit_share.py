"""Share of the signatures the by-index walks looked at that the
SignatureCache satisfied: 100 x sum(`cache_hits`) / sum(`walked`) over
the `commit_walk` spans whose `lookup` is `index`.  At light-1k.skip
the hits are what the trusting check of the same hop had verified
(~240 of 667)."""
from benchmark.lib import probes


def read(obs):
    walks = [ev for ev in obs.spans if ev["name"] == "commit_walk"
             and probes.attr(ev, "lookup") == "index"]
    walked = sum(probes.attr(ev, "walked", 0) for ev in walks)
    if not walked:
        return None
    return 100.0 * sum(probes.attr(ev, "cache_hits", 0)
                       for ev in walks) / walked
