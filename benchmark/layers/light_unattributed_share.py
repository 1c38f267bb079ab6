"""Share of the `light_sync` spans' time that no child span covers: 100
x sum(self time) / sum(duration).  What is left is the client's own
steps between fetch, hop, save and the witness check."""
from benchmark.lib import spantree


def read(obs):
    syncs = [ev for ev in obs.spans
             if ev["name"] == "light_sync" and ev.get("id")]
    total = sum(ev["dur_ns"] for ev in syncs)
    if not total:
        return None
    kids = spantree.children(obs.spans)
    return 100.0 * sum(spantree.self_ns(ev, kids) for ev in syncs) / total
