"""Share of the window that no named piece of work covers: 100 x (the
stretch from the first span's start to the last span's end - the
union of every span with a duration but `sync_height` and `sync_wait`)
/ that stretch.  Those two tile the sync loop by construction, so
their own time is exactly what is still unnamed: inside the wait, the
event loop's work for the p2p connections; inside the height, the
loop's own steps between its children.  Only a program whose spans
carry parents is read: before that no span covered the sync loop, and
the share said nothing."""
from benchmark.lib import spantree


def read(obs):
    if not spantree.by_id(obs.spans):
        return None
    return spantree.unattributed_share(
        obs.spans, frames=("sync_height", "sync_wait"))
