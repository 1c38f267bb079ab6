"""Per synced height, state/store.save (the state record with both
validator sets, the validators and params rows): the sum of the
`state_save` spans over the heights applied."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_ms(obs.spans, "state_save")
