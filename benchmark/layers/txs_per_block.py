"""Mean txs per block committed in the window (observer node)."""


def read(obs):
    sizes = obs.samples.get("block_txs") or []
    return sum(sizes) / len(sizes) if sizes else None
