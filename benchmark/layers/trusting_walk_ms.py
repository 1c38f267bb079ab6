"""Median `commit_walk` span that looked its signers up by address
(`lookup` = `address`): the trusting check's walk of the new commit
against the TRUSTED set, refused hops' whole walks included."""
from benchmark.lib import lightspans


def read(obs):
    return lightspans.median_ms(obs.spans, "commit_walk",
                                lookup="address")
