"""Per synced height, BlockExecutor.validate_block's own time: its
`validate_block` spans less the strict verification of the block's
LastCommit inside them (`commit_verify`, which `sync_commit_verify_ms`
reads), over the heights applied: the header and data checks, the
evidence pool."""
from benchmark.lib import spantree


def read(obs):
    return spantree.per_height_self_ms(obs.spans, "validate_block")
