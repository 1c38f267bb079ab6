"""Tiles a commit's signatures were dispatched as: the median over the
tiled `batch_verify` spans of their warm pipelined `kernel_execute`
children (valset-10k: 6,667 signatures, two tiles at the 4,096
bucket).  A batch that fits one tile is not tiled and has no say."""
from benchmark.lib import stats, tiled


def read(obs):
    return stats.median(len(tiles)
                        for _, _, tiles in tiled.batches(obs.spans))
