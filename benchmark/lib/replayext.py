"""A WAL playback with vote extensions in the spans and counters
(configuration ``vote-ext-60``): lib/replayspans.py's heights, and
below them the ``vote_extension`` spans (consensus; ``vote_tally``: a
precommit's extension verified, the pipeline barrier, the app's
VerifyVoteExtension), the ``consensus/process_proposal`` ABCI span (the
app's check of the injected commit info, ``oracle_validate``, inside
it), the ``ext`` attribute of ``vote_preverify`` and the counter family
``cometbft_consensus_vote_extension_verify_total{path}``.  A program
without them gives each reading nothing: None, never an exception.
"""
from __future__ import annotations

from typing import Optional

from . import probes, replayspans, spantree, stats

EXT_VERIFY = "cometbft_consensus_vote_extension_verify_total"


def per_height_median_ms(spans: list, name: str) -> Optional[float]:
    """Median over the committed heights of the summed duration of the
    spans called ``name`` below each, in ms; heights holding none count
    as 0, and None where no height holds one."""
    heights = replayspans.committed(spans)
    found = replayspans.below(spans, name)
    if not heights or not found:
        return None
    ids = spantree.by_id(spans)
    total = dict.fromkeys(heights, 0)
    for ev in found:
        for up in spantree.ancestors(ev, ids):
            if up["id"] in total:
                total[up["id"]] += ev["dur_ns"]
                break
    return stats.median(v / 1e6 for v in total.values())


def serial_share(delta: dict) -> Optional[float]:
    """100 x serial / (memo + serial) of the extension signatures the
    serial path judged over the window."""
    memo = probes.total(delta, EXT_VERIFY, path="memo")
    serial = probes.total(delta, EXT_VERIFY, path="serial")
    if memo + serial <= 0:
        return None
    return 100.0 * serial / (memo + serial)
