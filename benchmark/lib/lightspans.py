"""The light client's spans (libs/tracing category ``light``): a
``light_sync`` per request, under it a ``light_hop`` per verify()
attempt (``outcome``: verified | cant_trust | invalid) with the
attempt's ``header_checks`` and ``commit_verify`` spans below, and
``light_store_read`` / ``light_fetch`` / ``light_store_save`` /
``light_detect`` beside the hops.  Pure functions of the recorder's events; a program that records
none of these spans gives each of them nothing to read: they return
None and never raise.
"""
from __future__ import annotations

from typing import Callable, Optional

from . import probes, spantree, stats


def median_ms(spans: list, name: str, **attrs) -> Optional[float]:
    """Median duration of the spans called ``name`` whose attributes
    include ``attrs``."""
    return stats.median(
        ev["dur_ns"] / 1e6 for ev in spans if ev["name"] == name
        and all(probes.attr(ev, k) == v for k, v in attrs.items()))


def per_sync(spans: list, wanted: Callable[[dict], bool]
             ) -> Optional[float]:
    """Median over the ``light_sync`` spans of how many spans that
    ``wanted`` accepts lie anywhere below each."""
    ids = spantree.by_id(spans)
    counts = {ev["id"]: 0 for ev in ids.values()
              if ev["name"] == "light_sync"}
    for ev in spans:
        if not wanted(ev):
            continue
        for up in spantree.ancestors(ev, ids):
            if up["id"] in counts:
                counts[up["id"]] += 1
                break
    return stats.median(counts.values())


def hops_per_sync(spans: list, outcome: str) -> Optional[float]:
    return per_sync(spans, lambda ev: ev["name"] == "light_hop"
                    and probes.attr(ev, "outcome") == outcome)
