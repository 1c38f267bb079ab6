"""A WAL playback in the spans (libs/tracing category ``consensus``): a
``wal_replay`` per playback, under it a ``replay_height`` per height
(``outcome``: committed | stalled), and under each of those the
``wal_read`` (frames, CRC, JSON, message_from_wal), the
``vote_preverify`` (the barrier around the seam's batch) and the
``vote_tally`` (the records handled one by one) of every read-ahead;
the ``finalize_commit`` lies inside the ``vote_tally`` whose precommit
completed +2/3.  The denominator of every per-height reading is the
``replay_height`` spans that committed; what lies below any other is
left out.  Pure functions of the recorder's events; a program that
records none of these spans gives each of them nothing to read: they
return None and never raise.
"""
from __future__ import annotations

from typing import Optional

from . import probes, spantree, stats


def committed(spans: list) -> dict[int, dict]:
    """id -> the ``replay_height`` spans that committed their height."""
    return {ev["id"]: ev for ev in spans
            if ev["name"] == "replay_height" and ev.get("id")
            and probes.attr(ev, "outcome") == "committed"}


def below(spans: list, name: str) -> list:
    """The spans called ``name`` anywhere below a committed
    ``replay_height``."""
    heights = committed(spans)
    if not heights:
        return []
    ids = spantree.by_id(spans)
    return [ev for ev in spans if ev["name"] == name and any(
        up["id"] in heights for up in spantree.ancestors(ev, ids))]


def per_height_ms(spans: list, name: str, less: str = "") -> Optional[float]:
    """Total duration of the spans called ``name`` (less that of their
    children called ``less``) over the heights committed, in ms."""
    found = below(spans, name)
    if not found:
        return None
    total = sum(ev["dur_ns"] for ev in found)
    if less:
        mine = {ev["id"] for ev in found}
        total -= sum(ev["dur_ns"] for ev in spans
                     if ev["name"] == less and ev.get("parent") in mine)
    return total / 1e6 / len(committed(spans))


def unattributed_share(spans: list, *covered: str) -> Optional[float]:
    """100 x the part of the committed heights' time that none of
    their children called one of ``covered`` accounts for."""
    heights = committed(spans)
    total = sum(ev["dur_ns"] for ev in heights.values())
    if not total:
        return None
    named = sum(ev["dur_ns"] for ev in spans
                if ev["name"] in covered and ev.get("parent") in heights)
    return 100.0 * (total - named) / total


def median_attr(spans: list, name: str, key: str) -> Optional[float]:
    """Median of the attribute ``key`` over the spans called ``name``."""
    return stats.median(
        v for v in (probes.attr(ev, key) for ev in spans
                    if ev["name"] == name) if v is not None)


def share(spans: list, name: str, part: str, whole: str) -> Optional[float]:
    """100 x sum(attr ``part``) / sum(attr ``whole``) over the spans
    called ``name``."""
    found = [ev for ev in spans if ev["name"] == name
             and probes.attr(ev, whole)]
    if not found:
        return None
    return 100.0 * sum(probes.attr(ev, part, 0) for ev in found) \
        / sum(probes.attr(ev, whole) for ev in found)
