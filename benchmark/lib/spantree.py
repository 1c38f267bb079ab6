"""The program's spans as a tree: pure functions of the recorder's
events (libs/tracing snapshot(): ``ts_ns``, ``dur_ns``, ``name``,
``height``, ``attrs`` and — from the PR that gave spans causality —
``id``, ``parent``, ``tid``) and of the reduced profiler trace
(lib/profile).

A program that records no ``id``/``parent`` (an older commit) gives
every function here nothing to read: they return None or an empty
list and never raise, so a reader built on them leaves its metric out.
"""
from __future__ import annotations

from typing import Iterable, Optional

from . import probes, profile, stats


def by_id(spans: Iterable[dict]) -> dict[int, dict]:
    return {ev["id"]: ev for ev in spans if ev.get("id")}


def children(spans: Iterable[dict]) -> dict[int, list[dict]]:
    """parent id -> its child events, in the order given."""
    out: dict[int, list[dict]] = {}
    for ev in spans:
        if ev.get("parent"):
            out.setdefault(ev["parent"], []).append(ev)
    return out


def ancestors(ev: dict, ids: dict[int, dict]) -> list[dict]:
    """The chain of parents of ``ev``, nearest first, as far as
    ``ids`` knows them."""
    chain, seen = [], set()
    parent = ids.get(ev.get("parent") or 0)
    while parent is not None and parent["id"] not in seen:
        chain.append(parent)
        seen.add(parent["id"])
        parent = ids.get(parent.get("parent") or 0)
    return chain


def interval(ev: dict) -> tuple[int, int]:
    return ev["ts_ns"], ev["ts_ns"] + ev["dur_ns"]


def self_ns(ev: dict, kids: dict[int, list[dict]]) -> float:
    """Duration minus the union of the children's intervals (each
    clipped to the parent's own)."""
    lo, hi = interval(ev)
    covered = profile.union_ns(
        (max(lo, s), min(hi, e))
        for s, e in map(interval, kids.get(ev.get("id") or 0, ())))
    return ev["dur_ns"] - covered


def coverage_ns(spans: Iterable[dict], lo: int, hi: int) -> float:
    """Length of the union of the spans' intervals inside [lo, hi)."""
    return profile.union_ns(
        (max(lo, s), min(hi, e)) for s, e in map(interval, spans))


def unattributed_share(spans: list[dict],
                       frames: tuple[str, ...] = ()) -> Optional[float]:
    """100 x the part of the stretch the spans reach over (first start
    to last end) that no span with a duration covers.  Spans called
    one of ``frames`` stretch it and cover nothing: they tile a loop
    by construction, so their own time names nothing."""
    timed = [ev for ev in spans if ev["dur_ns"] > 0]
    if not timed:
        return None
    lo = min(ev["ts_ns"] for ev in timed)
    hi = max(interval(ev)[1] for ev in timed)
    named = [ev for ev in timed if ev["name"] not in frames]
    return 100.0 * (1.0 - coverage_ns(named, lo, hi) / (hi - lo))


def per_height_self_ms(spans: list[dict], name: str) -> Optional[float]:
    """Total self time (duration less what the children cover) of the
    spans called ``name`` over the heights applied, in ms."""
    heights = heights_applied(spans)
    kids = children(spans)
    own = [self_ns(ev, kids) for ev in spans
           if ev["name"] == name and ev.get("id")]
    if not heights or not own:
        return None
    return sum(own) / 1e6 / heights


def under(spans: list[dict], name: str, parent_name: str,
          warm_only: bool = False) -> list[dict]:
    """Spans called ``name`` whose parent is a ``parent_name`` span
    (with ``warm_only``: one whose ``warm`` attr is true)."""
    parents = {ev["id"] for ev in spans
               if ev["name"] == parent_name and ev.get("id")
               and (not warm_only or probes.attr(ev, "warm"))}
    return [ev for ev in spans
            if ev["name"] == name and ev.get("parent") in parents]


def median_under_ms(spans: list[dict], name: str, parent_name: str,
                    warm_only: bool = False) -> Optional[float]:
    return stats.median(ev["dur_ns"] / 1e6 for ev in
                        under(spans, name, parent_name, warm_only))


# -- one synced height ---------------------------------------------------

def heights_applied(spans: Iterable[dict]) -> int:
    """Heights the blocksync loop took up and applied: its
    ``sync_height`` spans whose ``outcome`` is ``applied``."""
    return sum(1 for ev in spans if ev["name"] == "sync_height"
               and probes.attr(ev, "outcome") == "applied")


def per_height_ms(spans: list[dict], *names: str) -> Optional[float]:
    """Total duration of the spans called any of ``names`` over the
    heights applied, in ms; None where either is missing."""
    heights = heights_applied(spans)
    durs = [ev["dur_ns"] for ev in spans if ev["name"] in names]
    if not heights or not durs:
        return None
    return sum(durs) / 1e6 / heights


def per_height_count(spans: list[dict], name: str,
                     key: str) -> Optional[float]:
    """Sum of the attr ``key`` of the spans called ``name`` over the
    heights applied."""
    heights = heights_applied(spans)
    counts = [probes.attr(ev, key) for ev in spans if ev["name"] == name]
    counts = [c for c in counts if c is not None]
    if not heights or not counts:
        return None
    return sum(counts) / heights


# -- dispatches on the device's clock ---------------------------------------

# how far off the shared clock may be when it pairs a kernel's device
# event with the dispatch that launched it (dispatches are 50 ms apart
# or follow one another through the device's queue)
PAIR_SLACK_NS = 2_000_000


def dispatches(trace: Optional[dict], spans: list[dict]) -> list[dict]:
    """One entry per kernel device event of the traced window:
    ``{"start", "end"}`` on the profiler's clock and ``"span"``, the
    innermost ``kernel_execute`` span (placed on that clock by
    profile.clock_offset_ns) that holds the event whole, or None."""
    off = profile.clock_offset_ns(trace) if trace else None
    if off is None:
        return []
    execs = sorted(((ev["ts_ns"] + off, ev["ts_ns"] + ev["dur_ns"] + off,
                     ev) for ev in spans
                    if ev["name"] == "kernel_execute"),
                   key=lambda t: t[0])
    out = []
    for _, start, dur in profile.kernel_events(trace):
        end = start + dur
        holding = [(e - s, ev) for s, e, ev in execs
                   if s <= start and end <= e]
        out.append({"start": start, "end": end,
                    "span": min(holding, key=lambda t: t[0])[1]
                    if holding else None})
    return out


def containment_share(trace: Optional[dict],
                      spans: list[dict]) -> Optional[float]:
    """100 x kernel device events lying wholly inside a
    ``kernel_execute`` span / all of them: the check of the shared
    clock."""
    found = dispatches(trace, spans)
    if not found:
        return None
    return 100.0 * sum(1 for d in found if d["span"] is not None) \
        / len(found)


def dispatch_overheads_us(trace: Optional[dict], spans: list[dict],
                          slack_ns: int = PAIR_SLACK_NS
                          ) -> list[float]:
    """Per dispatch, in us: from the start of its ``launch`` span to
    the end of its ``device_wait`` span (both on the host's clock, so
    their distance needs no shared clock), less the time its kernel
    ran on the device.  The shared clock only pairs a device event
    with its dispatch, and with ``slack_ns`` of room: it places the
    device's events against the host's up to a millisecond differently
    from run to run, which is why the two halves of this quantity (the
    gap before the kernel, the gap after it) are not read."""
    off = profile.clock_offset_ns(trace) if trace else None
    if off is None:
        return []
    kids = children(spans)
    calls = []      # (launch start, device_wait end) placed, host ns
    for ev in spans:
        if ev["name"] != "kernel_execute" or not ev.get("id"):
            continue
        legs = {k["name"]: k for k in kids.get(ev["id"], ())}
        if "launch" in legs and "device_wait" in legs:
            lo = legs["launch"]["ts_ns"]
            hi = interval(legs["device_wait"])[1]
            calls.append((lo + off, hi + off, hi - lo))
    calls.sort(key=lambda c: c[1])
    ran: dict[int, list[tuple[float, float]]] = {}
    for _, start, dur in profile.kernel_events(trace):
        # the first dispatch, by the end of its wait, that the kernel
        # could have ended in and did not begin before
        for i, (lo, hi, _) in enumerate(calls):
            if start + dur <= hi + slack_ns:
                if start >= lo - slack_ns:
                    ran.setdefault(i, []).append((start, start + dur))
                break
    return [(calls[i][2] - profile.union_ns(ivs)) / 1e3
            for i, ivs in sorted(ran.items())]
