#!/usr/bin/env python3
"""Render a per-height latency breakdown from a flight-record dump.

Input: a JSON dump written by the flight recorder
(cometbft_tpu/libs/tracing.py) — the /trace RPC body, a
``/debug/pprof/trace?dump=1`` file, a supervisor give-up dump, or a
nemesis safety-violation dump.  Output: one row per height attributing
the height's wall-clock to gossip / verify / execute / commit, plus
the batch-verify dispatches observed.

    python tools/trace_report.py flight-<pid>-001-*.json [--height H]

Attribution rules
-----------------
Height *windows* come from consensus events (they carry a height);
events recorded without a height (crypto kernel dispatches, abci
calls, p2p frames) are attributed to the window their monotonic
timestamp falls into.  Buckets:

  * gossip   — window start → ``proposal_complete`` (the time spent
               collecting the proposal over p2p), falling back to the
               ``step:Propose`` span;
  * verify   — crypto ``batch_verify``/``kernel_execute``/``host_prep``
               spans plus the executor's ``validate_block``;
  * execute  — abci call spans, ``<conn>/<method>`` (the app's share);
  * commit   — the block store's ``store_save_block`` plus the
               ``step:Commit`` span (fsync + finalize path);
  * pipeline — the executor's ``apply_block`` + ``barrier_wait``: the
               pipelined
               execute/commit overlapping the NEXT height, and the
               barrier stalls when it didn't finish in time.  Reported
               separately because pipelined work off the critical path
               must not be read as height wall-clock.

The ``runtime`` column is the interpreter's own time inside the
height's window: ms of garbage collection (``gc``: the ``gc_us`` of
the height's outermost spans that note it, every collection counted),
of which recorded as ``gc_pause`` spans by generation (``g0/g1/g2``: a
generation-0 collection under 1 ms is not recorded), and the ms of
``commit_release`` (``rel``: a commit's verifier and entries freed).

Marker instants (compact-block relay, aggregate-commit catchup, vote
and part arrivals) are counted per height in the ``markers`` column —
they carry no duration, but their counts tell the protocol story
(e.g. ``compact_block_miss`` > 0 means the reconstruct fast path fell
back to full parts).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

_MS = 1e6  # ns per ms

# crypto span names that count as "verify" work
_VERIFY_NAMES = {"batch_verify", "kernel_execute", "host_prep",
                 "kernel_compile"}

# consensus span -> bucket (tests/test_observability_drift.py pins
# this table against the names the instrumented modules actually
# emit; "step:*" spans are matched by prefix)
CONSENSUS_SPAN_BUCKETS = {
    "step:Commit": "commit",
    "barrier_wait": "pipeline",
    # around the crypto spans the verify bucket already sums: known
    # names, added to no bucket
    "commit_verify": None,
    "commit_walk": None,
    # read by name into the runtime column
    "commit_release": None,
    # a burst and a WAL playback (consensus/state.py, replay.py):
    # frames around the step, state and crypto spans already summed
    "vote_preverify": None,
    "vote_tally": None,
    "finalize_commit": None,
    "wal_replay": None,
    "replay_height": None,
    "wal_read": None,
    # a precommit's extension block inside the tally: its
    # VerifyVoteExtension call is in the execute column
    "vote_extension": None,
}

# state span -> bucket (pinned the same way): the executor and the
# block store own the spans of what consensus and blocksync call
STATE_SPAN_BUCKETS = {
    "validate_block": "verify",
    "store_save_block": "commit",
    "apply_block": "pipeline",
    # the steps inside apply_block
    "save_finalize_response": None,
    "update_state": None,
    "app_commit": None,
    "state_save": None,
    "fire_events": None,
}

# runtime span -> what the runtime column does with it (pinned against
# what libs/tracing.py's collection hook records)
RUNTIME_SPANS = frozenset({"gc_pause"})

# consensus instants counted per height (zero-duration markers)
CONSENSUS_MARKERS = frozenset({
    "proposal_recv", "proposal_received", "proposal_complete",
    "proposal_broadcast", "block_part_recv", "vote_recv",
    "compact_block_recv", "compact_block_rebuilt",
    "compact_block_miss", "compact_block_nack",
    "agg_commit_recv", "agg_commit_shed", "pipeline_advance",
    "commit",
})


def _to_int(v) -> int:
    try:
        return int(v)
    except (TypeError, ValueError):
        return 0


def _events(record: dict) -> list[dict]:
    evs = record.get("events", record if isinstance(record, list)
                     else [])
    out = []
    for e in evs:
        out.append({
            "ts_ns": _to_int(e.get("ts_ns")),
            "dur_ns": _to_int(e.get("dur_ns")),
            "category": e.get("category", ""),
            "name": e.get("name", ""),
            "height": _to_int(e.get("height")),
            "attrs": e.get("attrs") or {},
            "id": _to_int(e.get("id")),
            "parent": _to_int(e.get("parent")),
        })
    out.sort(key=lambda e: e["ts_ns"])
    return out


def _height_windows(events: list[dict]) -> dict[int, tuple[int, int]]:
    """height -> (first_ts, last_ts+dur) from height-stamped events."""
    win: dict[int, tuple[int, int]] = {}
    for e in events:
        h = e["height"]
        if h <= 0:
            continue
        end = e["ts_ns"] + e["dur_ns"]
        lo, hi = win.get(h, (e["ts_ns"], end))
        win[h] = (min(lo, e["ts_ns"]), max(hi, end))
    return win


def _attribute(events: list[dict],
               windows: dict[int, tuple[int, int]]) -> None:
    """Stamp height-less events with the height whose window contains
    their timestamp (in place)."""
    ordered = sorted(windows.items())
    for e in events:
        if e["height"] > 0:
            continue
        ts = e["ts_ns"]
        for h, (lo, hi) in ordered:
            if lo <= ts <= hi:
                e["height"] = h
                break


def _outermost_gc_us(events: list[dict]) -> dict[int, int]:
    """height -> ``gc_us`` summed over the spans that note it and lie
    under no other span that does (a sync_height's holds its
    commit_verifys')."""
    by_id = {e["id"]: e for e in events if e["id"]}
    out: dict[int, int] = {}
    for e in events:
        if "gc_us" not in e["attrs"]:
            continue
        up = by_id.get(e["parent"])
        while up is not None and "gc_us" not in up["attrs"]:
            up = by_id.get(up["parent"])
        if up is None:
            out[e["height"]] = out.get(e["height"], 0) + \
                _to_int(e["attrs"]["gc_us"])
    return out


def analyze(record: dict,
            height: Optional[int] = None) -> dict[int, dict]:
    """Per-height breakdown (values in ms) keyed by height."""
    events = _events(record)
    windows = _height_windows(events)
    _attribute(events, windows)
    gc_us = _outermost_gc_us(events)
    out: dict[int, dict] = {}
    for h, (lo, hi) in sorted(windows.items()):
        if height is not None and h != height:
            continue
        row = {"wall_ms": (hi - lo) / _MS, "gossip_ms": 0.0,
               "verify_ms": 0.0, "execute_ms": 0.0, "commit_ms": 0.0,
               "pipeline_ms": 0.0,
               "p2p_events": 0, "p2p_bytes": 0, "stalls": 0,
               "gc_ms": gc_us.get(h, 0) / 1e3,
               "gc_pause_ms": [0.0, 0.0, 0.0], "release_ms": 0.0,
               "markers": {}, "batches": []}
        propose_span = 0.0
        proposal_complete_ts = None
        for e in events:
            if e["height"] != h:
                continue
            cat, name, dur = e["category"], e["name"], e["dur_ns"]
            if cat == "crypto" and name in _VERIFY_NAMES:
                row["verify_ms"] += dur / _MS
                if name in ("batch_verify", "kernel_execute"):
                    a = e["attrs"]
                    row["batches"].append({
                        "name": name,
                        "batch": a.get("batch"),
                        "backend": a.get("backend",
                                         a.get("kernel", "?")),
                        "bucket": a.get("bucket"),
                        "ms": dur / _MS})
            elif cat == "abci" and "/" in name:
                # the calls, "<conn>/<method>"; state_root nests
                # inside consensus/finalize_block, oracle_validate
                # inside consensus/process_proposal
                row["execute_ms"] += dur / _MS
            elif cat == "state":
                bucket = STATE_SPAN_BUCKETS.get(name)
                if bucket is not None:
                    row[bucket + "_ms"] += dur / _MS
            elif cat == "p2p":
                row["p2p_events"] += 1
                row["p2p_bytes"] += _to_int(
                    e["attrs"].get("bytes", 0))
                if name.endswith(("_full", "_stall")):
                    row["stalls"] += 1
            elif cat == "runtime" and name in RUNTIME_SPANS:
                gen = _to_int(e["attrs"].get("generation"))
                row["gc_pause_ms"][min(max(gen, 0), 2)] += dur / _MS
            elif cat == "consensus":
                if name == "commit_release":
                    row["release_ms"] += dur / _MS
                bucket = CONSENSUS_SPAN_BUCKETS.get(name)
                if bucket is not None:
                    row[bucket + "_ms"] += dur / _MS
                elif name in CONSENSUS_MARKERS:
                    row["markers"][name] = \
                        row["markers"].get(name, 0) + 1
                if name == "step:Propose":
                    propose_span = dur / _MS
                elif name == "proposal_complete":
                    proposal_complete_ts = e["ts_ns"]
        row["gossip_ms"] = ((proposal_complete_ts - lo) / _MS
                            if proposal_complete_ts is not None
                            else propose_span)
        out[h] = row
    return out


def _runtime_cell(r: dict) -> str:
    return (f"gc {r['gc_ms']:.1f} (" +
            "/".join(f"{ms:.1f}" for ms in r["gc_pause_ms"]) +
            f") rel {r['release_ms']:.2f}")


def render_report(record: dict,
                  height: Optional[int] = None) -> str:
    rows = analyze(record, height=height)
    lines = []
    reason = record.get("reason")
    if reason:
        lines.append(f"flight record: {reason} "
                     f"({record.get('wall_time', '?')})")
    extra = record.get("extra") or {}
    if extra.get("conflicting_heights"):
        lines.append("conflicting-commit heights: "
                     f"{extra['conflicting_heights']}")
    if not rows:
        lines.append("no height-stamped events in this record")
        return "\n".join(lines) + "\n"
    hdr = (f"{'height':>7} {'wall_ms':>9} {'gossip_ms':>10} "
           f"{'verify_ms':>10} {'execute_ms':>11} {'commit_ms':>10} "
           f"{'pipe_ms':>8} {'p2p ev':>7} {'stalls':>7}  runtime")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for h, r in rows.items():
        lines.append(
            f"{h:>7} {r['wall_ms']:>9.2f} {r['gossip_ms']:>10.2f} "
            f"{r['verify_ms']:>10.2f} {r['execute_ms']:>11.2f} "
            f"{r['commit_ms']:>10.2f} {r['pipeline_ms']:>8.2f} "
            f"{r['p2p_events']:>7} {r['stalls']:>7}  "
            f"{_runtime_cell(r)}")
    for h, r in rows.items():
        if r["markers"]:
            mk = " ".join(f"{k}={v}" for k, v in
                          sorted(r["markers"].items()))
            lines.append(f"        h{h} markers: {mk}")
        for b in r["batches"]:
            lines.append(
                f"        h{h} {b['name']}: batch={b['batch']} "
                f"backend={b['backend']} bucket={b['bucket']} "
                f"{b['ms']:.2f}ms")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Per-height latency breakdown from a flight-"
                    "record dump")
    p.add_argument("dump", help="flight-record JSON file")
    p.add_argument("--height", type=int, default=None,
                   help="restrict to one height")
    p.add_argument("--json", action="store_true",
                   help="JSON instead of text")
    args = p.parse_args(argv)
    with open(args.dump) as f:
        record = json.load(f)
    if args.json:
        json.dump(analyze(record, height=args.height), sys.stdout,
                  indent=2)
        sys.stdout.write("\n")
    else:
        sys.stdout.write(render_report(record, height=args.height))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
