"""From a jax.profiler trace to device metrics.

capture:  a Capture wraps start_trace/stop_trace around a sub-window
          of a few seconds and drops anchor annotations whose host
          monotonic time is known, so the program's spans (libs/tracing,
          monotonic clock) can be put on the profiler's clock.
reduce:   read_xplane() turns the .xplane.pb into a small JSON-able
          dict — per device the op and module events as
          [name, start_ns, dur_ns] on the profiler's clock, plus the
          anchors — which is also the form of the recorded fixture.
metrics:  pure functions of that dict: busy/idle union, the kernel's
          events by name, the longest idle gaps and what the host was
          doing in each.

Every PR computes these numbers here, in the same way; no PR that
claims a gain can change this file.
"""
from __future__ import annotations

import glob
import os
import time
from typing import Iterable, Optional

ANCHOR = "bench_anchor"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNATTRIBUTED = "host-unattributed"
# every jitted function that IS the verification kernel carries this in
# its name (ops/ed25519_jax._pallas_verify_packed, _verify_packed, the
# shard_map'ed sharded_<kernel>_verify)
KERNEL_MARK = "verify"


class Capture:
    """One profiler session.  start() and stop() block (stop writes the
    trace); call them off the event loop."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.anchors_mono_ns: list[int] = []
        self.started = False

    def _anchor(self) -> None:
        import jax
        self.anchors_mono_ns.append(time.monotonic_ns())
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no per-call Python events
        opts.host_tracer_level = 1      # TraceAnnotations only
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.started = True
        self._anchor()

    def stop(self) -> None:
        import jax
        if not self.started:
            return
        self._anchor()
        jax.profiler.stop_trace()
        self.started = False


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO line
    (``%fusion.3 = s32[...] fusion(...)``): keep the op's own name."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def find_xplane(out_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str, anchors_mono_ns: Iterable[int] = ()) -> dict:
    """Reduce an .xplane.pb to the dict the metric functions read."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices = []
    anchors_profile: list[float] = []
    lines_seen: dict[str, list[str]] = {}
    for plane in data.planes:
        names = [ln.name for ln in plane.lines]
        lines_seen[plane.name] = names
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    target = dev["ops"]
                elif ln.name == MODULES_LINE:
                    target = dev["modules"]
                else:
                    continue
                for ev in ln.events:
                    target.append([short_name(ev.name),
                                   float(ev.start_ns),
                                   float(ev.duration_ns)])
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == ANCHOR:
                        anchors_profile.append(float(ev.start_ns))
    anchors_profile.sort()
    mono = sorted(anchors_mono_ns)
    anchors = [[m, p] for m, p in zip(mono, anchors_profile)] \
        if len(mono) == len(anchors_profile) else []
    return {"devices": devices, "anchors": anchors,
            "lines": lines_seen}


# -- pure functions of the reduced trace ----------------------------------

def union_ns(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_ns(trace: dict) -> Optional[tuple[float, float]]:
    """The traced window on the profiler's clock: first anchor to last
    (start() and stop() each drop one)."""
    a = trace.get("anchors") or []
    if len(a) >= 2:
        return a[0][1], a[-1][1]
    evs = [ev for d in trace.get("devices", ()) for ev in d["ops"]]
    if not evs:
        return None
    return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))


def _clipped(ops, lo: float, hi: float):
    for _, s, d in ops:
        s2, e2 = max(s, lo), min(s + d, hi)
        if e2 > s2:
            yield s2, e2


def busy(trace: dict) -> Optional[dict]:
    """Seconds an operation ran on the device inside the traced
    window, averaged over the devices that ran any, and the window's
    length.  None when the trace holds no device operation."""
    win = window_ns(trace)
    devs = [d for d in trace.get("devices", ()) if d["ops"]]
    if win is None or not devs:
        return None
    lo, hi = win
    per_dev = [union_ns(_clipped(d["ops"], lo, hi)) for d in devs]
    return {"busy_s": sum(per_dev) / len(per_dev) / 1e9,
            "window_s": (hi - lo) / 1e9, "devices": len(devs)}


def idle_share(trace: dict) -> Optional[float]:
    b = busy(trace)
    if b is None or b["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def kernel_events(trace: dict, mark: str = KERNEL_MARK) -> list:
    """[name, start_ns, dur_ns] of the kernel's device events: module
    executions whose name carries the mark, else ops that do."""
    win = window_ns(trace)
    out = []
    for d in trace.get("devices", ()):
        evs = [e for e in d["modules"] if mark in e[0]] or \
              [e for e in d["ops"] if mark in e[0]]
        out.extend(e for e in evs
                   if win is None or win[0] <= e[1] < win[1])
    return out


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds], ...]: device operations by total time."""
    by: dict[str, float] = {}
    for d in trace.get("devices", ()):
        for name, _, dur in d["ops"]:
            by[name] = by.get(name, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(by.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict) -> list[tuple[float, float]]:
    """(start_ns, end_ns) of every stretch of the traced window in
    which no device ran an operation."""
    win = window_ns(trace)
    if win is None:
        return []
    lo, hi = win
    ivs = sorted(iv for d in trace.get("devices", ())
                 for iv in _clipped(d["ops"], lo, hi))
    gaps, cursor = [], lo
    for s, e in ivs:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def clock_offset_ns(trace: dict) -> Optional[float]:
    """profiler_ns - monotonic_ns, the median over the anchors."""
    a = trace.get("anchors") or []
    if not a:
        return None
    offs = sorted(p - m for m, p in a)
    return offs[len(offs) // 2]


def attribute_gaps(trace: dict, spans: Iterable[dict],
                   n: int = 10) -> list:
    """[[name, seconds], ...]: idle time by what the host was doing.
    Each idle gap is cut at the boundaries of the spans that overlap
    it, and each piece goes to the innermost span covering it (the
    shortest one), else to host-unattributed; the seconds of one name
    add up.  ``spans`` are events on the monotonic clock: the
    program's (libs/tracing) and the driver's own."""
    off = clock_offset_ns(trace)
    placed = []
    if off is not None:
        placed = sorted(
            ((ev["ts_ns"] + off, ev["ts_ns"] + ev["dur_ns"] + off,
              ev["name"]) for ev in spans if ev["dur_ns"] > 0),
            key=lambda t: t[0])
    by: dict[str, float] = {}
    for gs, ge in idle_gaps(trace):
        over = []
        for ss, se, name in placed:
            if ss >= ge:
                break
            if se > gs:
                over.append((max(ss, gs), min(se, ge), se - ss, name))
        cuts = sorted({gs, ge} | {c for o in over for c in o[:2]})
        for a, b in zip(cuts, cuts[1:]):
            inner = min((o for o in over if o[0] <= a and o[1] >= b),
                        key=lambda o: o[2], default=None)
            name = inner[3] if inner is not None else UNATTRIBUTED
            by[name] = by.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(by.items(),
                                      key=lambda kv: -kv[1])[:n]]
