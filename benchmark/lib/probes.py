"""What the benchmark reads from the program: spans from the flight
recorder (libs/tracing) and metric families in Prometheus text form
(libs/metrics render()), as snapshots and deltas over a window.

Both are the program's public surfaces: the recorder's snapshot() and
the /metrics exposition.  Nothing here reaches into a private field.
"""
from __future__ import annotations

import re
from typing import Iterable, Optional

from . import stats

_SAMPLE = re.compile(
    r'^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*?)\})?\s+(\S+)')
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')

Key = tuple[str, tuple[tuple[str, str], ...]]


def parse_exposition(text: str) -> dict[Key, float]:
    """Prometheus text exposition -> {(name, sorted labels): value}."""
    out: dict[Key, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if m is None:
            continue
        name, labels, value = m.groups()
        try:
            v = float(value)
        except ValueError:
            continue
        out[(name, tuple(sorted(_LABEL.findall(labels or ""))))] = v
    return out


def metrics_snapshot(*registries) -> dict[Key, float]:
    snap: dict[Key, float] = {}
    for reg in registries:
        snap.update(parse_exposition(reg.render()))
    return snap


def metrics_delta(before: dict[Key, float], after: dict[Key, float]
                  ) -> dict[Key, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def select(delta: dict[Key, float], name: str, **labels: str
           ) -> dict[Key, float]:
    """Samples of one series name whose labels include ``labels``."""
    want = set(labels.items())
    return {k: v for k, v in delta.items()
            if k[0] == name and want <= set(k[1])}


def total(delta: dict[Key, float], name: str, **labels: str) -> float:
    return sum(select(delta, name, **labels).values())


def hist_mean_ms(delta: dict[Key, float], family: str, **labels: str
                 ) -> Optional[float]:
    """sum/count of a histogram family over the delta, in ms."""
    n = total(delta, family + "_count", **labels)
    if n <= 0:
        return None
    return total(delta, family + "_sum", **labels) / n * 1e3


def hist_quantile_ms(delta: dict[Key, float], family: str, q: float,
                     **labels: str) -> Optional[float]:
    """A quantile (0..1) of a histogram family over the delta, from
    its cumulative buckets, summed across matching label sets."""
    by_le: dict[float, float] = {}
    for (name, lab), v in select(delta, family + "_bucket",
                                 **labels).items():
        le = dict(lab).get("le")
        if le is None or le == "+Inf":
            continue
        by_le[float(le)] = by_le.get(float(le), 0.0) + v
    count = total(delta, family + "_count", **labels)
    bounds = sorted(by_le)
    val = stats.histogram_quantile(
        bounds, [by_le[b] for b in bounds], count, q)
    return None if val is None else val * 1e3


# -- spans -----------------------------------------------------------------

def spans_between(events: Iterable[dict], t0_ns: int, t1_ns: int,
                  name: Optional[str] = None) -> list[dict]:
    """Events that START inside [t0, t1)."""
    return [ev for ev in events
            if t0_ns <= ev["ts_ns"] < t1_ns
            and (name is None or ev["name"] == name)]


def attr(ev: dict, key: str, default=None):
    return (ev.get("attrs") or {}).get(key, default)


def median_span_ms(events: Iterable[dict], name: str,
                   warm_only: bool = False) -> Optional[float]:
    durs = [ev["dur_ns"] / 1e6 for ev in events
            if ev["name"] == name
            and (not warm_only or attr(ev, "warm"))]
    return stats.median(durs)


def is_shape_setup(ev: dict) -> bool:
    """A span that set a kernel shape up: a warm-up compile, or a
    dispatch that found its shape cold."""
    return ev["name"] == "kernel_compile" or (
        ev["name"] == "kernel_execute" and not attr(ev, "warm"))
