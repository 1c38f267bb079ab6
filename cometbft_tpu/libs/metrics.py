"""Prometheus-style metrics: registry + text exposition (metrics v2).

Reference: libs/metrics (go-kit metrics with a Prometheus provider) and
the per-package metrics.go files (internal/consensus/metrics.go:190,
mempool, p2p, state, blocksync, statesync, proxy).  Served at /metrics
by the instrumentation listener (node/node.go prometheusSrv).

v2 additions (the "metrics v2 + perf lab" layer):
  * Prometheus-text-format-correct exposition — label values and HELP
    text are escaped per the exposition format spec, so a peer moniker
    containing a quote or newline cannot break a scrape;
  * histogram trace exemplars — every bucket remembers its most recent
    observation together with the flight-recorder height in progress
    (libs/tracing.py ``current_height``), so a p99 outlier in a scrape
    links straight to ``/trace?height=H``.  Exemplars ride the
    OpenMetrics ``# {...}`` syntax and are OFF in the default render
    (plain text-format scrapers reject them) — pass ``exemplars=True``
    (``GET /metrics?exemplars=1``);
  * bounded label cardinality — a metric family never materializes
    more than ``max_children`` label sets; excess label values (e.g.
    peer-controlled ids under churn) collapse into one ``overflow``
    series instead of growing the registry without bound;
  * ``Registry.collect()`` — machine-readable family descriptors
    (name, kind, help, labels, live series) feeding the generated
    metrics catalog in docs/observability.md and the tier-1
    cardinality/help guard;
  * ``render_merged()`` — one exposition page over several registries
    (the node registry + the process-global DEFAULT that the crypto
    layer's backend-dispatch histograms live on).
"""
from __future__ import annotations

import resource
import threading
import time
from typing import Optional, Sequence

from . import tracing


def _escape_label_value(v: str) -> str:
    """Exposition-format label escaping: backslash, double-quote and
    newline (in that order — escaping the escape char first)."""
    return v.replace("\\", "\\\\").replace('"', '\\"') \
            .replace("\n", "\\n")


def _escape_help(h: str) -> str:
    """HELP lines escape backslash and newline only."""
    return h.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_labels(names: Sequence[str], values: Sequence[str]) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{_escape_label_value(v)}"'
                     for n, v in zip(names, values))
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def _fmt_exemplar(ex) -> str:
    """OpenMetrics exemplar: ``# {labels} value timestamp``."""
    value, ts, labels = ex
    inner = ",".join(f'{k}="{_escape_label_value(str(v))}"'
                     for k, v in labels.items())
    return f" # {{{inner}}} {_fmt_value(value)} {ts:.3f}"


_MEMO_MAX = 1024
# Hard ceiling on label sets per family: beyond this, new label values
# collapse into one "overflow" series.  Peer-controlled label values
# (peer ids under churn, lane names from a byzantine app) therefore
# cannot grow a family without bound — the tier-1 cardinality guard
# (tests/test_metrics_contract.py) locks this invariant.
_CHILDREN_MAX = 2048
_OVERFLOW = "overflow"


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str,
                 label_names: Sequence[str] = ()):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self.max_children = _CHILDREN_MAX
        self._children: dict[tuple, "_Metric"] = {}
        self._memo: dict[tuple, "_Metric"] = {}
        self._lock = threading.Lock()

    def with_labels(self, *values: str):
        # hot path: with_labels runs per gossip message in the p2p
        # send/recv routines — the raw-tuple memo skips the per-call
        # str() normalization and lock (dict reads are GIL-atomic;
        # writes happen only under the lock below).  Only all-str
        # tuples are memoized: that is the actual hot-path shape, and
        # it keeps equal-but-differently-typed values (1 vs "1") from
        # creating duplicate memo entries for one child; the memo is
        # FIFO-bounded like the vote memos so peer-controlled label
        # values cannot grow it without bound.
        try:
            child = self._memo.get(values)
        except TypeError:           # unhashable label value
            child, memoizable = None, False
        else:
            memoizable = all(type(v) is str for v in values)
        if child is not None:
            return child
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected {len(self.label_names)} label "
                f"values, got {len(values)}")
        key = tuple(str(v) for v in values)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                if len(self._children) >= self.max_children:
                    # cardinality ceiling: collapse into the shared
                    # overflow series rather than growing unboundedly
                    key = tuple(_OVERFLOW
                                for _ in self.label_names)
                    child = self._children.get(key)
                if child is None:
                    child = self._new_child(key)
                    self._children[key] = child
            if memoizable:
                if len(self._memo) >= _MEMO_MAX:
                    self._memo.pop(next(iter(self._memo)))
                self._memo[values] = child
            return child

    def _new_child(self, key: tuple):  # pragma: no cover - abstract
        raise NotImplementedError

    def _samples(self):  # -> list[(suffix, labels, value, exemplar)]
        raise NotImplementedError

    def series_count(self) -> int:
        return len(self._children) if self.label_names else 1

    def describe(self) -> dict:
        """Family descriptor for Registry.collect()."""
        return {"name": self.name, "kind": self.kind,
                "help": self.help, "labels": list(self.label_names),
                "series": self.series_count()}

    def render(self, exemplars: bool = False) -> str:
        lines = [f"# HELP {self.name} {_escape_help(self.help)}",
                 f"# TYPE {self.name} {self.kind}"]
        for suffix, labels, value, ex in self._samples():
            tail = _fmt_exemplar(ex) if exemplars and ex else ""
            lines.append(
                f"{self.name}{suffix}{labels} "
                f"{_fmt_value(value)}{tail}")
        return "\n".join(lines)


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name: str, help_: str,
                 label_names: Sequence[str] = ()):
        super().__init__(name, help_, label_names)
        self._value = 0.0

    def _new_child(self, key):
        return Counter(self.name, self.help)

    def add(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError("counters only go up")
        self._value += v

    inc = add

    @property
    def value(self) -> float:
        return self._value

    def _samples(self):
        if self.label_names:
            return [("", _fmt_labels(self.label_names, k), c._value,
                     None)
                    for k, c in sorted(self._children.items())]
        return [("", "", self._value, None)]

    def render(self, exemplars: bool = False) -> str:
        if not exemplars:
            return super().render()
        # OpenMetrics mode (the exemplar page): counter sample names
        # MUST carry the _total suffix and the family name drops it —
        # a conforming parser rejects the page otherwise
        family = self.name[:-len("_total")] \
            if self.name.endswith("_total") else self.name
        lines = [f"# HELP {family} {_escape_help(self.help)}",
                 f"# TYPE {family} counter"]
        for _suffix, labels, value, _ex in self._samples():
            lines.append(f"{family}_total{labels} "
                         f"{_fmt_value(value)}")
        return "\n".join(lines)


class CounterFunc(Counter):
    """A counter family READ at scrape time: ``read()`` returns
    {value of its one label: count}.  For counts their owner keeps as
    plain integers because a metric object a call would cost more than
    the work counted (wire/proto.py's codec: native / python /
    declined)."""

    def __init__(self, name: str, help_: str, label: str, read):
        super().__init__(name, help_, (label,))
        self._read = read

    def series_count(self) -> int:
        return len(self._read())

    def _samples(self):
        return [("", _fmt_labels(self.label_names, (k,)), float(v), None)
                for k, v in sorted(self._read().items())]


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name: str, help_: str,
                 label_names: Sequence[str] = ()):
        super().__init__(name, help_, label_names)
        self._value = 0.0

    def _new_child(self, key):
        return Gauge(self.name, self.help)

    def set(self, v: float) -> None:
        self._value = float(v)

    def add(self, v: float = 1.0) -> None:
        self._value += v

    def sub(self, v: float = 1.0) -> None:
        self._value -= v

    @property
    def value(self) -> float:
        return self._value

    def _samples(self):
        if self.label_names:
            return [("", _fmt_labels(self.label_names, k), g._value,
                     None)
                    for k, g in sorted(self._children.items())]
        return [("", "", self._value, None)]


_DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                    5.0, 10.0)


class Histogram(_Metric):
    """Prometheus-correct cumulative histogram.

    ``observe`` feeds ``_bucket``/``_sum``/``_count``; each bucket also
    remembers its latest observation as an OpenMetrics exemplar
    annotated with the flight-recorder height in progress, linking a
    scrape outlier to ``/trace?height=H``."""

    kind = "histogram"

    def __init__(self, name: str, help_: str,
                 label_names: Sequence[str] = (),
                 buckets: Sequence[float] = _DEFAULT_BUCKETS):
        super().__init__(name, help_, label_names)
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * len(self.buckets)
        self._sum = 0.0
        self._count = 0
        # per-bucket (value, unix_ts, labels) — index len(buckets) is
        # the +Inf bucket
        self._exemplars: dict[int, tuple] = {}

    def _new_child(self, key):
        return Histogram(self.name, self.help, buckets=self.buckets)

    def observe(self, v: float,
                exemplar: Optional[dict] = None) -> None:
        self._sum += v
        self._count += 1
        idx = len(self.buckets)        # +Inf unless a bucket matches
        for i, b in enumerate(self.buckets):
            if v <= b:
                self._counts[i] += 1
                if i < idx:
                    idx = i
        if exemplar is None:
            # trace exemplar: stamp the height the consensus machine
            # is working on so the observation links to /trace
            h = tracing.recorder().current_height
            if h:
                exemplar = {"trace_height": h}
        if exemplar:
            # exemplar timestamps are exposition metadata — OpenMetrics
            # requires wall clock — not interval arithmetic
            # bftlint: disable=monotonic-clock
            self._exemplars[idx] = (v, time.time(), exemplar)

    def quantile(self, q: float) -> float:
        """Estimate the q-quantile (0 < q <= 1) by linear
        interpolation over the cumulative bucket counts — the same
        estimate Prometheus' histogram_quantile() would give a
        scraper, computed in-process so ``/health`` can serve a p95
        without a metrics pipeline.  Returns 0.0 with no samples; the
        +Inf bucket clamps to the largest finite bound (observations
        past the last bucket are unbounded, so the estimate is a
        floor there, not a value)."""
        if self._count == 0:
            return 0.0
        rank = q * self._count
        prev_bound, prev_cum = 0.0, 0
        for i, b in enumerate(self.buckets):
            cum = self._counts[i]
            if cum >= rank:
                width = cum - prev_cum
                if width <= 0:
                    return b
                return prev_bound + (b - prev_bound) * \
                    (rank - prev_cum) / width
            prev_bound, prev_cum = b, cum
        return self.buckets[-1] if self.buckets else 0.0

    def _child_samples(self, labels_prefix: str):
        out = []
        for i, b in enumerate(self.buckets):
            c = self._counts[i]
            le = _fmt_value(b)
            if labels_prefix:
                lab = labels_prefix[:-1] + f',le="{le}"}}'
            else:
                lab = f'{{le="{le}"}}'
            out.append(("_bucket", lab, c, self._exemplars.get(i)))
        inf_lab = (labels_prefix[:-1] + ',le="+Inf"}') \
            if labels_prefix else '{le="+Inf"}'
        out.append(("_bucket", inf_lab, self._count,
                    self._exemplars.get(len(self.buckets))))
        out.append(("_sum", labels_prefix, self._sum, None))
        out.append(("_count", labels_prefix, self._count, None))
        return out

    def _samples(self):
        if self.label_names:
            out = []
            for k, h in sorted(self._children.items()):
                out.extend(h._child_samples(
                    _fmt_labels(self.label_names, k)))
            return out
        return self._child_samples("")


class Registry:
    def __init__(self, namespace: str = "cometbft"):
        self.namespace = namespace
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _register(self, m: _Metric) -> _Metric:
        with self._lock:
            if m.name in self._metrics:
                return self._metrics[m.name]
            self._metrics[m.name] = m
            return m

    def counter(self, subsystem: str, name: str, help_: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._register(Counter(
            f"{self.namespace}_{subsystem}_{name}", help_, labels))

    def counter_func(self, subsystem: str, name: str, help_: str,
                     label: str, read) -> CounterFunc:
        return self._register(CounterFunc(
            f"{self.namespace}_{subsystem}_{name}", help_, label, read))

    def gauge(self, subsystem: str, name: str, help_: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge(
            f"{self.namespace}_{subsystem}_{name}", help_, labels))

    def histogram(self, subsystem: str, name: str, help_: str = "",
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = _DEFAULT_BUCKETS
                  ) -> Histogram:
        return self._register(Histogram(
            f"{self.namespace}_{subsystem}_{name}", help_, labels,
            buckets))

    def collect(self) -> list[dict]:
        """Sorted family descriptors — the generated metrics catalog
        (docs/observability.md) and the tier-1 cardinality/help guard
        read the registry through this."""
        with self._lock:
            metrics = sorted(self._metrics.values(),
                             key=lambda m: m.name)
        return [m.describe() for m in metrics]

    def families(self) -> list[_Metric]:
        with self._lock:
            return sorted(self._metrics.values(),
                          key=lambda m: m.name)

    def render(self, exemplars: bool = False) -> str:
        return "\n".join(m.render(exemplars=exemplars)
                         for m in self.families()) + "\n"


def render_merged(*registries: Registry,
                  exemplars: bool = False) -> str:
    """One exposition page over several registries (node registry
    first, then e.g. the process-global DEFAULT).  A family name
    already emitted is skipped so the page never carries duplicate
    TYPE lines."""
    seen: set[str] = set()
    out: list[str] = []
    for reg in registries:
        if reg is None:
            continue
        for m in reg.families():
            if m.name in seen:
                continue
            seen.add(m.name)
            out.append(m.render(exemplars=exemplars))
    return "\n".join(out) + "\n"


# The process-global registry (reference: the Prometheus default
# registerer); nodes may also construct private registries in tests.
# The crypto layer's batch-verify histograms and the TPU-dispatch
# breaker state live here (they have no node context) — the node's
# /metrics endpoint merges this registry in via render_merged().
DEFAULT = Registry()


# The interpreter's own time, read when /metrics is scraped (upstream's
# go_gc_duration_seconds and process_cpu_seconds_total come from its
# client's default collectors): the recorder's collection hook keeps
# the totals (libs/tracing.py), the kernel the rest.  Nothing is
# observed on a hot path.
def _gc_totals(col: int, scale: float) -> dict:
    return {str(gen): t[col] * scale
            for gen, t in enumerate(tracing.gc_generation_totals())}


def _cpu_seconds() -> dict:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"user": ru.ru_utime, "system": ru.ru_stime}


def _context_switches() -> dict:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"voluntary": ru.ru_nvcsw, "involuntary": ru.ru_nivcsw}


DEFAULT.counter_func(
    "runtime", "gc_pause_seconds_total",
    "Seconds the interpreter spent in garbage collections, by the "
    "generation collected (2 = a full collection).", "generation",
    lambda: _gc_totals(0, 1e-9))
DEFAULT.counter_func(
    "runtime", "gc_collections_total",
    "Garbage collections, by the generation collected.", "generation",
    lambda: _gc_totals(1, 1))
DEFAULT.counter_func(
    "process", "cpu_seconds_total",
    "CPU time the process has used, in user and in system mode "
    "(getrusage): against wall time it says whether the host ran the "
    "process or kept it waiting.", "mode", _cpu_seconds)
DEFAULT.counter_func(
    "process", "context_switches_total",
    "Context switches of the process (getrusage): voluntary = it "
    "waited, involuntary = the host took the CPU away.", "kind",
    _context_switches)


class Timer:
    """Context manager observing elapsed seconds into a Histogram."""

    def __init__(self, hist: Histogram):
        self.hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.hist.observe(time.perf_counter() - self._t0)
        return False
