"""Flight recorder: node-wide span tracing with crash-dump timelines.

Always-on, near-zero-overhead attribution of where a height's
wall-clock goes.  Monotonic-clock spans and instant events are written
to fixed-size per-category ring buffers (the flight recorder) — no
I/O, no allocation beyond one tuple per event, bounded memory.  The
committee-based-consensus measurement line of work (PAPERS.md) showed
per-step latency attribution is what separates signature cost from
gossip/tally cost; this module bakes that attribution into the node so
every later perf PR is judged against the same timeline.

Readers:
  * the ``/trace`` JSON-RPC endpoint (rpc/core.py) — live timeline,
    filterable by height/category;
  * ``/debug/pprof/trace`` on the pprof listener (libs/pprof.py);
  * automatic crash dumps: the supervisor give-up path and the nemesis
    safety-assertion failure both call :func:`dump`, leaving a JSON
    flight record next to the node's data (the black box);
  * ``tools/trace_report.py`` — per-height gossip/verify/execute/commit
    breakdown rendered from a dump.

The interpreter's own time (category ``runtime``): one ``gc.callbacks``
hook keeps the process's garbage-collection pause totals and records a
``gc_pause`` span under the span the collection struck; a span opened
with ``runtime=True`` notes the pauses that fell inside it (``gc_us``).

Disabled mode compiles to a no-op: ``span()`` returns a shared inert
context manager and ``instant()`` returns immediately — the benchmark
guard in tests/test_tracing.py holds the disabled path under 1µs per
call.  Category enables and the ring size come from
``instrumentation.trace_*`` (config.py), wired by the node.

Events are tuples ``(ts_ns, dur_ns, name, height, attrs, id, parent,
tid)`` on a ``deque(maxlen=size)`` per category;
``time.monotonic_ns()`` is the only clock, so timelines are immune to
wall-clock steps and strictly ordered within a process.

Causality: every event has a process-unique ``id``; a span opened
while another is open in the same context (a ``contextvars``
variable, so it follows ``await`` and ``asyncio.to_thread``) records
that span's id as its ``parent`` and, when it names no height,
inherits the parent's — the height is the request identifier.  A
would-be parent that has already closed (a long-lived task created
inside a span) is no parent.  ``tid`` is the recording thread.  Self
time of a span is its duration minus what its children cover.

Clock anchors: monotonic timestamps are process-local, so two nodes'
timelines cannot be compared directly.  The recorder keeps a bounded
list of periodically refreshed ``(monotonic_ns, wall_ns)`` anchor
pairs — sampled together, refreshed passively whenever an event is
recorded past the anchor interval — exposed in every dump and at the
``/trace`` RPC.  ``tools/fleet_report.py`` fits offset + drift from
the pairs and merges N nodes' dumps onto one wall timeline (the
cluster critical path the committee-consensus measurement papers
decompose).  Wall time is never used for interval arithmetic here;
anchors are alignment metadata, the same boundary class as the pex
addrbook save/load conversion.
"""
from __future__ import annotations

import contextvars
import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

# canonical categories (free-form strings are accepted; these are the
# ones the node emits and the report understands)
CONSENSUS = "consensus"
CRYPTO = "crypto"
P2P = "p2p"
MEMPOOL = "mempool"
ABCI = "abci"
BLOCKSYNC = "blocksync"
STATE = "state"
SUPERVISOR = "supervisor"
NEMESIS = "nemesis"
LIGHT = "light"
RUNTIME = "runtime"

CATEGORIES = (CONSENSUS, CRYPTO, P2P, MEMPOOL, ABCI, BLOCKSYNC, STATE,
              SUPERVISOR, NEMESIS, LIGHT, RUNTIME)

now_ns = time.monotonic_ns
_get_ident = threading.get_ident

# process-unique event ids (next() on a count is atomic under the GIL)
_IDS = itertools.count(1)
# the innermost span open in this context (task or thread)
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "cometbft_tpu_span", default=None)


def _causal(height: int) -> tuple[int, int]:
    """(parent id, height) for an event recorded now: the open span
    of this context is its parent and lends its height."""
    p = _CURRENT.get()
    if p is None or p.closed:
        return 0, height
    return p.id, height or p.height


class Recorder:
    """Per-category ring buffers + dump machinery.

    The module-global instance behind :func:`span`/:func:`instant` is
    what the node wires; tests may construct private recorders."""

    #: bound on the anchor list; old middle anchors are evicted but the
    #: very first is kept so drift fits retain the longest baseline
    ANCHORS_MAX = 64

    def __init__(self, buffer_size: int = 4096, enabled: bool = True,
                 categories: Optional[str] = None,
                 dump_dir: str = "", node_id: str = "",
                 anchor_interval_s: float = 30.0):
        self.buffer_size = max(1, int(buffer_size))
        self.enabled = enabled
        # None = every category; else the enabled set
        self.categories: Optional[frozenset] = (
            frozenset(c.strip() for c in categories.split(",")
                      if c.strip())
            if isinstance(categories, str) and categories.strip()
            else (frozenset(categories) if categories else None))
        self.dump_dir = dump_dir
        self.node_id = node_id
        self.last_dump_path = ""
        # (monotonic_ns, wall_ns) pairs for cross-node alignment; the
        # first is taken here so even a dump written in the first
        # interval carries one.  time.time_ns is sampled ONLY to pair
        # with a monotonic reading — never for interval arithmetic.
        self.anchor_interval_ns = max(1, int(anchor_interval_s * 1e9))
        self.anchors: list[tuple[int, int]] = []
        self._next_anchor_ns = 0
        self.refresh_anchor(force=True)
        # best-effort height context: the consensus step machine
        # stamps the height in progress, and events recorded without
        # an explicit height (crypto dispatches, p2p frames, abci
        # calls) inherit it — that is what makes "/trace?height=H" a
        # complete per-height timeline rather than consensus-only
        self.current_height = 0
        self._rings: dict[str, deque] = {}
        self._dump_seq = 0
        # reentrant: a garbage collection can strike while the lock is
        # held (a ring allocated, the rings freed by clear()) and the
        # collector's hook records a gc_pause into a ring it may have
        # to create, on the same thread
        self._lock = threading.RLock()

    # -- hot path ----------------------------------------------------
    def enabled_for(self, category: str) -> bool:
        return self.enabled and (self.categories is None or
                                 category in self.categories)

    def _ring(self, category: str) -> deque:
        ring = self._rings.get(category)
        if ring is None:
            # rare path; the lock only guards ring creation — appends
            # ride the GIL (deque.append is atomic)
            with self._lock:
                ring = self._rings.get(category)
                if ring is None:
                    ring = deque(maxlen=self.buffer_size)
                    self._rings[category] = ring
        return ring

    def record(self, category: str, name: str, start_ns: int,
               end_ns: int, height: int, attrs: Optional[dict],
               span_id: int = 0, parent: int = 0) -> None:
        self._ring(category).append(
            (start_ns, end_ns - start_ns, name,
             height or self.current_height, attrs,
             span_id or next(_IDS), parent, _get_ident()))
        if end_ns >= self._next_anchor_ns:
            self.refresh_anchor()

    def record_instant(self, category: str, name: str, height: int,
                       attrs: Optional[dict], parent: int = 0) -> None:
        ts = now_ns()
        self.record(category, name, ts, ts, height, attrs,
                    parent=parent)

    def refresh_anchor(self, force: bool = False) -> None:
        """Sample a fresh (monotonic_ns, wall_ns) pair.  Driven
        passively from the record paths — one int comparison per event
        — so a recorder that sees traffic keeps current anchors with
        no timer task; idle recorders still hold their construction
        anchor."""
        mono = now_ns()
        if not force and mono < self._next_anchor_ns:
            return
        self._next_anchor_ns = mono + self.anchor_interval_ns
        self.anchors.append((mono, time.time_ns()))
        if len(self.anchors) > self.ANCHORS_MAX:
            # keep the first (longest drift baseline) and the newest
            del self.anchors[1]

    # -- readers -----------------------------------------------------
    def snapshot(self, height: Optional[int] = None,
                 category: Optional[str] = None,
                 limit: int = 0) -> list[dict]:
        """Merged timeline, strictly ordered by monotonic timestamp.
        ``height`` keeps only events stamped with that height;
        ``category`` keeps one ring; ``limit`` keeps the newest N."""
        out = []
        for cat, ring in list(self._rings.items()):
            if category is not None and cat != category:
                continue
            for ts, dur, name, h, attrs, eid, parent, tid in list(ring):
                if height is not None and h != height:
                    continue
                ev = {"ts_ns": ts, "dur_ns": dur, "category": cat,
                      "name": name, "height": h, "id": eid,
                      "parent": parent, "tid": tid}
                if attrs:
                    ev["attrs"] = attrs
                out.append(ev)
        out.sort(key=lambda e: (e["ts_ns"], e["dur_ns"]))
        if limit > 0:
            out = out[-limit:]
        return out

    def clear(self) -> None:
        with self._lock:
            self._rings.clear()

    # -- the black box -----------------------------------------------
    def resolved_dump_dir(self) -> str:
        """Where automatic dumps land.  A node wires its data dir (or
        the explicit ``instrumentation.dump_dir``); a bare recorder —
        unit tests, tools, library embedders that never call
        configure() — falls back to $COMETBFT_TPU_DUMP_DIR, then the
        system temp dir.  Never the process CWD: supervisor give-up
        dumps from test runs used to litter the repository root."""
        if self.dump_dir:
            return self.dump_dir
        env = os.environ.get("COMETBFT_TPU_DUMP_DIR", "")
        if env:
            return env
        import tempfile
        return tempfile.gettempdir()

    def dump(self, reason: str = "", path: str = "",
             extra: Optional[dict] = None) -> str:
        """Write the whole flight record to a JSON file and return its
        path.  Never raises — a failing dump must not mask the crash
        being dumped; returns "" on failure."""
        try:
            with self._lock:
                self._dump_seq += 1
                seq = self._dump_seq
            if not path:
                slug = "".join(c if c.isalnum() or c in "-_" else "-"
                               for c in reason)[:48] or "flight"
                path = os.path.join(
                    self.resolved_dump_dir(),
                    f"flight-{os.getpid()}-{seq:03d}-{slug}.json")
            self.refresh_anchor(force=True)
            record = {
                "reason": reason,
                "wall_time": time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                "monotonic_ns": now_ns(),
                "pid": os.getpid(),
                "node": self.node_id,
                "anchors": [list(a) for a in self.anchors],
                "extra": extra or {},
                "events": self.snapshot(),
            }
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(record, f)
            os.replace(tmp, path)
            self.last_dump_path = path
            return path
        except Exception:
            return ""


# the process-global recorder (the node configures it; tests may swap
# their own via set_recorder)
_R = Recorder()


# ---------------------------------------------------------------------
# the interpreter's own time: garbage collections

# a generation-0 collection shorter than this is counted, not recorded
# (hundreds a second would fill the ring)
GC_SPAN_MIN_NS = 1_000_000

_gc_ns = 0                  # pauses so far, all generations
_gc_gen_ns = [0, 0, 0]      # ... by generation
_gc_gen_n = [0, 0, 0]       # collections by generation
_gc_t0 = 0                  # start of the collection under way


def _gc_hook(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry.  A collection holds the GIL from start
    to stop and never nests, so one start time and plain ints do; it
    runs on the thread that tripped it, whose open span is the parent
    of the ``gc_pause``.  Counts with the recorder off too."""
    global _gc_t0, _gc_ns
    if phase == "start":
        _gc_t0 = now_ns()
        return
    t1 = now_ns()
    t0 = _gc_t0
    gen = info["generation"]
    _gc_ns += t1 - t0
    _gc_gen_ns[gen] += t1 - t0
    _gc_gen_n[gen] += 1
    if gen or t1 - t0 >= GC_SPAN_MIN_NS:
        r = _R
        if r.enabled and (r.categories is None or
                          RUNTIME in r.categories):
            parent, height = _causal(0)
            r.record(RUNTIME, "gc_pause", t0, t1, height,
                     {"generation": gen,
                      "collected": info["collected"]}, parent=parent)


def gc_ns_total() -> int:
    """Nanoseconds this process has spent in garbage collections."""
    return _gc_ns


def gc_generation_totals() -> tuple[tuple[int, int], ...]:
    """(pause ns, collections) of generation 0, 1 and 2."""
    return tuple(zip(_gc_gen_ns, _gc_gen_n))


gc.callbacks.append(_gc_hook)


class _NopSpan:
    """Shared inert context manager for the disabled path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **attrs) -> None:
        pass


_NOP = _NopSpan()


class _Span:
    """One span.  ``with`` times it and makes it the parent of what
    opens inside; ``begin``/``end`` time it without binding the
    context, for an interval that is not one lexical block (a
    pipelined tile: :func:`under` then names it the parent of each
    piece).  ``_r`` is None for a :func:`timed` span whose category
    is off: it reads the clock for its caller and records nothing.
    ``_rt`` is the ``runtime`` flag: a recording span that has it
    reads the collector's pause total at both ends and notes
    ``gc_us``."""
    __slots__ = ("_r", "cat", "name", "height", "attrs", "t0", "t1",
                 "id", "parent", "closed", "_token", "_rt", "_gc0")

    def __init__(self, r: Optional[Recorder], cat: str, name: str,
                 height: int, attrs: Optional[dict],
                 runtime: bool = False):
        self._r = r
        self.cat = cat
        self.name = name
        self.height = height
        self.attrs = attrs
        self.t0 = self.t1 = 0
        self.id = self.parent = 0
        self.closed = False
        self._token = None
        # the reading belongs to the runtime category, as gc_pause does
        self._rt = runtime and r is not None and (
            r.categories is None or RUNTIME in r.categories)

    def begin(self):
        if self._r is not None:
            self.parent, self.height = _causal(self.height)
            self.id = next(_IDS)
            if self._rt:
                self._gc0 = _gc_ns
        self.t0 = now_ns()
        return self

    def end(self) -> None:
        self.t1 = now_ns()
        self.closed = True
        if self._r is not None:
            if self._rt:
                if self.attrs is None:
                    self.attrs = {}
                self.attrs["gc_us"] = (_gc_ns - self._gc0) // 1000
            self._r.record(self.cat, self.name, self.t0, self.t1,
                           self.height, self.attrs, self.id,
                           self.parent)

    def __enter__(self):
        self.begin()
        if self._r is not None:
            self._token = _CURRENT.set(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._r is not None:
            _CURRENT.reset(self._token)
            if exc_type is not None:
                self.note(error=exc_type.__name__)
        self.end()
        return False

    @property
    def seconds(self) -> float:
        """The span's own clock readings, for a metric observed at
        the same boundary."""
        return (self.t1 - self.t0) / 1e9

    def note(self, **attrs) -> None:
        """Attach attributes discovered mid-span."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)


class _Under:
    """Make an open span the context's parent for a block."""
    __slots__ = ("_sp", "_token")

    def __init__(self, sp: _Span):
        self._sp = sp

    def __enter__(self):
        self._token = _CURRENT.set(self._sp)
        return self._sp

    def __exit__(self, *exc):
        _CURRENT.reset(self._token)
        return False


# ---------------------------------------------------------------------
# module-level API — what the instrumented call sites use

def span(category: str, name: str, height: int = 0, *,
         runtime: bool = False, **attrs):
    """Context manager recording a monotonic span on exit.  When the
    category (or tracing) is disabled this is a no-op.  ``runtime``
    makes the span note what of the interpreter's own time fell
    inside it: ``gc_us``, the process's garbage-collection pauses (a
    collection holds the GIL, so it is the right charge whichever
    thread or task the span is on)."""
    r = _R
    if not r.enabled or (r.categories is not None and
                         category not in r.categories):
        return _NOP
    return _Span(r, category, name, height, attrs or None, runtime)


def timed(category: str, name: str, height: int = 0, *,
          runtime: bool = False, **attrs) -> _Span:
    """A span whose clock readings its caller also uses (``.seconds``
    after it closed): one pair of readings feeds the span and the
    metric observed at the same boundary.  Always reads the clock;
    records (and reads what ``runtime`` asks for) only when the
    category is on."""
    r = _R
    on = r.enabled and (r.categories is None or
                        category in r.categories)
    return _Span(r if on else None, category, name, height,
                 attrs or None, runtime)


def under(sp):
    """Context manager: inside it ``sp`` (begun, not yet ended) is
    the parent of whatever opens.  Inert for a span that records
    nothing."""
    if getattr(sp, "_r", None) is None:
        return _NOP
    return _Under(sp)


def current():
    """The innermost span open in this context, or None: what a span
    begun later, once the block that is open then has closed, names
    as its parent through :func:`under`."""
    p = _CURRENT.get()
    return None if p is None or p.closed else p


def instant(category: str, name: str, height: int = 0,
            **attrs) -> None:
    """Record a zero-duration point event."""
    r = _R
    if not r.enabled or (r.categories is not None and
                         category not in r.categories):
        return
    parent, height = _causal(height)
    r.record_instant(category, name, height, attrs or None, parent)


def record_span(category: str, name: str, start_ns: int,
                end_ns: Optional[int] = None, height: int = 0,
                **attrs) -> None:
    """Record a span whose start was captured by the caller (e.g. the
    consensus step tracker, which learns a step ended only when the
    next one begins).  Its parent is the span open around the call."""
    r = _R
    if not r.enabled or (r.categories is not None and
                         category not in r.categories):
        return
    parent, height = _causal(height)
    r.record(category, name, start_ns,
             end_ns if end_ns is not None else now_ns(), height,
             attrs or None, parent=parent)


def set_height(height: int) -> None:
    """Stamp the height in progress (consensus step machine) so
    height-less events inherit it."""
    _R.current_height = height


def enabled(category: str = "") -> bool:
    return _R.enabled_for(category) if category else _R.enabled


def snapshot(height: Optional[int] = None,
             category: Optional[str] = None,
             limit: int = 0) -> list[dict]:
    return _R.snapshot(height=height, category=category, limit=limit)


def dump(reason: str = "", path: str = "",
         extra: Optional[dict] = None) -> str:
    return _R.dump(reason=reason, path=path, extra=extra)


def clear() -> None:
    _R.clear()


def configure(enabled: bool = True, buffer_size: int = 4096,
              categories: Optional[str] = None,
              dump_dir: str = "", node_id: str = "",
              anchor_interval_s: float = 30.0) -> Recorder:
    """(Re)configure the process-global recorder — called by the node
    from instrumentation.trace_* config.  Existing rings are dropped
    so the new buffer size takes effect."""
    global _R
    _R = Recorder(buffer_size=buffer_size, enabled=enabled,
                  categories=categories, dump_dir=dump_dir,
                  node_id=node_id,
                  anchor_interval_s=anchor_interval_s)
    return _R


def refresh_anchor(force: bool = False) -> None:
    """Take a fresh clock anchor on the process-global recorder."""
    _R.refresh_anchor(force=force)


def anchors() -> list[tuple[int, int]]:
    return list(_R.anchors)


def recorder() -> Recorder:
    return _R


def set_recorder(r: Recorder) -> Recorder:
    """Test seam: install a private recorder; returns the old one."""
    global _R
    old, _R = _R, r
    return old
