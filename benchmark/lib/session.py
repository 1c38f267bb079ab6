"""One run of one cell: what the harness hands a traffic driver (Ctx,
Window) and what a per-layer reader is given (Obs).
"""
from __future__ import annotations

import os
import random
import time
from dataclasses import dataclass, field
from typing import Optional

from .loader import Bench, Cell

TRACE_BUFFER = 1 << 18      # recorder ring, per category: a window's
                            # spans must all fit (the default is 4096)


class Ctx:
    def __init__(self, bench: Bench, cell: Cell, seed: int,
                 seconds: float, trace: bool, rehearsal: bool,
                 compiles, t_start: float):
        self.bench = bench
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.rehearsal = rehearsal
        self.compiles = compiles
        self.t_start = t_start
        self.laps: dict[str, float] = {}
        self.overrides: dict = {}       # run.py --param, sweeps only
        self.keep_trace = False
        self._lap_t = t_start
        self.work_dir = os.path.join(bench.root, ".bench_work",
                                     cell.name)
        os.makedirs(self.work_dir, exist_ok=True)

    def rng(self, tag: str) -> random.Random:
        return random.Random(f"{tag}-{self.seed}")

    def param(self, key: str, default=None):
        """A traffic parameter; under --rehearsal the cell's
        ``rehearsal`` group overrides (tiny sizes)."""
        if key in self.overrides:
            return self.overrides[key]
        if self.rehearsal and key in self.cell.params.get(
                "rehearsal", {}):
            return self.cell.params["rehearsal"][key]
        return self.cell.param(key, default)

    def lap(self, label: str) -> None:
        """Seconds of set-up since the previous lap, under ``label``:
        set-up facts, printed before the result line."""
        now = time.monotonic()
        self.laps[label] = self.laps.get(label, 0.0) + now - self._lap_t
        self._lap_t = now

    def configure_tracing(self) -> None:
        """(Re)size the program's flight recorder for a window.  A
        driver that constructs Nodes calls this again afterwards:
        every Node re-creates the process-global recorder."""
        from cometbft_tpu.libs import tracing
        tracing.configure(enabled=True, buffer_size=TRACE_BUFFER,
                          dump_dir=self.work_dir)

    def warmup_gate(self):
        from .warmup import WarmupGate
        return WarmupGate(self.compiles,
                          min_ops=int(self.param("warmup_ops")),
                          quiet_ops=int(self.param("quiet_ops", 32)),
                          cap_s=float(self.param("warmup_cap_s", 400)))


@dataclass
class Window:
    """The measured window on the host's monotonic clock."""
    start: float
    seconds: float

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def open(self) -> bool:
        return time.monotonic() < self.end


@dataclass
class Outcome:
    """What a driver's check() owes the harness."""
    attempted: int
    failed: int
    problems: list[str] = field(default_factory=list)


@dataclass
class Obs:
    """Everything a per-layer reader may read."""
    cell: Cell
    spans: list[dict]               # every span that started in the window
    setup_spans: list[dict]         # shape set-ups seen during set-up
    metrics: dict                   # probes delta over the window
    samples: dict                   # the driver's own readings
    compiles_in_window: int
    laps: dict[str, float]
    device_kind: str
    trace: Optional[dict] = None    # reduced profiler trace (--trace 1)
    trace_spans: list[dict] = field(default_factory=list)

    def crypto(self, name: str) -> list[dict]:
        return [ev for ev in self.spans
                if ev["category"] == "crypto" and ev["name"] == name]
