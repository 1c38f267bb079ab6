"""Circuit breaker: closed → open → half-open, plus a latched-open
terminal state for non-transient faults.

Built for the TPU kernel dispatch path (crypto/batch.py): a failed
kernel compile is deterministic per process, so re-attempting it per
batch burns seconds of compile time on every commit.  The breaker
classifies that as non-transient and LATCHES open — the fallback
path is taken forever, no re-probe.  Transient faults (timeouts,
connection resets) open the breaker for ``reset_timeout_s`` and then
admit a single half-open probe.

State is exported as a gauge on whatever metrics registry the caller
wires in, so a degraded node is visible at /metrics.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

from . import metrics as libmetrics
from .log import Logger, nop_logger

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"
LATCHED_OPEN = "latched_open"

STATE_CODES = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2, LATCHED_OPEN: 3}


class Metrics:
    def __init__(self, registry: Optional[libmetrics.Registry] = None):
        m = registry if registry is not None else libmetrics.Registry()
        self.state = m.gauge(
            "breaker", "state",
            "Circuit state (0 closed, 1 open, 2 half-open, "
            "3 latched-open).", labels=("breaker",))
        self.failures = m.counter(
            "breaker", "failures_total",
            "Failures recorded against the circuit.",
            labels=("breaker",))
        self.transitions = m.counter(
            "breaker", "transitions_total",
            "State transitions of the circuit.",
            labels=("breaker", "state"))


class CircuitBreaker:
    """``allow()`` gates the protected call; the caller reports the
    outcome with ``record_success()`` / ``record_failure(latch=...)``.

    * closed: calls flow; ``failure_threshold`` consecutive failures
      open the circuit.
    * open: calls are refused until ``reset_timeout_s`` has elapsed,
      then ONE probe is admitted (→ half-open).
    * half-open: the probe's outcome closes or re-opens the circuit;
      concurrent calls are refused while the probe is in flight.
    * latched-open: terminal.  ``record_failure(latch=True)`` marks
      the fault non-transient; the circuit never re-probes.

    The clock is injectable for deterministic tests.
    """

    def __init__(self, name: str, failure_threshold: int = 1,
                 reset_timeout_s: float = 30.0,
                 monotonic: Callable[[], float] = time.monotonic,
                 metrics: Optional[Metrics] = None,
                 logger: Optional[Logger] = None):
        self.name = name
        self.failure_threshold = max(1, failure_threshold)
        self.reset_timeout_s = reset_timeout_s
        self._monotonic = monotonic
        self.metrics = metrics if metrics is not None else Metrics()
        self.logger = logger if logger is not None else nop_logger()
        self._state = CLOSED
        self._failures = 0         # consecutive, while closed
        self._opened_at = 0.0
        self._probe_in_flight = False
        self.metrics.state.with_labels(self.name).set(
            STATE_CODES[CLOSED])

    # ------------------------------------------------------------------
    @property
    def state(self) -> str:
        return self._state

    def _transition(self, state: str) -> None:
        if state == self._state:
            return
        self.logger.info("breaker transition", breaker=self.name,
                         from_=self._state, to=state)
        self._state = state
        self.metrics.state.with_labels(self.name).set(
            STATE_CODES[state])
        self.metrics.transitions.with_labels(self.name, state).inc()

    # ------------------------------------------------------------------
    def allow(self) -> bool:
        """True when the protected call may proceed.  In half-open,
        exactly one caller gets True per probe window."""
        if self._state == CLOSED:
            return True
        if self._state == LATCHED_OPEN:
            return False
        if self._state == OPEN:
            if self._monotonic() - self._opened_at >= \
                    self.reset_timeout_s:
                self._transition(HALF_OPEN)
                self._probe_in_flight = True
                return True
            return False
        # HALF_OPEN: admit a single probe at a time
        if not self._probe_in_flight:
            self._probe_in_flight = True
            return True
        return False

    def record_success(self) -> None:
        if self._state == LATCHED_OPEN:
            return
        self._failures = 0
        self._probe_in_flight = False
        self._transition(CLOSED)

    def record_failure(self, latch: bool = False) -> None:
        self.metrics.failures.with_labels(self.name).inc()
        self._probe_in_flight = False
        if self._state == LATCHED_OPEN:
            return
        if latch:
            self._transition(LATCHED_OPEN)
            return
        if self._state == HALF_OPEN:
            self._opened_at = self._monotonic()
            self._transition(OPEN)
            return
        self._failures += 1
        if self._failures >= self.failure_threshold:
            self._opened_at = self._monotonic()
            self._failures = 0
            self._transition(OPEN)
