"""The warm-up rule, the same in every traffic driver.

Run the cell's own traffic for at least ``min_ops`` operations and
until the last ``quiet_ops`` of them caused no backend compile, no step
of the program's pad_bucket_refinements counter and no new ``bucket``
value in a kernel_execute span; past ``cap_s`` the run fails.  Whatever
the program sets up lazily (a bucket the tuner inserts, a shape it
compiles inside a call) is thereby paid in setup_s and never inside
the window.  The benchmark reads the refinement as the program reports
it and knows nothing else of the tuner.
"""
from __future__ import annotations

import time

from . import probes

REFINEMENTS = "cometbft_crypto_pad_bucket_refinements"


class WarmupTimeout(RuntimeError):
    pass


class WarmupGate:
    def __init__(self, compiles, min_ops: int, quiet_ops: int = 32,
                 cap_s: float = 400.0):
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.libs import tracing
        self._tracing = tracing
        self._registry = libmetrics.DEFAULT
        self.compiles = compiles
        self.min_ops = min_ops
        self.quiet_ops = quiet_ops
        self.cap_s = cap_s
        self.ops = 0
        self.last_change_op = 0
        self.buckets: set = set()
        self.setup_spans: list[dict] = []
        self._n_compiles = len(compiles)
        self._refinements = self._read_refinements()
        self._t0 = time.monotonic()

    def _read_refinements(self) -> float:
        return probes.total(probes.metrics_snapshot(self._registry),
                            REFINEMENTS)

    def op_done(self, n: int = 1) -> None:
        """Call after each operation (or a few) of warm-up traffic.
        Drains the recorder's crypto ring: set-up spans are kept for
        the set-up metrics, the rest is warm-up noise."""
        self.ops += n
        changed = False
        if len(self.compiles) != self._n_compiles:
            self._n_compiles = len(self.compiles)
            changed = True
        ref = self._read_refinements()
        if ref != self._refinements:
            self._refinements = ref
            changed = True
        events = self._tracing.snapshot(category=self._tracing.CRYPTO)
        self._tracing.clear()
        for ev in events:
            if probes.is_shape_setup(ev):
                self.setup_spans.append(ev)
            if ev["name"] == "kernel_execute":
                b = probes.attr(ev, "bucket")
                if b not in self.buckets:
                    self.buckets.add(b)
                    changed = True
        if changed:
            self.last_change_op = self.ops
        if not self.done() and \
                time.monotonic() - self._t0 > self.cap_s:
            raise WarmupTimeout(
                f"warm-up not quiet after {self.cap_s:.0f} s and "
                f"{self.ops} operations (last change at operation "
                f"{self.last_change_op})")

    def done(self) -> bool:
        return self.ops >= self.min_ops and \
            self.ops - self.last_change_op >= self.quiet_ops
