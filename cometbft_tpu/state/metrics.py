"""State/execution + pruner metrics (reference: state/metrics.gen.go)."""
from __future__ import annotations

from typing import Optional

from ..libs import metrics as libmetrics


class Metrics:
    def __init__(self, registry: Optional[libmetrics.Registry] = None):
        m = registry if registry is not None else libmetrics.Registry()
        self.consensus_param_updates = m.counter(
            "state", "consensus_param_updates",
            "Number of consensus parameter updates returned by the "
            "application since process start.")
        self.validator_set_updates = m.counter(
            "state", "validator_set_updates",
            "Number of validator set updates returned by the "
            "application since process start.")
        self.block_processing_time = m.histogram(
            "state", "block_processing_time",
            "Time spent processing a block in ms: FinalizeBlock, "
            "saving its response, the state update, the application's "
            "Commit with the mempool update, the state save and the "
            "events (the apply_block span's boundary).",
            buckets=tuple(1.0 + 10.0 * i for i in range(10)))
        self.application_block_retain_height = m.gauge(
            "state", "application_block_retain_height",
            "The retain height set by the application.")
        self.pruning_service_block_retain_height = m.gauge(
            "state", "pruning_service_block_retain_height",
            "The retain height set by the pruning service (data "
            "companion).")
        self.block_store_base_height = m.gauge(
            "state", "block_store_base_height",
            "The first height the block store retains.")
