#!/usr/bin/env python
"""Generate the metrics catalog for docs/observability.md.

Assembles every metric family a node registers (all per-subsystem
Metrics classes on one registry, plus the lazily-registered families
on the process-global DEFAULT: crypto batch-verify / kernel-dispatch
histograms, breaker state, signature-cache counters) and prints a
markdown table of name, type, labels and help — the docs section is
pasted from this output, and the exposition contract test keeps the
registry honest (non-empty help, bounded labels).

Usage: python tools/metrics_catalog.py [--markdown|--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def collect_catalog() -> list[dict]:
    from cometbft_tpu.abci.metrics import Metrics as ProxyMetrics
    from cometbft_tpu.blocksync.metrics import (
        Metrics as BlocksyncMetrics,
    )
    from cometbft_tpu.consensus.metrics import (
        Metrics as ConsensusMetrics,
    )
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.libs.health import Metrics as HealthMetrics
    from cometbft_tpu.libs.supervisor import (
        Metrics as SupervisorMetrics,
    )
    from cometbft_tpu.lightserve.cache import (
        Metrics as LightserveMetrics,
    )
    from cometbft_tpu.mempool.metrics import Metrics as MempoolMetrics
    from cometbft_tpu.ops import ed25519_jax
    from cometbft_tpu.p2p.metrics import Metrics as P2PMetrics
    from cometbft_tpu.state.metrics import Metrics as StateMetrics
    from cometbft_tpu.statesync.metrics import (
        Metrics as StatesyncMetrics,
    )
    from cometbft_tpu.types import signature_cache

    reg = libmetrics.Registry()
    for cls in (ConsensusMetrics, MempoolMetrics, P2PMetrics,
                BlocksyncMetrics, StatesyncMetrics, StateMetrics,
                ProxyMetrics, SupervisorMetrics, LightserveMetrics,
                HealthMetrics):
        cls(reg)
    # force the lazy process-global families into existence
    from cometbft_tpu.crypto import bls12381
    from cometbft_tpu.crypto import pipeline as crypto_pipeline
    from cometbft_tpu.types import validation as types_validation
    crypto_batch.verify_seconds_histogram()
    crypto_batch.tpu_breaker()
    crypto_pipeline.dispatch_histogram()
    ed25519_jax._refine_counter()
    ed25519_jax.streamed_tiles_counter()
    signature_cache._metrics()
    from cometbft_tpu.light import client as light_client
    light_client.hop_counters()
    bls12381._agg_pk_metrics()
    types_validation.commit_verify_histogram()
    # registered where the module is imported
    from cometbft_tpu.consensus import replay as _replay  # noqa: F401
    # verification pipeline: overlap ratio + tile rejects, and the
    # staging/kernel workers' queue-wait/depth families (register a
    # worker on a throwaway registry-backed pair via the lazy
    # singletons' metric declarations)
    crypto_pipeline.overlap_histogram()
    crypto_pipeline._tile_reject_counter()
    from cometbft_tpu.libs.workers import SupervisedWorker
    _w = SupervisedWorker("catalog_probe")
    _w.stop()

    seen = set()
    out = []
    for fam in reg.collect() + libmetrics.DEFAULT.collect():
        if fam["name"] in seen:
            continue
        seen.add(fam["name"])
        out.append(fam)
    return sorted(out, key=lambda f: f["name"])


def to_markdown(catalog: list[dict]) -> str:
    lines = ["| Name | Type | Labels | Help |",
             "|------|------|--------|------|"]
    for fam in catalog:
        labels = ", ".join(f"`{l}`" for l in fam["labels"]) or "—"
        help_ = fam["help"].replace("\n", " ").replace("|", "\\|")
        lines.append(
            f"| `{fam['name']}` | {fam['kind']} | {labels} "
            f"| {help_} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="JSON instead of markdown")
    args = ap.parse_args(argv)
    catalog = collect_catalog()
    if args.json:
        print(json.dumps(catalog, indent=2))
    else:
        print(to_markdown(catalog))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
