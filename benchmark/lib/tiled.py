"""A tiled dispatch in the program's spans: the ``batch_verify`` spans
whose batch went through the pipelined path
(ops/ed25519_jax.TilePipeline, fed a tile at a time by ``verify_batch``
or from ``BatchVerifier.add``), each with its ``host_prep`` and
``kernel_execute`` children in the order they started.

Pure functions of the recorder's events, as lib/spantree's: spans
without ``id``/``parent`` give them nothing to read.
"""
from __future__ import annotations

from . import probes, spantree


def batches(spans: list[dict]) -> list[tuple[dict, list, list]]:
    """[(batch_verify span, its host_prep children, its warm pipelined
    kernel_execute children)], for every batch that has such a tile."""
    kids = spantree.children(spans)
    out = []
    for bv in spans:
        if bv["name"] != "batch_verify" or not bv.get("id"):
            continue
        mine = sorted(kids.get(bv["id"], ()), key=lambda e: e["ts_ns"])
        tiles = [e for e in mine if e["name"] == "kernel_execute"
                 and probes.attr(e, "pipelined")
                 and probes.attr(e, "warm")]
        if tiles:
            out.append((bv, [e for e in mine
                             if e["name"] == "host_prep"], tiles))
    return out
