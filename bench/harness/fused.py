"""Readers of the spans inside the hot index's fused dispatch:
``fused_scan`` → ``kernel:<name>`` (argument copy plus enqueue) →
``h2d`` (host→device conversion, ``h2d_bytes``), and ``device_wait``
(the wait for the kernel's outputs and their copy back)."""
from __future__ import annotations

from .readers import batches, find, mean


def mean_in_fused(run, name: str, counter: str = None):
    """Mean per current batch of the summed wall time (or ``counter``)
    of the spans named ``name`` inside ``fused_scan`` spans, summed over
    shards, over the batches that have one; None where none has (a
    program that opens no such span)."""
    per = []
    for root in batches(run, "current"):
        spans = [s for f in find(root, name="fused_scan")
                 for s in find(f, name=name)]
        if spans:
            per.append(sum(s.get("counters", {}).get(counter, 0)
                           if counter else s["wall_ms"] for s in spans))
    return mean(per)
