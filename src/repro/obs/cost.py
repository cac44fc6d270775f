"""Kernel-level cost attribution (DESIGN.md §15).

Every ``kernel:*`` span already counts the bytes it streamed
(rows x dim x elem_size — int8 scans count 1 byte/elem, fp32 4). This
module turns those raw counters into the judgment an operator needs
from a slow trace: *achieved GB/s* per kernel dispatch, the fraction of
the roofline that represents, and a one-word verdict for the whole
request — **bandwidth-bound** (the kernels dominated and ran near the
memory roofline: buy bandwidth or shrink bytes), **dispatch-bound**
(wall time went to everything around the kernels: Python dispatch,
planning, merging — batch harder), or **queue-bound** (the request
mostly waited for admission/dispatch: shed load or add capacity).

The roofline fraction needs the peak HBM bandwidth of the chip the
trace ran on, looked up by JAX's ``device_kind`` in ``PEAK_HBM_GBS``. A
kind not in the table (the CPU among them) gets ``achieved_gbs`` and no
fraction: no peak is assumed. The span's wall time is host time, so the
fraction is an end-to-end bound, not a kernel's device-time roofline
share.

Annotation happens on SERIALIZED trace dicts (the flight recorder's
retained records), never on the hot path: serving pays for the raw
counters only.
"""
from __future__ import annotations

# Peak HBM bandwidth of one chip in GB/s, keyed by ``device_kind``.
# "TPU v5 lite" is the TPU v5e: 819 GB/s (Google Cloud, "TPU v5e").
# benchmarks/roofline.py HBM_BW mirrors the v5e entry — asserted equal in
# tests/test_obs.py so the two can't drift apart silently.
PEAK_HBM_GBS = {"TPU v5 lite": 819.0}


def device_kind() -> str:
    """``device_kind`` of the process's first JAX device."""
    import jax
    return jax.devices()[0].device_kind


def annotate_span(span_dict: dict, peak_gbs: float | None) -> None:
    """Recursively annotate ``kernel:*`` spans that carry
    ``bytes_streamed`` with achieved_gbs, and with roofline_frac when
    the chip's peak is known, in place."""
    counters = span_dict.get("counters")
    if (span_dict.get("name", "").startswith("kernel:") and counters
            and counters.get("bytes_streamed")
            and span_dict.get("wall_ms", 0) > 0):
        gbs = counters["bytes_streamed"] / (span_dict["wall_ms"] / 1e3) / 1e9
        counters["achieved_gbs"] = round(gbs, 4)
        if peak_gbs is not None:
            counters["roofline_frac"] = round(gbs / peak_gbs, 6)
    for child in span_dict.get("children", ()):
        annotate_span(child, peak_gbs)


def _fold(span_dict: dict, pred) -> float:
    total = sum(_fold(c, pred) for c in span_dict.get("children", ()))
    if pred(span_dict):
        total += span_dict.get("wall_ms", 0.0)
    return total


def annotate_costs(trace_dict: dict, kind: str | None = None) -> dict:
    """Annotate a serialized trace (``Trace.to_dict()`` shape) with
    per-kernel roofline numbers and a trace-level ``cost`` verdict.
    ``kind`` is the ``device_kind`` the trace ran on (default: this
    process's device). Mutates and returns ``trace_dict``."""
    root = trace_dict.get("spans")
    if not root:
        return trace_dict
    peak = PEAK_HBM_GBS.get(kind or device_kind())
    annotate_span(root, peak)
    wall = trace_dict.get("wall_ms") or root.get("wall_ms", 0.0)
    # kernel spans never nest inside each other, so the fold is a sum of
    # disjoint intervals; queue_wait_ms is a root counter the batcher
    # sets (time between submit and dispatch)
    kernel_ms = _fold(root, lambda s: s.get("name", "").startswith("kernel:"))
    queue_ms = float((root.get("counters") or {}).get("queue_wait_ms", 0.0))
    best_frac = 0.0
    stack = [root]
    while stack:
        s = stack.pop()
        c = s.get("counters") or {}
        if c.get("roofline_frac", 0.0) > best_frac:
            best_frac = c["roofline_frac"]
        stack.extend(s.get("children", ()))
    if wall <= 0:
        bound = "unknown"
    elif queue_ms / wall >= 0.5:
        bound = "queue-bound"
    elif kernel_ms / wall >= 0.5:
        bound = "bandwidth-bound"
    else:
        bound = "dispatch-bound"
    trace_dict["cost"] = {
        "wall_ms": round(wall, 3),
        "kernel_ms": round(kernel_ms, 3),
        "queue_wait_ms": round(queue_ms, 3),
        "kernel_frac": round(kernel_ms / wall, 4) if wall > 0 else 0.0,
        "bound": bound,
    }
    if peak is not None:
        trace_dict["cost"]["best_roofline_frac"] = round(best_frac, 6)
    return trace_dict
