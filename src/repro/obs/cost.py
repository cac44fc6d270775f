"""Where a traced request's time went (DESIGN.md §15).

A one-word verdict an operator can read off a slow trace, from the spans
it already carries:

- **queue-bound**: the request mostly waited for admission and dispatch
  (the root's ``queue_wait_ms``): shed load or add capacity;
- **device-bound**: the host mostly blocked on the device (``device_wait``
  spans: the wait for a kernel's outputs and their copy back): shrink
  the bytes a scan reads or move work to a faster device;
- **host-bound**: everything else — embedding, planning, host scans,
  argument copies, merging — took the time: batch harder or move host
  work onto the device.

Device time itself (and a kernel's share of its roofline) is read from
a profiler trace, not from spans: a span measures the host's clock.

Annotation happens on SERIALIZED trace dicts (the flight recorder's
records, when they are read or dumped), never on the hot path: serving
pays for the raw spans only.
"""
from __future__ import annotations


def _fold(span_dict: dict, name: str) -> float:
    """Summed wall time of the spans named ``name`` in the subtree."""
    total = sum(_fold(c, name) for c in span_dict.get("children", ()))
    if span_dict.get("name") == name:
        total += span_dict.get("wall_ms", 0.0)
    return total


def annotate_costs(trace_dict: dict) -> dict:
    """Add a trace-level ``cost`` verdict to a serialized trace
    (``Trace.to_dict()`` shape). ``device_wait_ms`` sums the trace's
    ``device_wait`` spans; shards that run in parallel can make it
    exceed the wall time. Mutates and returns ``trace_dict``."""
    root = trace_dict.get("spans")
    if not root:
        return trace_dict
    wall = trace_dict.get("wall_ms") or root.get("wall_ms", 0.0)
    wait_ms = _fold(root, "device_wait")
    # queue_wait_ms is a root counter the batcher sets (time between
    # submit and dispatch)
    queue_ms = float((root.get("counters") or {}).get("queue_wait_ms", 0.0))
    if wall <= 0:
        bound = "unknown"
    elif queue_ms / wall >= 0.5:
        bound = "queue-bound"
    elif wait_ms / wall >= 0.5:
        bound = "device-bound"
    else:
        bound = "host-bound"
    trace_dict["cost"] = {
        "wall_ms": round(wall, 3),
        "device_wait_ms": round(wait_ms, 3),
        "queue_wait_ms": round(queue_ms, 3),
        "device_wait_frac": round(wait_ms / wall, 4) if wall > 0 else 0.0,
        "bound": bound,
    }
    return trace_dict
