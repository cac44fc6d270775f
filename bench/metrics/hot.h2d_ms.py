"""Mean wall time per current batch of the fused scan's host-to-device
conversions of its arguments (``h2d`` spans under ``fused_scan``,
summed over shards)."""
from harness.fused import mean_in_fused


def read(run):
    return mean_in_fused(run, "h2d")
