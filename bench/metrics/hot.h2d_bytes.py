"""Mean bytes per current batch that the fused scan hands to the device:
the ``h2d_bytes`` counters (nbytes of the host arrays: queries, corpus,
mask) of the ``h2d`` spans under ``fused_scan``, summed over shards."""
from harness.fused import mean_in_fused


def read(run):
    return mean_in_fused(run, "h2d", counter="h2d_bytes")
