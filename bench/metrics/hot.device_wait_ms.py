"""Mean wall time per current batch that the fused scan's host blocks on
the device and on the results' return (``device_wait`` spans under
``fused_scan``, summed over shards)."""
from harness.fused import mean_in_fused


def read(run):
    return mean_in_fused(run, "device_wait")
