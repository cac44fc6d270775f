"""Mean wall time per current batch of the exact fp32 rescore of the
int8 scans' pools (``rescore`` spans of every scan source: fused, solo
and IVF segments; summed over shards). None on an fp32 store."""
from harness.readers import mean_span_ms


def read(run):
    return mean_span_ms(run, intent="current", names=("rescore",))
