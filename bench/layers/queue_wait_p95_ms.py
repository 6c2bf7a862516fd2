"""95th percentile of the admission-queue wait (``queue_wait`` spans)."""
from bench.stats import percentile


def read(ctx):
    waits = [s.duration_us * 1e-3 for s in ctx.spans("queue_wait")]
    return percentile(waits, 95) if waits else None
