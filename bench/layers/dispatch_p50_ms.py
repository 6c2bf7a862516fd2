"""Median ``dispatch`` span: query weights, then every segment and the
delta enqueued on the device, once per scored batch."""
from bench.stats import percentile


def read(ctx):
    spans = [s.duration_us * 1e-3 for s in ctx.spans("dispatch")]
    return percentile(spans, 50) if spans else None
