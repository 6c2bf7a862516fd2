"""Median ``device_wait`` span: the worker blocked until the batch's
device work is done, once per scored batch."""
from bench.stats import percentile


def read(ctx):
    spans = [s.duration_us * 1e-3 for s in ctx.spans("device_wait")]
    return percentile(spans, 50) if spans else None
