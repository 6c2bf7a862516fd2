"""Median ``merge`` span: the host merge of the candidate lists, with
their transfer from the device, once per scored batch."""
from bench.stats import percentile


def read(ctx):
    spans = [s.duration_us * 1e-3 for s in ctx.spans("merge")]
    return percentile(spans, 50) if spans else None
