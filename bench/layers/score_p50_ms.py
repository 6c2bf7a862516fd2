"""Median ``score`` span: dispatch to every segment and the delta until
the candidates are on the host, once per scored batch."""
from bench.stats import percentile


def read(ctx):
    spans = [s.duration_us * 1e-3 for s in ctx.spans("score")]
    return percentile(spans, 50) if spans else None
