"""Device time of the BM25 pair walk (``pair_walk_bm25``) per scored
batch."""
from bench import trace_reduce, walk_bm25


def read(ctx):
    if ctx.trace_events is None or not ctx.batches:
        return None
    sec = trace_reduce.kernel_seconds(ctx.trace_events, walk_bm25.PATTERN)
    return sec * 1e3 / len(ctx.batches) if sec > 0 else None
