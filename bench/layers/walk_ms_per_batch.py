"""Device time of the pair-walk kernel per scored batch."""
from bench import trace_reduce, walk


def read(ctx):
    if ctx.trace_events is None or not ctx.batches:
        return None
    sec = trace_reduce.kernel_seconds(ctx.trace_events, walk.PATTERN)
    return sec * 1e3 / len(ctx.batches) if sec > 0 else None
