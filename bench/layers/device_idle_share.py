"""Share of the traced window in which no operation ran on the device."""
from bench import trace_reduce


def read(ctx):
    if ctx.trace_events is None or ctx.window_s <= 0:
        return None
    busy, _ = trace_reduce.busy(ctx.trace_events)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ctx.window_s)
