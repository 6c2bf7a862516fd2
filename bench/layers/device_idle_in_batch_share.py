"""Share of the traced window in which the first device is idle while
the server is inside a batch (a ``serve.*`` stage annotation is open).
``device_idle_share`` less this share is idle time spent waiting for
requests."""
from bench import trace_reduce


def read(ctx):
    if ctx.trace_events is None or ctx.window_s <= 0:
        return None
    _, busy = trace_reduce.busy(ctx.trace_events)
    stages = [(e.start_ns, e.start_ns + e.dur_ns) for e in ctx.trace_events
              if e.plane.startswith("/host") and e.name.startswith("serve.")]
    if not busy or not stages:
        return None
    # idle inside the stages: what the stages add to the busy time
    both = trace_reduce._union(stages + [tuple(iv) for iv in busy])
    idle_ns = sum(e - s for s, e in both) - sum(e - s for s, e in busy)
    return 100.0 * idle_ns * 1e-9 / ctx.window_s
