"""Share of the HBM roofline the pair-walk kernel reaches: the bytes
the window's batches need (``bench/walk.py``) over the kernel's device
time at the chip's peak HBM bandwidth."""
from bench import trace_reduce, walk


def read(ctx):
    if ctx.trace_events is None or not ctx.batches:
        return None
    sec = trace_reduce.kernel_seconds(ctx.trace_events, walk.PATTERN)
    need = [ctx.walk_bytes.batch(b, ctx.k) for b in ctx.batches]
    if sec <= 0 or any(n is None for n in need):
        return None
    return 100.0 * sum(need) / (sec * ctx.peaks["hbm_bytes_per_s"])
