"""Share of the HBM roofline the BM25 pair walk reaches: the bytes the
window's batches need (``bench/walk.py``'s posting blocks and
candidates, plus ``bench/walk_bm25.py``'s length rows) over the
kernel's device time at the chip's peak HBM bandwidth."""
from bench import trace_reduce, walk_bm25


def read(ctx):
    if ctx.trace_events is None or not ctx.batches:
        return None
    sec = trace_reduce.kernel_seconds(ctx.trace_events, walk_bm25.PATTERN)
    need = [ctx.walk_bytes.batch(b, ctx.k) for b in ctx.batches]
    if sec <= 0 or any(n is None for n in need):
        return None
    terms = {int(t) for b in ctx.batches for q in b for t in q}
    rows = walk_bm25.LengthRowBytes.from_index(ctx.server.index, terms)
    need = sum(need) + sum(rows.batch(b) for b in ctx.batches)
    return 100.0 * need / (sec * ctx.peaks["hbm_bytes_per_s"])
