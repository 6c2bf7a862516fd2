"""Compilations the server counted while serving its batches
(``serve_compiles``) over its scored batches: 0 once every shape the
batches use is warm."""


def read(ctx):
    counter = ctx.server.registry.get("serve_compiles")
    batches = ctx.server.metrics.batches
    if counter is None or not batches:
        return None
    return counter.value / batches
