"""Sweep points per engine dispatch (program counter
``engine_dispatches{path=batch}``): how well ``run_batch`` batches."""


def read(ctx):
    n = ctx.counter("engine_dispatches", path="batch")
    return ctx.work["points"] / n if n else None
