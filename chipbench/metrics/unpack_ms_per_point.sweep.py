"""Host time of ``run_batch``'s per-point Stats unpacking and
``_finalize`` (span ``cache_sim.unpack``) per sweep point."""


def read(ctx):
    # a CPU backend runs the "device" phases on the host: read only where
    # the trace saw a device
    if not ctx.trace or ctx.trace.busy_s <= 0:
        return None
    s = ctx.span("cache_sim.unpack")
    return 1e3 * s.total_s / ctx.work["points"] if s.count else None
