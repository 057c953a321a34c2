"""Host time of ``run_batch``'s preparation (span ``cache_sim.prepare``:
trace generation, the unified filter, the configs) per sweep point."""


def read(ctx):
    # a CPU backend runs the "device" phases on the host: read only where
    # the trace saw a device
    if not ctx.trace or ctx.trace.busy_s <= 0:
        return None
    s = ctx.span("cache_sim.prepare")
    return 1e3 * s.total_s / ctx.work["points"] if s.count else None
