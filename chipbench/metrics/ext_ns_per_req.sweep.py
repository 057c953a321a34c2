"""Device time of the scan's extended tier (``engine_scan_ext_slot`` in
the profiler trace) per request packed on that tier (program counter
``tier_requests{tier="ext"}``): the extended LLC's cost per request,
whatever the number of sets and tiles."""


def read(ctx):
    t = ctx.trace.kernel_s("engine_scan_ext_slot") if ctx.trace else 0.0
    n = ctx.counter("tier_requests", tier="ext")
    return t * 1e9 / n if t > 0 and n else None
