"""Device time of the scan's conventional tier (``engine_scan_conv_slot``
in the profiler trace) per request packed on that tier (program counter
``tier_requests{tier="conv"}``): the conventional LLC's cost per
request, in one set tile or several."""


def read(ctx):
    t = ctx.trace.kernel_s("engine_scan_conv_slot") if ctx.trace else 0.0
    n = ctx.counter("tier_requests", tier="conv")
    return t * 1e9 / n if t > 0 and n else None
