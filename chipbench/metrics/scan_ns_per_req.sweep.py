"""Device time of the engine's scan kernels (``engine_scan_*`` in the
profiler trace) per simulated request."""


def read(ctx):
    t = ctx.trace.kernel_s("engine_scan_") if ctx.trace else 0.0
    return t * 1e9 / ctx.work["requests"] if t > 0 else None
