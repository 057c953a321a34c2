"""Share of the HBM roofline the scan kernels reach: the work's bytes
(``roofline.sweep_bytes``) over the peak HBM bandwidth, over their device
time.  HBM bandwidth is the bound because the kernels move int32 state
and the chip's int32 vector peak is not published."""


def read(ctx):
    t = ctx.trace.kernel_s("engine_scan_") if ctx.trace else 0.0
    if t <= 0:
        return None
    return 100.0 * ctx.work["bytes"] / ctx.peak()["hbm_bytes_per_s"] / t
