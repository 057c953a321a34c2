"""Share of the padded slots ``engine.pack`` hands the scan that hold a
request (program counter ``packed_slots``): how much of the scan's work
is real."""


def read(ctx):
    # a CPU backend runs the "device" phases on the host: read only where
    # the trace saw a device
    if not ctx.trace or ctx.trace.busy_s <= 0:
        return None
    slots = ctx.counter("packed_slots")
    return 100.0 * ctx.work["requests"] / slots if slots else None
