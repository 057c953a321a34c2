"""Bytes of packed batch arrays the engine's dispatches hand the device
(program counter ``device_put_bytes``, both tiers), in MB per sweep
point: what each point costs the host-to-device transfer, padding
included."""


def read(ctx):
    # a CPU backend has no transfer to a device: read only where the
    # trace saw a device
    if not ctx.trace or ctx.trace.busy_s <= 0:
        return None
    n = ctx.counter("device_put_bytes")
    return n / 1e6 / ctx.work["points"] if n else None
