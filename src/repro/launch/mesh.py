"""Mesh construction for the cache-sim fleet runtime.

Only a function, never a module-level mesh: importing this module does
not touch jax device state.
"""
from __future__ import annotations

import jax


def make_fleet_mesh(max_devices: int | None = None):
    """1-D ``("fleet",)`` mesh for the cache-sim fleet runtime
    (``runtime/fleet.py``): replica-stacked state shards its leading dim
    over this axis.  Uses the largest power-of-two prefix of the host's
    devices (replica batches are pow2-bucketed, so a non-pow2 axis would
    never tile).  On CPU, multiple devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before
    the first jax call — the CI fleet job runs the test suite that way.
    """
    n = len(jax.devices())
    if max_devices is not None:
        n = min(n, max_devices)
    n = 1 << (n.bit_length() - 1)       # largest pow2 <= n
    # Auto axes: the fleet step slices replica rows out of the sharded
    # output, which Explicit (sharding-in-types) axes refuse
    return jax.make_mesh((n,), ("fleet",),
                         axis_types=(jax.sharding.AxisType.Auto,))
