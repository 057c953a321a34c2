"""Work counts for roofline shares, and the table of peaks.

The bytes count the work the simulation needs, not what an implementation
moves: per simulated request its trace record (block address 4 B, write
flag 1 B, BDI level 1 B), and per simulated point the cache state read
once and written once.  They follow from the configuration and the request
counts alone, never from padded shapes.
"""
from __future__ import annotations

import json
from pathlib import Path

from .reference import Geometry

RECORD_BYTES = 4 + 1 + 1
# per way: tag 4, LRU counter 4, valid 1, dirty 1 (+ physical size 4 in
# the extended tier); per extended set: bytes used 4, BF2 count 4 and two
# Bloom filters
CONV_WAY_BYTES = 4 + 4 + 1 + 1
EXT_WAY_BYTES = CONV_WAY_BYTES + 4

_PEAKS = Path(__file__).with_name("peaks.json")


def state_bytes(geo: Geometry) -> int:
    conv = geo.conv_sets * geo.conv_ways * CONV_WAY_BYTES
    ext = geo.ext_sets * (geo.ext_max_ways * EXT_WAY_BYTES + 4 + 4
                          + 2 * geo.bloom_bits // 8)
    return conv + ext


def sweep_bytes(requests: int, state: int) -> int:
    """Bytes of one pass: every request's record plus every point's state
    (``state`` summed over the points) read and written once."""
    return requests * RECORD_BYTES + 2 * state


def peaks(device_kind: str) -> dict:
    """The published peaks of a device; a device not in the table is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}; add them with their source")
    return table[device_kind]
