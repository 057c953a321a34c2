"""The benchmark's own copy of the Table-2 synthetic trace generator.

The simulator under test generates its traces itself; the reference
simulates the traces this module makes, so a program whose generator
shrank or changed the work gives Stats that no longer match.  The app
profiles are data (``table2_apps.json``), copied from the paper's Table 2
as the program models it.

A trace is (block addresses uint32, write flags bool, BDI levels int32):
per-core streams interleaved round-robin, as the program defines them.
"""
from __future__ import annotations

import json
import zlib
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

HIGH, LOW, UNCOMP = 0, 1, 2

_APPS_FILE = Path(__file__).with_name("table2_apps.json")


def load_apps() -> Dict[str, dict]:
    return json.loads(_APPS_FILE.read_text())["apps"]


def block_bytes() -> int:
    return json.loads(_APPS_FILE.read_text())["block_bytes"]


def _core_stream(app: dict, ws_bytes: int, block: int, n: int, core: int,
                 n_cores: int, rng: np.random.Generator) -> np.ndarray:
    ws = max(ws_bytes // block, 1024)
    if app["shared_dataset"]:
        lo, span = 0, ws
    else:
        span = max(ws // n_cores, 256)
        lo = core * span
    phase = (core * span) // max(n_cores, 1)
    pattern = app["pattern"]
    if pattern in ("streaming", "sweep"):
        idx = (phase + np.arange(n)) % span
    elif pattern == "strided":
        idx = (phase + np.arange(n) * 17) % span
    elif pattern == "stencil":
        base = (phase + np.arange(n)) % span
        neigh = rng.integers(-2, 3, size=n)
        row = int(np.sqrt(span)) or 1
        idx = (base + neigh * row) % span
    elif pattern == "tiles":
        tile = 4096
        tiles = max(span // tile, 1)
        t = (phase // tile + (np.arange(n) // (tile * 4))) % tiles
        idx = t * tile + rng.integers(0, tile, size=n)
    elif pattern == "wavefront":
        diag = (phase + np.arange(n) // 8) % span
        idx = (diag + rng.integers(0, 8, size=n)) % span
    elif pattern == "powerlaw":
        u = rng.random(n)
        idx = (span * u ** 2.2).astype(np.int64) % span
        idx = (idx + phase) % span
    elif pattern == "scatter":
        idx = rng.integers(0, span, size=n)
    elif pattern == "hotbins":
        hot = max(span // 4, 64)
        is_hot = rng.random(n) < 0.7
        idx = np.where(is_hot, rng.integers(0, hot, size=n),
                       (phase + np.arange(n)) % span)
    else:
        raise ValueError(f"unknown access pattern {pattern!r}")
    return (lo + idx).astype(np.uint32)


def generate(name: str, *, n_cores: int, length: int, seed: int,
             ws_scale: float) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One app's trace of ``length`` requests from ``n_cores`` streams."""
    app = load_apps()[name]
    block = block_bytes()
    ws_bytes = app["working_set_bytes"]
    if ws_scale != 1.0:
        ws_bytes = int(ws_bytes * ws_scale)
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % 65536)
    per_core = length // max(n_cores, 1) + 1
    streams = [_core_stream(app, ws_bytes, block, per_core, c, n_cores, rng)
               for c in range(max(n_cores, 1))]
    addrs = np.stack(streams, axis=1).reshape(-1)[:length]
    writes = rng.random(length) < app["write_frac"]
    # a block's compressibility is a property of its address
    h = (addrs.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) \
        >> np.uint64(40)
    u = (h % np.uint64(1000)).astype(np.float64) / 1000.0
    levels = np.where(u < app["p_high"], HIGH,
                      np.where(u < app["p_high"] + app["p_low"], LOW,
                               UNCOMP)).astype(np.int32)
    return addrs, writes, levels
