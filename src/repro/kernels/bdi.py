"""Pallas kernels: BDI compression / decompression (paper §4.3.1).

Blocks are 128 B = 32 four-byte segments.  Compression classifies each
block by whether all two's-complement deltas from the base segment fit in
int8 (HIGH, 4x) / int16 (LOW, 2x) / neither (UNCOMP), and emits the delta
payload; the base is carried out-of-line (the paper's 'auxiliary
registers').  All arithmetic is mod-2^32 uint32 — identical to what the
dynamic-range check costs on the VPU.

Tiling: (BLOCKS_PER_TILE, 32) uint32 tiles in VMEM; one grid dim over the
block batch.  Used on the serving path fused around the block gather
(decompress-on-read), see kernels/ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.compression import HIGH, LOW, UNCOMP

BLOCKS_PER_TILE = 256
SEGMENTS = 32


def _all(mask: jnp.ndarray) -> jnp.ndarray:
    """AND over the segments, as an int32 min (N, 1)."""
    return jnp.min(mask.astype(jnp.int32), axis=1, keepdims=True) > 0


def _compress_kernel(blocks_ref, level_ref, base_ref, payload_ref):
    blocks = blocks_ref[...]                    # (N, 32) uint32
    base = blocks[:, 0:1]                       # (N, 1)
    deltas = blocks - base                      # mod-2^32
    hi8 = jnp.uint32(127)
    lo8 = jnp.uint32(0x100000000 - 128)
    hi16 = jnp.uint32(32767)
    lo16 = jnp.uint32(0x100000000 - 32768)
    fits8 = _all((deltas <= hi8) | (deltas >= lo8))
    fits16 = _all((deltas <= hi16) | (deltas >= lo16))
    level = jnp.where(fits8, HIGH, jnp.where(fits16, LOW, UNCOMP)
                      ).astype(jnp.int32)
    level_ref[...] = level
    base_ref[...] = base
    payload_ref[...] = jnp.where(level == UNCOMP, blocks, deltas)


def _decompress_kernel(level_ref, base_ref, payload_ref, out_ref):
    level = level_ref[...]                      # (N, 1) int32
    base = base_ref[...]                        # (N, 1) uint32
    payload = payload_ref[...]
    restored = base + payload                   # mod-2^32 add inverts
    out_ref[...] = jnp.where(level == UNCOMP, payload, restored)


def _tiles(n: int):
    bt = min(BLOCKS_PER_TILE, n)
    assert n % bt == 0, (n, bt)
    return bt, (n // bt,)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bdi_compress(blocks: jnp.ndarray, *, interpret: bool):
    """blocks (N, 32) u32 -> (level (N,) i32, base (N,) u32, payload (N,32))."""
    n, segs = blocks.shape
    assert segs == SEGMENTS
    bt, grid = _tiles(n)
    row = lambda i: (i, 0)
    level, base, payload = pl.pallas_call(
        _compress_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bt, segs), row)],
        out_specs=[pl.BlockSpec((bt, 1), row), pl.BlockSpec((bt, 1), row),
                   pl.BlockSpec((bt, segs), row)],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1), jnp.uint32),
                   jax.ShapeDtypeStruct((n, segs), jnp.uint32)],
        interpret=interpret,
    )(blocks)
    return level[:, 0], base[:, 0], payload


@functools.partial(jax.jit, static_argnames=("interpret",))
def bdi_decompress(level: jnp.ndarray, base: jnp.ndarray,
                   payload: jnp.ndarray, *, interpret: bool):
    """level (N,) i32, base (N,) u32, payload (N, 32) u32 -> (N, 32) u32;
    the per-block columns travel as (N, 1)."""
    n, segs = payload.shape
    bt, grid = _tiles(n)
    row = lambda i: (i, 0)
    return pl.pallas_call(
        _decompress_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bt, 1), row), pl.BlockSpec((bt, 1), row),
                  pl.BlockSpec((bt, segs), row)],
        out_specs=pl.BlockSpec((bt, segs), row),
        out_shape=jax.ShapeDtypeStruct((n, segs), jnp.uint32),
        interpret=interpret,
    )(level[:, None], base[:, None], payload)
