"""Pallas kernel: extended-LLC tag lookup + LRU update (paper Algorithm 1).

Hardware mapping (DESIGN.md §2): one *warp owns one cache set* becomes one
*grid program instance owns a tile of sets*; the warp's 32 lanes comparing
32 ways in parallel become the VPU lanes comparing the way dimension; the
``ballot_sync``/``ffs`` pair becomes a masked reduce + argmax over lanes —
no divergence, which is exactly why this layout is TPU-native.  The
argmax is a min over a lane iota (Mosaic's argmax takes float32 only).

Tiling: sets are tiled ``SET_BLOCK`` per program; the (SET_BLOCK, ways)
metadata tiles live in VMEM (ways <= 128 so a tile is a few KiB; the MXU is
not involved — this is a VPU kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.tag_store import LRU_MAX_INT

SET_BLOCK = 256


def _tag_lookup_kernel(req_ref, tags_ref, valid_ref, lru_ref,
                       hit_ref, way_ref, newlru_ref):
    tags = tags_ref[...]                       # (SB, W) uint32
    valid = valid_ref[...] != 0                # (SB, W)
    lru = lru_ref[...].astype(jnp.int32)       # (SB, W), < LRU_MAX_INT
    req = req_ref[...]                         # (SB, 1) uint32
    w = tags.shape[1]

    match = valid & (tags == req)                      # Alg.1 lines 2-3
    hit = jnp.max(match.astype(jnp.int32), axis=1,     # ballot_sync
                  keepdims=True) > 0
    w_iota = jax.lax.broadcasted_iota(jnp.int32, tags.shape, 1)
    way = jnp.min(jnp.where(match, w_iota, w), axis=1, keepdims=True)
    way = jnp.where(way == w, 0, way)                  # ffs (argmax)
    onehot = (w_iota == way) & hit
    dec = jnp.maximum(lru, 1) - 1                      # saturating decrement
    new_lru = jnp.where(onehot, LRU_MAX_INT, jnp.where(hit, dec, lru))

    hit_ref[...] = hit.astype(jnp.int32)
    way_ref[...] = way
    newlru_ref[...] = new_lru.astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tag_lookup(tags: jnp.ndarray, valid: jnp.ndarray, lru: jnp.ndarray,
               req: jnp.ndarray, *, interpret: bool):
    """tags/valid/lru (S, W); req (S,).  Returns (hit, way, new_lru), the
    first two (S,) int32.  Per-set values travel as (S, 1) columns."""
    s, w = tags.shape
    sb = min(SET_BLOCK, s)
    assert s % sb == 0, (s, sb)
    row = lambda i: (i, 0)
    hit, way, new_lru = pl.pallas_call(
        _tag_lookup_kernel,
        grid=(s // sb,),
        in_specs=[
            pl.BlockSpec((sb, 1), row),
            pl.BlockSpec((sb, w), row),
            pl.BlockSpec((sb, w), row),
            pl.BlockSpec((sb, w), row),
        ],
        out_specs=[
            pl.BlockSpec((sb, 1), row),
            pl.BlockSpec((sb, 1), row),
            pl.BlockSpec((sb, w), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1), jnp.int32),
            jax.ShapeDtypeStruct((s, 1), jnp.int32),
            jax.ShapeDtypeStruct((s, w), jnp.uint32),
        ],
        interpret=interpret,
    )(req[:, None], tags, valid, lru)
    return hit[:, 0], way[:, 0], new_lru
