"""Pallas kernel: indirect block gather — the Indirect-MOV analogue.

The paper needs a new ISA instruction (§4.3.2) because GPU register files
are immediate-indexed; on TPU the data array lives in VMEM which is
address-indexed, so the 'optimized Indirect-MOV' is simply a dynamic-index
row read inside the kernel.  This kernel is the extended-LLC *data array
access* path: given per-set way indices (from tag_lookup), it pulls the hit
block out of each set's (ways, words) data tile.

Tiling: one grid step owns SET_BLOCK sets; the (SET_BLOCK, ways, words)
data tile sits in VMEM.  The gather is a one-hot contraction over the ways
axis — on TPU this maps to a VPU select-accumulate (no serialized loads),
which is the whole point of the adaptation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SET_BLOCK = 64


def _gather_kernel(way_ref, data_ref, out_ref):
    way = way_ref[...]                         # (SB, 1) int32
    # one-hot select over ways (VPU select + OR; rows are disjoint so OR ==
    # select — exact for uint32 payloads).  ``data_ref[:, i]`` is a strided
    # sublane load of way i of every set.
    out = jnp.zeros(out_ref.shape, jnp.uint32)
    for i in range(data_ref.shape[1]):
        out = out | jnp.where(way == i, data_ref[:, i, :], jnp.uint32(0))
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks(data: jnp.ndarray, way: jnp.ndarray, *, interpret: bool):
    """data (S, W, words) u32; way (S,) i32 -> (S, words) u32.  ``S`` is a
    multiple of 8 up to SET_BLOCK, of SET_BLOCK beyond (``ops`` pads)."""
    s, w, words = data.shape
    sb = min(SET_BLOCK, s)
    assert s % sb == 0, (s, sb)
    return pl.pallas_call(
        _gather_kernel,
        grid=(s // sb,),
        in_specs=[pl.BlockSpec((sb, 1), lambda i: (i, 0)),
                  pl.BlockSpec((sb, w, words), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((sb, words), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, words), jnp.uint32),
        interpret=interpret,
    )(way[:, None].astype(jnp.int32), data)
