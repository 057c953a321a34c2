"""Pallas kernel: batched Bloom-filter membership test + insert masks.

The Morpheus-controller predictor (paper §4.1.2) services a *batch* of
requests per step in our serving tier — this kernel tests K multiply-shift
hash bits per request against the per-set 32-byte filters in one VMEM
pass, and (for inserts) produces the OR-masks to apply.

Inputs arrive pre-gathered (filters row per query) — the set-index gather
is a cheap XLA op; the kernel does the bit math where the parallelism is,
with queries on lanes (QUERY_BLOCK per grid step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.bloom import _bit_mask, _hash_bits, _test

QUERY_BLOCK = 512


def _query_kernel(filters_ref, tags_ref, pred_ref, masks_ref):
    filters = filters_ref[...]                  # (words, QB) uint32
    bits = _hash_bits(tags_ref[...], filters.shape[0] * 32)   # (1, QB)s
    pred_ref[...] = _test(filters, bits).astype(jnp.int32)
    masks_ref[...] = _bit_mask(bits, filters.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bloom_query(filters: jnp.ndarray, tags: jnp.ndarray, *,
                interpret: bool):
    """filters (Q, words) u32 pre-gathered; tags (Q,) u32.

    Returns (predicted (Q,) i32, insert_masks (Q, words) u32).  Inside the
    kernel each query is one column — the engine's Bloom layout, so the
    bit math is ``core.bloom``'s own."""
    q, words = filters.shape
    qb = min(QUERY_BLOCK, q)
    assert q % qb == 0, (q, qb)
    col = lambda i: (0, i)
    pred, masks = pl.pallas_call(
        _query_kernel,
        grid=(q // qb,),
        in_specs=[pl.BlockSpec((words, qb), col),
                  pl.BlockSpec((1, qb), col)],
        out_specs=[pl.BlockSpec((1, qb), col),
                   pl.BlockSpec((words, qb), col)],
        out_shape=[jax.ShapeDtypeStruct((1, q), jnp.int32),
                   jax.ShapeDtypeStruct((words, q), jnp.uint32)],
        interpret=interpret,
    )(filters.T, tags[None, :].astype(jnp.uint32))
    return pred[0], masks.T
