"""Pallas kernel: flash-decoding single-token attention over a KV tile
stream — the memory-bound consumer the Morpheus tier feeds.

One grid dimension walks KV blocks (the cache pages); online-softmax
running max / denominator / accumulator live in VMEM scratch and persist
across the sequential grid steps (TPU grid semantics).  The masked pages
(invalid ring slots, future positions) contribute -inf logits.

Tiling: q (B, H, hd) stays resident; each step streams a (B, Tb, KV, hd)
KV tile HBM->VMEM.  hd is 128-aligned for all assigned archs; Tb=512
bounds the tile at a few MiB of VMEM in bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

T_BLOCK = 512
NEG = -2.0e38


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_ref, l_ref, acc_ref):
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)          # (B, H, hd)
    k = k_ref[...].astype(jnp.float32)          # (B, Tb, KV, hd)
    v = v_ref[...].astype(jnp.float32)          # (B, Tb, KV, hd)
    valid = valid_ref[...] != 0                 # (B, Tb)

    b, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd)
    logits = jax.lax.dot_general(
        qg, k, (((3,), (3,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32)      # (b, kvh, g, Tb)
    logits = logits * (hd ** -0.5)
    logits = jnp.where(valid[:, None, None, :], logits, NEG)

    m_prev = m_ref[...]                          # (b, kvh, g)
    l_prev = l_ref[...]
    acc_prev = acc_ref[...]                      # (b, kvh, g, hd)

    m_cur = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[..., None])       # (b, kvh, g, Tb)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p, v, (((3,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32)      # (b, kvh, g, hd)
    acc_new = acc_prev * alpha[..., None] + pv

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(t == nt - 1)
    def _fin():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[...] = out.reshape(b, h, hd)


@functools.partial(jax.jit, static_argnames=("interpret", "t_block"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     valid: jnp.ndarray, *, interpret: bool,
                     t_block: int = T_BLOCK):
    """q (B,H,hd); k/v (B,T,KV,hd); valid (B,T) -> (B,H,hd) f32."""
    b, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    tb = min(t_block, t)
    assert t % tb == 0, (t, tb)
    g = h // kvh
    grid = (t // tb,)
    return pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, h, hd), lambda i: (0, 0, 0)),
            pl.BlockSpec((b, tb, kvh, hd), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((b, tb, kvh, hd), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((b, tb), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((b, h, hd), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((b, kvh, g), jnp.float32),
            pltpu.VMEM((b, kvh, g), jnp.float32),
            pltpu.VMEM((b, kvh, g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid.astype(jnp.int32))
