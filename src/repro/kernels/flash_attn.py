"""Pallas kernel: flash attention (forward) for train/prefill shapes.

Tiling: grid = (B, KV_heads, nq, nk); the last grid dimension walks KV
tiles sequentially (TPU grid semantics) while online-softmax state
(running max m, denominator l, output accumulator acc) lives in VMEM
scratch.  Per program instance the working set is

    q tile   (G, Tq, hd)      — resident across the nk walk
    k tile   (Tk, hd)         — streamed HBM -> VMEM
    v tile   (Tk, hdv)
    scratch  (G, Tq) x2 + (G, Tq, hdv)

with Tq = Tk = 128 and hd <= 256 this is well under 1 MiB — VMEM-friendly
— and every matmul contraction dim is a multiple of the 128 MXU tile for
all assigned archs.

Variants covered (they are config bits on the mask/logits, not separate
kernels): GQA grouping, causal, sliding-window (local) attention, gemma-2
logit soft-capping, and MLA's v head dim != qk head dim.

The (S, T) score matrix never exists in HBM: on real TPU hardware this is
the difference between the naive path's O(S*T) HBM traffic and O(S+T).
The pure-jnp oracle is ``ref.flash_attention`` (tests sweep shapes/dtypes
and assert allclose).  Forward only: the training path keeps the
query-chunked jnp attention (its backward is generated by AD), serving
paths (prefill) have no backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.0e38
Q_BLOCK = 128
K_BLOCK = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, window: int, softcap: float, scale: float,
                  q_block: int, k_block: int, seq_q: int, seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, Tq, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (Tk, hd)
    v = v_ref[0, 0].astype(jnp.float32)          # (Tk, hdv)

    logits = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (G, Tq, Tk)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)

    qpos = iq * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, k_block), 0)
    kpos = ik * k_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, k_block), 1)
    mask = (qpos < seq_q) & (kpos < seq_k)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    logits = jnp.where(mask[None], logits, NEG)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(logits, axis=-1)                       # (G, Tq)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[..., None])                 # (G, Tq, Tk)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_new = acc_prev * alpha[..., None] + pv

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    q_block: int = Q_BLOCK, k_block: int = K_BLOCK,
                    interpret: bool):
    """q (B, S, H, hd), k (B, T, KV, hd), v (B, T, KV, hdv)
    -> (B, S, H, hdv).  H = KV * G (grouped-query attention)."""
    b, s, h, hd = q.shape
    t, kvh, hdv = k.shape[1], k.shape[2], v.shape[3]
    g = h // kvh
    scale = hd ** -0.5 if scale is None else scale

    s_pad = -(-s // q_block) * q_block
    t_pad = -(-t // k_block) * k_block
    q5 = jnp.moveaxis(q.reshape(b, s, kvh, g, hd), 1, 3)   # (B,KV,G,S,hd)
    k4 = jnp.moveaxis(k, 1, 2)                             # (B,KV,T,hd)
    v4 = jnp.moveaxis(v, 1, 2)                             # (B,KV,T,hdv)
    if s_pad != s:
        q5 = jnp.pad(q5, ((0, 0),) * 3 + ((0, s_pad - s), (0, 0)))
    if t_pad != t:
        k4 = jnp.pad(k4, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        v4 = jnp.pad(v4, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))

    nq, nk = s_pad // q_block, t_pad // k_block
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, softcap=softcap,
        scale=scale, q_block=q_block, k_block=k_block, seq_q=s, seq_k=t)

    out = pl.pallas_call(
        kernel,
        grid=(b, kvh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, q_block, hd),
                         lambda b_, n, iq, ik: (b_, n, 0, iq, 0)),
            pl.BlockSpec((1, 1, k_block, hd),
                         lambda b_, n, iq, ik: (b_, n, ik, 0)),
            pl.BlockSpec((1, 1, k_block, hdv),
                         lambda b_, n, iq, ik: (b_, n, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, q_block, hdv),
                               lambda b_, n, iq, ik: (b_, n, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, s_pad, hdv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, q_block), jnp.float32),
            pltpu.VMEM((g, q_block), jnp.float32),
            pltpu.VMEM((g, q_block, hdv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q5, k4, v4)

    out = jnp.moveaxis(out, 3, 1)[:, :s].reshape(b, s, h, hdv)
    return out
