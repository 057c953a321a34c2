"""Mixture-of-Experts MLP: shared + routed experts, top-k, capacity-based
scatter dispatch (SPMD-friendly; experts shard over the ``model`` axis).

Dispatch avoids the O(T*E*C*D) one-hot einsum: token rows are scatter-added
into per-expert capacity buffers and gathered back — FLOP cost is just the
expert matmuls, and the XLA SPMD partitioner turns the scatter/gather into
all-to-all-style collectives when the buffers are expert-sharded.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..distributed import context as dist_ctx
from . import layers as L

Array = jnp.ndarray


def init_moe(key, cfg: ArchConfig, dtype) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    s_in, s_f = d ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * s_f).astype(dtype),
    }
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = L.init_dense_mlp(ks[4], d, fs, dtype)
    return p


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    if cfg.capacity_factor <= 0:
        # Dropless dispatch: every (token, expert) slot fits.  Capacity
        # dropping makes a token's output depend on which OTHER tokens are
        # in the batch, so cached decode (T=1 per sequence) can't reproduce
        # the full forward (T=S) — archs whose serving path must be exactly
        # prefill/decode-consistent (deepseek-v2 MLA) opt into this.
        # top_k expert indices are distinct per token, so one expert can
        # receive at most ``tokens`` assignments.
        c = tokens
    else:
        c = int(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # pad to 8 for layout friendliness


def moe_mlp(p: dict, x: Array, cfg: ArchConfig) -> Array:
    """x (..., D) -> (..., D).

    Two numerically-identical implementations:

    * pure-jnp (no mesh installed): global capacity buffers; fine for CPU
      tests and single-host runs, but under pjit the data-replicated
      expert buffers force GSPMD to all-reduce multi-GB scatter targets
      every layer (§Perf deepseek-moe iteration 1 baseline).
    * shard_map (mesh installed via distributed.context): tokens stay on
      their data shard (replicated over `model`), every chip dispatches
      ONLY into its local experts' capacity buffers, and one psum of the
      (tokens, d_model) output crosses the `model` axis — the Megatron
      EP-within-TP pattern.
    """
    mesh = dist_ctx.get_mesh()
    if mesh is not None and "model" in mesh.shape \
            and cfg.num_experts % mesh.shape["model"] == 0:
        return _moe_mlp_shardmap(p, x, cfg, mesh)
    return _moe_mlp_dense(p, x, cfg)


def _moe_mlp_dense(p: dict, x: Array, cfg: ArchConfig) -> Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    t = x2.shape[0]
    e, k = cfg.num_experts, cfg.top_k
    cap = _capacity(t, cfg)

    gates = jax.nn.softmax((x2.astype(jnp.float32) @ p["router"]), axis=-1)
    w, idx = jax.lax.top_k(gates, k)                       # (T, k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)

    e_flat = idx.reshape(-1)                               # (T*k,)
    tok_flat = jnp.repeat(jnp.arange(t), k)                # (T*k,)
    w_flat = w.reshape(-1)

    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)    # (T*k, E)
    pos_all = jnp.cumsum(onehot, axis=0) - 1               # (T*k, E)
    pos = jnp.take_along_axis(pos_all, e_flat[:, None], axis=1)[:, 0]
    keep = pos < cap                                       # capacity drop

    buf = jnp.zeros((e, cap, d), x2.dtype)
    buf = buf.at[e_flat, pos].add(
        jnp.where(keep[:, None], x2[tok_flat], 0), mode="drop")

    # expert FFN (swiglu) — experts shard over the `model` axis
    a = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    a = jax.nn.silu(a) if cfg.act == "silu" else jax.nn.gelu(a)
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", a * u, p["w_down"])

    y_tok = out_buf[e_flat, jnp.minimum(pos, cap - 1)]     # (T*k, D)
    y_tok = y_tok * (w_flat * keep)[:, None].astype(y_tok.dtype)
    y = jnp.sum(y_tok.reshape(t, k, d), axis=1)

    if "shared" in p:
        y = y + L.dense_mlp(p["shared"], x2, cfg.act)
    return y.reshape(orig_shape)


def _dispatch_compute(p_local: dict, x2: Array, gates: Array, cfg: ArchConfig,
                      e_lo: int, e_local: int) -> Array:
    """Route ``x2`` (T, D) into the ``e_local`` experts starting at global
    expert index ``e_lo`` and return this shard's partial output (T, D).

    Shared helper of the shard_map path (per-chip) — pure jnp, no
    collectives; the caller psums the result over the `model` axis."""
    t, d = x2.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = _capacity(t, cfg)

    w, idx = jax.lax.top_k(gates, k)                       # (T, k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    e_flat = idx.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t), k)
    w_flat = w.reshape(-1)

    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_all, e_flat[:, None], axis=1)[:, 0]
    keep = pos < cap

    local = e_flat - e_lo                                  # local expert id

    # §Perf iteration moe-2 (gather-dispatch / scatter-combine): only an
    # int32 inverse slot index goes through the scatter; token data moves
    # at BUFFER size (e_local*cap*d), never at (T*k, d) size.  The naive
    # form scattered/gathered 3.2 GB (T*k, d) update tensors per layer
    # (plus their gradients); this form moves ~250 MB.
    slots = jnp.arange(t * k, dtype=jnp.int32)
    sentinel = jnp.int32(t * k)
    inv = jnp.full((e_local, cap), sentinel, jnp.int32)
    # out-of-range experts (other chips') must map to a POSITIVE
    # out-of-bounds index: negative indices would wrap NumPy-style instead
    # of being dropped by mode="drop"
    row = jnp.where((local >= 0) & (local < e_local), local, e_local)
    inv = inv.at[row, pos].set(slots, mode="drop")
    valid = inv < sentinel                                 # (e_local, cap)
    tok_slot = jnp.where(valid, inv // k, t)               # t = OOB row

    buf = x2.at[tok_slot].get(mode="fill", fill_value=0)   # (e_local,cap,d)

    a = jnp.einsum("ecd,edf->ecf", buf, p_local["w_gate"])
    a = jax.nn.silu(a) if cfg.act == "silu" else jax.nn.gelu(a)
    u = jnp.einsum("ecd,edf->ecf", buf, p_local["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", a * u, p_local["w_down"])

    w_slot = jnp.where(valid, w_flat.at[jnp.minimum(inv, sentinel - 1)]
                       .get(mode="fill", fill_value=0), 0)
    contrib = out_buf * w_slot[..., None].astype(out_buf.dtype)
    y = jnp.zeros((t, d), x2.dtype)
    return y.at[tok_slot].add(contrib, mode="drop")


def _moe_mlp_shardmap(p: dict, x: Array, cfg: ArchConfig, mesh) -> Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    x3 = x.reshape(-1, d)                                   # (T_global, D)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_model = mesh.shape["model"]
    e_local = cfg.num_experts // n_model

    # tokens shard over the batch axes when divisible (the normal case);
    # tiny-batch decode (e.g. long_500k, global_batch 1) replicates them —
    # every data row redundantly computes the same single-token dispatch,
    # which is correct and costs nothing at that scale
    import numpy as _np
    n_batch = int(_np.prod([mesh.shape[a] for a in batch_axes]))         if batch_axes else 1
    if batch_axes and x3.shape[0] % n_batch == 0:
        tok_spec = P(batch_axes, None)
    else:
        tok_spec = P(None, None)

    def per_chip(router, w_gate, w_up, w_down, xs):
        # xs: (T_local, D) — this data shard's tokens, replicated over model
        gates = jax.nn.softmax(xs.astype(jnp.float32) @ router, axis=-1)
        m = jax.lax.axis_index("model")
        p_local = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        y_partial = _dispatch_compute(p_local, xs, gates, cfg,
                                      m * e_local, e_local)
        return jax.lax.psum(y_partial, "model")

    y = jax.shard_map(
        per_chip, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), tok_spec),
        out_specs=tok_spec,
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x3)

    if "shared" in p:
        y = y + L.dense_mlp(p["shared"], x3, cfg.act)
    return y.reshape(orig_shape)


def aux_load_balance_loss(p: dict, x: Array, cfg: ArchConfig) -> Array:
    """Switch-style auxiliary loss: E * dot(mean gate prob, token fraction)."""
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    gates = jax.nn.softmax(x2 @ p["router"], axis=-1)
    _, idx = jax.lax.top_k(gates, cfg.top_k)
    frac = jnp.mean(jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32),
                    axis=(0, 1))
    prob = jnp.mean(gates, axis=0)
    return cfg.num_experts * jnp.sum(frac * prob)
