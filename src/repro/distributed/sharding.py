"""Sharding rules: parameter PartitionSpecs, and the cache-sim fleet axis.

Conventions:
  * ``pod``   — pure data parallelism across pods (gradient all-reduce)
  * ``data``  — data parallelism / context parallelism for long decode
  * ``model`` — tensor parallelism: heads, d_ff, experts, vocab, d_inner

Parameters are matched by their pytree path leaf-name; any unmatched array
is replicated.  Divisibility is always checked — a dim that does not tile
over the axis falls back to replication rather than producing a compile
error.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# leaf-name -> (dim -> logical axis) ; dims not listed are replicated
_PARAM_RULES: Dict[str, Dict[int, str]] = {
    # embeddings
    "embed": {0: "model"},          # (V, D) vocab-sharded
    "unembed": {1: "model"},        # (D, V)
    # attention
    "wq": {1: "model"},
    "wk": {1: "model"},
    "wv": {1: "model"},
    "wo": {0: "model"},
    "w_ukv": {1: "model"},          # MLA up-projection (r, H*(nd+vd))
    "w_dkv": {},                    # small latent down-proj: replicated
    # dense mlp
    "w_gate": {1: "model"},         # (D, F) / moe (E, D, F) handled below
    "w_up": {1: "model"},
    "w_down": {0: "model"},
    # moe (3D weights: expert axis shards)
    "router": {},
    # mamba
    "w_z": {1: "model"},
    "w_x": {1: "model"},
    "w_B": {}, "w_C": {}, "w_dt": {},
    "conv_x": {1: "model"}, "conv_B": {}, "conv_C": {},
    "out_proj": {0: "model"},
}

_MOE_RULES = {"w_gate": {0: "model"}, "w_up": {0: "model"},
              "w_down": {0: "model"}}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _spec_for(path, leaf, mesh: Mesh) -> P:
    name = None
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            name = str(k.key)
            break
    ndim = len(leaf.shape)
    rules = dict(_PARAM_RULES.get(name, {}))
    # stacked block params have a leading num_blocks dim; 3D moe weights
    # have a leading expert dim.  Distinguish by name + ndim.
    base_ndim = {"embed": 2, "unembed": 2, "wq": 2, "wk": 2, "wv": 2,
                 "wo": 2, "w_ukv": 2, "w_dkv": 2, "w_gate": 2, "w_up": 2,
                 "w_down": 2, "router": 2, "w_z": 2, "w_x": 2, "w_B": 2,
                 "w_C": 2, "w_dt": 2, "conv_x": 2, "conv_B": 2, "conv_C": 2,
                 "out_proj": 2}.get(name)
    if base_ndim is None:
        return P()  # norms, A_log, biases: replicated
    extra = ndim - base_ndim  # 0 (plain), 1 (stacked OR moe), 2 (stacked moe)
    if name in _MOE_RULES and extra >= 1:
        # (E, d, f) or (blocks, E, d, f): expert axis shards over model
        moe_dim = extra - 1 if extra >= 1 else 0
        spec = [None] * ndim
        if leaf.shape[moe_dim] % _axis_size(mesh, "model") == 0:
            spec[moe_dim] = "model"
            return P(*spec)
        return P()
    spec = [None] * ndim
    for dim, ax in rules.items():
        d = dim + extra
        if d < ndim and leaf.shape[d] % _axis_size(mesh, ax) == 0:
            spec[d] = ax
    return P(*spec)


def param_specs(params_shape: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree for a params (shape) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: _spec_for(p, x, mesh), params_shape)


def param_shardings(params_shape: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_shape, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------ fleet axis
#
# The cache-sim fleet (runtime/fleet.py) stacks N replicas' EngineState
# rows along dim0 and advances them as one dispatch; over a multi-device
# mesh that dim shards over the ``fleet`` axis.  Every EngineState /
# PackedTraces leaf carries the replica-batch dim leading, so one
# PartitionSpec prefix covers the whole pytree.

FLEET_AXIS = "fleet"


def fleet_spec() -> P:
    """Pytree-prefix PartitionSpec for replica-stacked state: dim0
    (the replica/tenant-row batch) shards over the fleet axis, every
    other dim stays local to its device."""
    return P(FLEET_AXIS)


def fleet_padding(n_rows: int, mesh: Optional[Mesh] = None, *,
                  bucket: bool = True) -> int:
    """Rows of padding so a replica batch (a) buckets to a power of two
    (bounds jit recompiles as governors diverge and replica groups churn,
    same trick as ``engine._bucket`` on trace length) and (b) tiles the
    fleet mesh axis exactly (shard_map requires dim0 divisible by the
    axis size).  Padding rows are fresh ``engine.init_state`` rows fed
    empty traces — provable no-ops that are sliced off after the step."""
    assert n_rows > 0
    target = n_rows if not bucket else 1 << (n_rows - 1).bit_length()
    if mesh is not None and FLEET_AXIS in mesh.shape:
        ax = mesh.shape[FLEET_AXIS]
        target = ((target + ax - 1) // ax) * ax
    return target - n_rows

