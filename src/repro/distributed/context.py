"""Ambient distribution context.

A caller installs the active mesh here (``use_mesh``); layers whose
optimal implementation is an explicit shard_map (today: the MoE dispatch)
pick it up.  When no mesh is installed (the launchers, unit tests,
single-host examples) layers use their pure-jnp path — the two paths are
numerically identical (tests/test_perf_rewrites.py).
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from jax.sharding import Mesh

_ACTIVE_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


@contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield
    finally:
        set_mesh(prev)
