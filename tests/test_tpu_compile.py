"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler is installed next to JAX, so a chip that is described
(``v5e:2x2``) but not attached still compiles — and refuses — exactly what
the real chip would.  Interpret mode cannot show this: these tests are what
keeps the engine scan, the serving pool's kernels and the fleet's
four-chip ``shard_map`` step lowerable through Mosaic.  Nothing runs; each
test checks that a Mosaic kernel (``tpu_custom_call``) is in the program.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU library.
"""
import numpy as np
import pytest

import jax
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import cache_sim as cs
from repro.core import engine
from repro.kernels import ops
from repro.runtime import ReplicaSpec, fleet
from repro.serving.paged_kv import PoolConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compile for the described chip, not in interpret mode (the
        # resolver sees this process's CPU backend); a compile for a
        # described device cannot be read back from the persistent cache,
        # so the cache is off; traces made in interpret mode are dropped
        mp.setattr(ops, "interpret_mode", lambda: False)
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _avals(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


def _assert_kernel(fn, *avals):
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text


# --------------------------------------------------------- engine scan

# the full benchmark profile's trace length on four systems: Morpheus-ALL
# (both tiers, compressed extended ways), the conventional-only baseline,
# Morpheus-ALL at full scale (4 182 extended sets: the set-tiled scan
# within the default scoped VMEM) and on the A100-class GPU (10 240
# conventional sets in 8 tiles, 7 695 extended sets in 16)
ENGINE_CELLS = {"morpheus_all": ("kmeans", "Morpheus-ALL", 32, 36),
                "conv_only": ("kmeans", "BL", 32, 0),
                "full_scale": ("kmeans", "Morpheus-ALL@1", 17, 51),
                "a100": ("kmeans", "Morpheus-ALL@A100", 27, 81)}


def _packed(cell):
    app, system, n_compute, n_cache = ENGINE_CELLS[cell]
    cfg, trace, *_ = cs._prepare(cs.RunPoint(app, system, n_compute,
                                             n_cache, 120_000))
    return cfg, engine.pack(cfg, [trace] * 4)


@pytest.mark.parametrize("cell", sorted(ENGINE_CELLS))
def test_engine_scan_cold_compiles(one_chip, cell):
    cfg, pt = _packed(cell)
    _assert_kernel(lambda p: engine._run_packed(cfg, p, "pallas"),
                   _avals(pt, one_chip))


@pytest.mark.parametrize("cell", sorted(ENGINE_CELLS))
def test_engine_scan_state_compiles(one_chip, cell):
    cfg, pt = _packed(cell)
    state = engine.init_state(cfg, pt.warmup.shape[0])
    _assert_kernel(lambda p, s: engine._run_packed_state(cfg, p, s, "pallas"),
                   _avals(pt, one_chip), _avals(state, one_chip))


# ---------------------------------------------------------- pool kernels

POOL = PoolConfig()
WAYS, WORDS = POOL.ways, POOL.page_words


def test_tag_lookup_compiles(one_chip):
    s = POOL.conv_sets
    _assert_kernel(ops.tag_lookup, *_avals(
        (np.zeros((s, WAYS), np.uint32), np.zeros((s, WAYS), bool),
         np.zeros((s, WAYS), np.uint32), np.zeros((s,), np.uint32)),
        one_chip))


@pytest.mark.parametrize("q", [1, 600])
def test_bloom_query_compiles(one_chip, q):
    _assert_kernel(ops.bloom_query, *_avals(
        (np.zeros((q, POOL.bloom_words), np.uint32),
         np.zeros((q,), np.uint32)), one_chip))


def test_bdi_compress_compiles(one_chip):
    _assert_kernel(ops.bdi_compress,
                   _avals(np.zeros((1, WORDS), np.uint32), one_chip))


@pytest.mark.parametrize("n", [3, 300])
def test_bdi_decompress_compiles(one_chip, n):
    _assert_kernel(ops.bdi_decompress, *_avals(
        (np.zeros((n,), np.int32), np.zeros((n,), np.uint32),
         np.zeros((n, WORDS), np.uint32)), one_chip))


@pytest.mark.parametrize("n", [5, 70])
def test_gather_blocks_compiles(one_chip, n):
    _assert_kernel(ops.gather_blocks, *_avals(
        (np.zeros((n, WAYS, WORDS), np.uint32), np.zeros((n,), np.int32)),
        one_chip))


# ------------------------------------------------- fleet over four chips

def test_fleet_shard_map_step_compiles(topo):
    """The ``("fleet",)`` shard_map group step over the 2x2 host."""
    mesh = Mesh(np.array(topo.devices), ("fleet",),
                axis_types=(AxisType.Auto,))
    reps = [ReplicaSpec(app, "Morpheus-ALL", length=24_000,
                        epoch_len=3_000, seed=i).build()
            for i, app in enumerate(("kmeans", "cfd", "stencil", "kmeans"))]
    inputs = [r.epoch_inputs() for r in reps]
    cfg = inputs[0][0]
    assert all(c == cfg for c, *_ in inputs)
    pt = engine.pack(cfg, [t for _, ts, _, _ in inputs for t in ts],
                     pos0=[p for _, _, ps, _ in inputs for p in ps])
    step = fleet._group_step(cfg, "pallas", mesh, (1,) * len(reps), 0)
    text = step.lower(
        tuple(_avals(r.state, NamedSharding(mesh, PartitionSpec()))
              for r in reps),
        _avals(pt, NamedSharding(mesh, PartitionSpec("fleet")))
    ).compile().as_text()
    assert "tpu_custom_call" in text
