"""The Table-1 GPU at its published capacity (``Morpheus-ALL@1``): the scale
as a property of the modelled system, the set-tiled Pallas scan, and the
nine 1/8-scale systems left as they were, each system's tier shapes on the
jnp engine, the Pallas engine and the serial oracle.

At ``sim_scale`` 1 the extended tier of 51 cache-mode SMs has 4 182 sets,
more than one VMEM tile of the scan holds, so the scan runs over several
set tiles; the 1/8-scale systems' tiers stay one tile.
"""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import cache_sim as cs
from repro.core import controller as ctl
from repro.core import engine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reference  # noqa: E402
from chipbench.kinds import sweep  # noqa: E402

FULL = json.loads((ROOT / "chipbench" / "configs"
                   / "morpheus-all-fullscale.json").read_text())
LIMITS = json.loads((ROOT / "chipbench" / "traffic"
                     / "split17.json").read_text())["limits"]


@pytest.fixture
def _obs_off():
    obs.disable()
    yield
    obs.disable()


def _counters():
    snap = obs.metrics_registry().snapshot()
    return {(m["name"], tuple(sorted(s["labels"].items()))): s["value"]
            for m in snap["metrics"] if m["kind"] == "counter"
            for s in m["samples"]}


def test_full_scale_geometry_and_tiles():
    cfg = cs.build_config(cs.SYSTEMS["Morpheus-ALL@1"], 51)
    assert (cfg.amap.conv_sets, cfg.amap.ext_sets, cfg.ext_max_ways) == \
        (1280, 4182, 128)
    (tc, nc), (te, ne) = engine.set_tiling(cfg)
    assert (tc, nc) == (1280, 1)
    assert ne >= 2 and te % 128 == 0 and te * ne >= 4182 > te * (ne - 1)
    geo = reference.geometry(FULL, 51)
    assert (geo.conv_sets, geo.ext_sets, geo.ext_max_ways) == \
        (cfg.amap.conv_sets, cfg.amap.ext_sets, cfg.ext_max_ways)
    spec = cs.SYSTEMS["Morpheus-ALL@1"]
    flags = FULL["flags"]
    assert (spec.sim_scale, spec.morpheus, spec.compression,
            spec.indirect_mov) == (FULL["sim_scale"], flags["morpheus"],
                                   flags["compression"],
                                   flags["indirect_mov"])


# the full-scale split of the cell, and a smaller cache-mode share
POINTS = [("kmeans", 17, 51), ("histo", 17, 51), ("spmv", 40, 28)]


@pytest.fixture(scope="module")
def full_scale_results():
    return cs.run_batch([cs.RunPoint(app, "Morpheus-ALL@1", nc, nk, 20_000,
                                     11, "jnp") for app, nc, nk in POINTS])


@pytest.mark.parametrize("i", range(len(POINTS)))
def test_full_scale_equals_reference(full_scale_results, i):
    """The program at ``Morpheus-ALL@1`` against the benchmark's plain
    reference at the full-scale configuration: integer Stats exact, float
    Stats, time and IPC within the cell's limit."""
    app, nc, nk = POINTS[i]
    want = reference.run_point(FULL, app, nc, nk, 20_000, 11)
    nums = sweep.compare([sweep.answer(full_scale_results[i])], [want])
    assert nums["stats_mismatch"] == 0
    assert nums["float_rel_err"] < LIMITS["float_rel_err"]


def test_set_tiled_pallas_scan_equals_jnp(_obs_off):
    """The set-tiled Pallas scan (interpret mode) against the jnp engine,
    bit for bit in the final state and the integer Stats, on a trace over
    every one of the 4 182 extended sets: a short scan that spans all of
    the tier's tiles.  ``engine.pack`` counts the requests of each tier and
    the Pallas dispatch its set tiles."""
    cfg = cs.build_config(cs.SYSTEMS["Morpheus-ALL@1"], 51)
    total = cfg.amap.total_sets
    rng = np.random.default_rng(5)
    # three tags per set, in a random order: misses, hits, evictions of
    # compressed blocks, Bloom inserts, on every set of both tiers
    addrs = rng.permutation(np.arange(3 * total, dtype=np.uint32))
    addrs = np.concatenate([addrs, rng.choice(addrs, 2 * total)])
    writes = rng.random(len(addrs)) < 0.3
    levels = rng.integers(0, 3, len(addrs)).astype(np.int32)
    trace = (addrs, writes, levels, total)
    obs.enable(trace=False, metrics=True)
    pt = engine.pack(cfg, [trace])
    counts = _counters()
    assert counts[("tier_requests", (("tier", "conv"),))] + \
        counts[("tier_requests", (("tier", "ext"),))] == len(addrs)
    assert counts[("tier_requests", (("tier", "ext"),))] == \
        int((addrs % total >= cfg.amap.conv_sets).sum())
    (_, nc), (te, ne) = engine.set_tiling(cfg)
    assert pt.ext_tag.shape[1] == te * ne > cfg.amap.ext_sets
    assert not pt.ext_active[:, cfg.amap.ext_sets:].any()
    assert pt.ext_active[0].any(axis=1)[:cfg.amap.ext_sets].all()
    assert pt.ext_tag.shape[2] <= 64

    state = engine.init_state(cfg, 1)
    s_jnp, d_jnp = engine.advance_packed(cfg, pt, state, "jnp")
    assert ("scan_set_tiles", (("tier", "ext"),)) not in _counters()
    s_pal, d_pal = engine.advance_packed(cfg, pt, state, "pallas")
    counts = _counters()
    assert counts[("scan_set_tiles", (("tier", "conv"),))] == nc == 1
    assert counts[("scan_set_tiles", (("tier", "ext"),))] == ne
    for f in engine.EngineState._fields:
        if f == "stats":
            continue
        a, b = np.asarray(getattr(s_jnp, f)), np.asarray(getattr(s_pal, f))
        assert a.shape == b.shape and np.array_equal(a, b), f
    for f in ctl.Stats._fields:
        a, b = np.asarray(getattr(d_jnp, f)), np.asarray(getattr(d_pal, f))
        if f in ctl._INT_FIELDS:
            assert np.array_equal(a, b), f
        else:
            np.testing.assert_allclose(b, a, rtol=1e-6, err_msg=f)
    assert int(d_jnp.ext_hits[0]) > 0 and int(d_jnp.conv_hits[0]) > 0


# Stats of one kmeans point (24 compute, 44 cache-mode SMs, 12 000
# requests, trace seed 7) on each 1/8-scale system, as the program gave
# them before the scale became a property of the system: (conv_hits,
# conv_misses, ext_hits, ext_false_pos, ext_pred_miss, ext_true_miss,
# dram_accesses, writebacks, bloom_swaps), latency_ns, energy_nJ
PINNED = {
    "BL": ((833, 5167, 0, 0, 0, 0, 5167, 314, 0), 3274816.0, 126946.546875),
    "IBL": ((833, 5167, 0, 0, 0, 0, 5167, 314, 0), 3274816.0, 126946.546875),
    "IBL-4x-LLC": ((1249, 4751, 0, 0, 0, 0, 4751, 0, 0), 3088448.0,
                   111061.765625),
    "Frequency-Boost": ((833, 5167, 0, 0, 0, 0, 5167, 314, 0), 3274816.0,
                        126946.546875),
    "Unified-SM-Mem": ((708, 5162, 0, 0, 0, 0, 5162, 307, 0), 3251776.0,
                       126519.03125),
    "Morpheus-Basic": ((341, 1274, 908, 27, 3450, 3477, 4751, 0, 0),
                       3220023.0, 114096.328125),
    "Morpheus-Compression": ((341, 1274, 908, 27, 3450, 3477, 4751, 0, 0),
                             3229103.0, 114096.328125),
    "Morpheus-Indirect-MOV": ((341, 1274, 908, 27, 3450, 3477, 4751, 0, 0),
                              3182623.0, 114096.328125),
    "Morpheus-ALL": ((341, 1274, 908, 27, 3450, 3477, 4751, 0, 0),
                     3191703.0, 114096.328125),
}
INTS = ("conv_hits", "conv_misses", "ext_hits", "ext_false_pos",
        "ext_pred_miss", "ext_true_miss", "dram_accesses", "writebacks",
        "bloom_swaps")


# the same kmeans point on the full-scale system, as the program gave it
# when all three paths below first agreed
PINNED_FULL = ((45, 1479, 122, 2, 4352, 4354, 5833, 0, 0), 3586854.0,
               131544.0)

# the jnp and the Pallas engine (one ``run_batch`` each), and the serial
# oracle ``controller.simulate_jit`` on the trace ``_prepare`` makes
PATHS = ("jnp", "pallas", "serial")


def _kmeans_stats(path):
    points = [cs.RunPoint("kmeans", n, 24, 44, 12_000, 7,
                          "" if path == "serial" else path)
              for n in cs.SYSTEMS]
    if path != "serial":
        return dict(zip(cs.SYSTEMS, (r.stats for r in cs.run_batch(points))))
    out = {}
    for name, pt in zip(cs.SYSTEMS, points):
        cfg, (addrs, writes, levels, warmup), *_ = cs._prepare(pt)
        out[name] = ctl.simulate_jit(cfg, jnp.asarray(addrs),
                                     jnp.asarray(writes),
                                     jnp.asarray(levels), warmup)
    return out


@pytest.fixture(scope="module")
def kmeans_stats():
    """The kmeans point's Stats on every system by one path, each path
    computed once for the module."""
    done = {}

    def get(path):
        if path not in done:
            done[path] = _kmeans_stats(path)
        return done[path]
    return get


def _assert_pinned(stats, pinned, path):
    ints, latency, energy = pinned
    assert tuple(int(np.asarray(getattr(stats, f))) for f in INTS) == ints
    # the serial oracle sums the float Stats request by request, the
    # engines set by set: they agree within the benchmark's float limit
    rtol = LIMITS["float_rel_err"] if path == "serial" else 1e-6
    np.testing.assert_allclose(float(stats.latency_ns), latency, rtol=rtol)
    np.testing.assert_allclose(float(stats.energy_nJ), energy, rtol=rtol)


def test_only_the_full_scale_system_is_new():
    assert set(cs.SYSTEMS) == set(PINNED) | {"Morpheus-ALL@1",
                                             "Morpheus-ALL@A100"}
    assert cs.SYSTEMS["Morpheus-ALL@1"].sim_scale == 1
    assert cs.SYSTEMS["Morpheus-ALL@A100"].sim_scale == 1
    # every system but the A100 one models the Table-1 GPU
    assert {n for n, s in cs.SYSTEMS.items() if s.gpu != cs.TABLE1} == \
        {"Morpheus-ALL@A100"}


# the jnp engine's cases are named by the system alone
@pytest.mark.parametrize("name,path", [
    pytest.param(n, p, id=n if p == "jnp" else f"{n}-{p}")
    for p in PATHS for n in PINNED])
def test_eighth_scale_systems_keep_scale_and_stats(kmeans_stats, name,
                                                   path):
    assert cs.SYSTEMS[name].sim_scale == 8
    _assert_pinned(kmeans_stats(path)[name], PINNED[name], path)
    cfg = cs.build_config(cs.SYSTEMS[name], 44)
    assert all(tiles == 1 for _, tiles in engine.set_tiling(cfg))


@pytest.mark.parametrize("path", PATHS)
def test_full_scale_system_keeps_stats(kmeans_stats, path):
    """``Morpheus-ALL@1`` at the 1/8-scale systems' kmeans point: its
    extended tier spans several set tiles of the scan."""
    _assert_pinned(kmeans_stats(path)["Morpheus-ALL@1"], PINNED_FULL, path)
    cfg = cs.build_config(cs.SYSTEMS["Morpheus-ALL@1"], 44)
    assert engine.set_tiling(cfg)[1][1] > 1
