"""Morpheus-ALL on an A100-class GPU at its published capacity
(``Morpheus-ALL@A100``): the modelled GPU as a property of the system,
both tiers of the Pallas scan set-tiled, the program against the
benchmark's plain reference, and the two per-layer readers the cell adds.

108 SMs, 40 MB of LLC: 10 240 conventional sets, more than one VMEM tile
of the scan holds, so the conventional tier runs over several set tiles
too; at 81 cache-mode SMs the extended tier has 7 695 sets.
"""
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro import obs
from repro.core import cache_sim as cs
from repro.core import controller as ctl
from repro.core import engine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness, reference  # noqa: E402
from chipbench.kinds import sweep  # noqa: E402

CONFIGS = ROOT / "chipbench" / "configs"
A100 = json.loads((CONFIGS / "morpheus-all-a100.json").read_text())
LIMITS = json.loads((ROOT / "chipbench" / "traffic"
                     / "split27.json").read_text())["limits"]
SYSTEM = "Morpheus-ALL@A100"


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


def test_a100_geometry_and_tiles():
    spec = cs.SYSTEMS[SYSTEM]
    assert spec.gpu is cs.A100 and spec.sim_scale == A100["sim_scale"] == 1
    cfg = cs.build_config(spec, 81)
    assert (cfg.amap.conv_sets, cfg.amap.ext_sets, cfg.ext_max_ways) == \
        (10_240, 7_695, 128)
    # 17 935 sets: the pack's set key still fits 16 bits
    assert cfg.amap.total_sets < 2**16
    assert engine.set_tiling(cfg) == ((1280, 8), (512, 16))
    geo = reference.geometry(A100, 81)
    assert (geo.conv_sets, geo.ext_sets, geo.ext_max_ways) == \
        (cfg.amap.conv_sets, cfg.amap.ext_sets, cfg.ext_max_ways)
    flags = A100["flags"]
    assert (spec.morpheus, spec.compression, spec.indirect_mov,
            spec.predictor.value, spec.conv_scale, spec.mem_boost) == \
        (flags["morpheus"], flags["compression"], flags["indirect_mov"],
         flags["predictor"], flags["conv_scale"], flags["mem_boost"])


@pytest.mark.parametrize("config,system", [
    ("morpheus-all-a100", SYSTEM),
    ("morpheus-all-fullscale", "Morpheus-ALL@1"),
    ("morpheus-all", "Morpheus-ALL"),
])
def test_gpu_spec_equals_config_gpu_block(config, system):
    """Every field of the program's ``GPUSpec`` (its name aside), and
    every time-model constant all GPUs share, equals the same key of the
    configuration's ``gpu`` block, which the reference reads: the two
    cannot drift apart."""
    block = json.loads((CONFIGS / f"{config}.json").read_text())["gpu"]
    gpu = cs.SYSTEMS[system].gpu
    for f in dataclasses.fields(gpu):
        if f.name != "name":
            assert getattr(gpu, f.name) == block[f.name], f.name
    for key in ("ipc_per_core", "mlp_per_core", "conv_ways", "ext_ways",
                "bw_ext_core", "indirect_mov_ext_bw_gain", "max_cache_frac"):
        assert getattr(cs, key.upper()) == block[key], key
    assert gpu.ext_sets_per_core == block["ext_bytes_per_core"] // (
        block["ext_ways"] * block["block_bytes"])


@pytest.mark.parametrize("gpu", [cs.TABLE1, cs.A100], ids=lambda g: g.name)
def test_llc_and_noc_outrun_dram(gpu):
    """The modelled LLC is faster than the memory behind it, and the NoC
    carries at least the LLC's bandwidth."""
    assert gpu.bw_dram < gpu.bw_conv < gpu.bw_noc


def test_a100_split_is_the_75_percent_cap():
    from repro.core import policy
    traffic = harness.load_json(harness.HERE / "traffic" / "split27.json")
    want = [(p.app, p.n_compute, p.n_cache) for app in traffic["apps"]
            for p in policy.grid_points(app, SYSTEM, grid=traffic["grid"],
                                        length=traffic["length"])]
    assert sweep.points(A100, traffic) == want
    assert {(nc, nk) for _, nc, nk in want} == {(27, 81)} and len(want) == 14


# the cell's split, and a smaller cache-mode share
POINTS = [("kmeans", 27, 81), ("histo", 27, 81), ("spmv", 60, 48)]


@pytest.fixture(scope="module")
def a100_run():
    """One ``run_batch`` of the three points with spans and counters on:
    (results, the ``cache_sim.run_batch`` span's tags, counters)."""
    obs.enable(trace=True, metrics=True)
    try:
        results = cs.run_batch([cs.RunPoint(app, SYSTEM, nc, nk, 20_000, 11,
                                            "jnp") for app, nc, nk in POINTS])
        top, = [e for e in obs.tracer().events
                if e["ph"] == "X" and e["name"] == "cache_sim.run_batch"]
        counts = _counters()
    finally:
        obs.disable()
    return results, top["args"], counts


@pytest.mark.parametrize("i", range(len(POINTS)))
def test_a100_equals_reference(a100_run, i):
    """The program at ``Morpheus-ALL@A100`` against the benchmark's plain
    reference at the A100 configuration: integer Stats exact, float Stats,
    time and IPC within the cell's limit."""
    app, nc, nk = POINTS[i]
    want = reference.run_point(A100, app, nc, nk, 20_000, 11)
    nums = sweep.compare([sweep.answer(a100_run[0][i])], [want])
    assert nums["stats_mismatch"] == 0
    assert nums["float_rel_err"] < LIMITS["float_rel_err"]


def test_run_batch_tags_the_modelled_gpu(a100_run):
    _, tags, counts = a100_run
    assert tags["gpu"] == "A100" and tags["points"] == len(POINTS)
    # two configurations (81 and 48 cache-mode SMs), one dispatch each,
    # and each dispatch's two tiers transferred
    assert counts[("engine_dispatches", (("path", "batch"),))] == 2
    assert counts[("device_put_bytes", (("tier", "conv"),))] > 0
    assert counts[("device_put_bytes", (("tier", "ext"),))] > 0


def test_set_tiled_pallas_scan_equals_jnp(_obs_off):
    """The Pallas scan (interpret mode) against the jnp engine, bit for bit
    in the final state and the integer Stats, on a trace over every set of
    both tiers: 8 conventional and 16 extended set tiles, which the
    dispatch counts."""
    cfg = cs.build_config(cs.SYSTEMS[SYSTEM], 81)
    total = cfg.amap.total_sets
    rng = np.random.default_rng(5)
    # three tags per set, in a random order: misses, hits, evictions of
    # compressed blocks, Bloom inserts, on every set of both tiers
    addrs = rng.permutation(np.arange(3 * total, dtype=np.uint32))
    addrs = np.concatenate([addrs, rng.choice(addrs, 2 * total)])
    writes = rng.random(len(addrs)) < 0.3
    levels = rng.integers(0, 3, len(addrs)).astype(np.int32)
    trace = (addrs, writes, levels, total)
    pt = engine.pack(cfg, [trace])
    (tc, nc), (te, ne) = engine.set_tiling(cfg)
    assert pt.conv_tag.shape[1] == tc * nc == cfg.amap.conv_sets
    assert pt.ext_tag.shape[1] == te * ne > cfg.amap.ext_sets
    assert pt.conv_active[0].any(axis=1).all()
    assert pt.ext_active[0].any(axis=1)[:cfg.amap.ext_sets].all()
    assert pt.conv_tag.shape[2] <= 64 and pt.ext_tag.shape[2] <= 64

    state = engine.init_state(cfg, 1)
    s_jnp, d_jnp = engine.advance_packed(cfg, pt, state, "jnp")
    obs.enable(trace=False, metrics=True)
    s_pal, d_pal = engine.advance_packed(cfg, pt, state, "pallas")
    counts = _counters()
    assert counts[("scan_set_tiles", (("tier", "conv"),))] == nc == 8
    assert counts[("scan_set_tiles", (("tier", "ext"),))] == ne == 16
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
    assert int(d_jnp.conv_hits[0]) > 0 and int(d_jnp.ext_hits[0]) > 0


# ------------------------------------------------ the cell's two readers

def _reader(name):
    return harness.load_module(harness.METRICS_DIR / f"{name}.py",
                               "test_reader_" + name.replace(".", "_"))


def _ctx(counters, kernels=None, busy_s=1.0, points=14):
    kernels = kernels or {}
    trace = SimpleNamespace(busy_s=busy_s, kernel_s=lambda p: sum(
        s for n, s in kernels.items() if n.startswith(p)))
    return SimpleNamespace(
        work={"points": points}, trace=trace,
        counter=lambda name, **labels: sum(
            v for (n, lab), v in counters.items()
            if n == name and all(dict(lab).get(k) == x
                                 for k, x in labels.items())))


PUT = {("device_put_bytes", (("tier", "conv"),)): 838_860_800,
       ("device_put_bytes", (("tier", "ext"),)): 469_762_048}
TIERS = {("tier_requests", (("tier", "conv"),)): 7_900_000,
         ("tier_requests", (("tier", "ext"),)): 5_540_000}
KERNELS = {"engine_scan_conv_slot": 0.079, "engine_scan_ext_slot": 0.4}


def test_put_mb_per_point_sums_both_tiers():
    got = _reader("put_mb_per_point.sweep").read(_ctx(PUT))
    assert got == pytest.approx((838_860_800 + 469_762_048) / 1e6 / 14)


def test_conv_ns_per_req_reads_the_conventional_tier_only():
    got = _reader("conv_ns_per_req.sweep").read(_ctx(TIERS, KERNELS))
    assert got == pytest.approx(0.079 * 1e9 / 7_900_000)


@pytest.mark.parametrize("name,ctx", [
    ("put_mb_per_point.sweep", _ctx({})),
    ("put_mb_per_point.sweep", _ctx(PUT, busy_s=0.0)),
    ("conv_ns_per_req.sweep", _ctx(TIERS)),
    ("conv_ns_per_req.sweep", _ctx({}, KERNELS)),
])
def test_readers_give_nothing_without_their_source(name, ctx):
    """A program without the counter, a trace without the kernel, or one
    without a device plane (a CPU backend) gives no number and raises
    nothing."""
    assert _reader(name).read(ctx) is None
