"""The plain reference against the program, on the CPU at small sizes."""
import json

import numpy as np
import pytest

from chipbench import control, harness, reference, tracegen
from chipbench.kinds import sweep

CONFIGS = {n: harness.load_json(harness.HERE / "configs" / f"{n}.json")
           for n in ("morpheus-all", "ibl-4x-llc")}


@pytest.mark.parametrize("app", ["kmeans", "cfd", "histo", "sgem"])
@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_trace_copy_equals_program(app, seed):
    from repro.workloads import synthetic
    got = synthetic.generate(app, n_cores=24, length=6000, seed=seed,
                             ws_scale=1 / 8)
    want = tracegen.generate(app, n_cores=24, length=6000, seed=seed,
                             ws_scale=1 / 8)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)


def test_app_profiles_equal_program():
    from repro.workloads import synthetic
    for name, spec in tracegen.load_apps().items():
        assert synthetic.WORKLOADS[name].__dict__ == dict(spec, name=name)


POINTS = [("morpheus-all", "kmeans", 24, 44), ("morpheus-all", "histo", 10, 51),
          ("morpheus-all", "lbm", 62, 6), ("ibl-4x-llc", "kmeans", 32, 0),
          ("ibl-4x-llc", "nw", 68, 0)]


@pytest.fixture(scope="module")
def program_results():
    from repro.core import cache_sim
    pts = [cache_sim.RunPoint(app, CONFIGS[c]["system"], nc, nk, 12_000, 5,
                              "jnp") for c, app, nc, nk in POINTS]
    return cache_sim.run_batch(pts)


@pytest.mark.parametrize("i", range(len(POINTS)))
def test_reference_equals_program(program_results, i):
    c, app, nc, nk = POINTS[i]
    want = reference.run_point(CONFIGS[c], app, nc, nk, 12_000, 5)
    got = sweep.answer(program_results[i])
    nums = sweep.compare([got], [want])
    assert nums["stats_mismatch"] == 0
    assert nums["float_rel_err"] < 1e-6


@pytest.mark.parametrize("i", range(len(POINTS)))
def test_bf16_control_fails(i):
    c, app, nc, nk = POINTS[i]
    want = reference.run_point(CONFIGS[c], app, nc, nk, 12_000, 5)
    ctrl = reference.run_point(CONFIGS[c], app, nc, nk, 12_000, 5,
                               round_floats=control.bf16)
    limit = harness.load_json(harness.HERE / "traffic" / "sweep.json")[
        "limits"]["float_rel_err"]
    assert sweep.compare([ctrl], [want])["float_rel_err"] > limit


def test_lru_saturation_ties_go_to_the_lowest_way():
    """Blocks untouched for 4095 set accesses all read 0: the victim is
    then the lowest way, not the oldest block."""
    geo = reference.geometry(CONFIGS["ibl-4x-llc"], 0)
    sets = geo.total_sets
    # fill set 0's 32 ways (tag t in way t), touch tag 1 so that tag 2 is
    # the oldest, hammer tag 0 until every other block reads 0, then miss:
    # the victim is way 1 (tag 1), which the last request misses on
    fill = [t * sets for t in range(32)]
    trace = fill + [1 * sets] + [0] * 5000 + [99 * sets, 1 * sets]
    addrs = np.array(trace, np.uint32)
    c = reference.simulate(geo, addrs, np.zeros(len(trace), bool),
                           np.full(len(trace), 2, np.int32), 0)
    assert c["conv_misses"] == 34
    from repro.core import cache_sim
    from repro.core import engine
    prog = engine.simulate_parallel(
        cache_sim.build_config(cache_sim.SYSTEMS["IBL-4x-LLC"], 0), addrs,
        np.zeros(len(trace), bool), np.full(len(trace), 2, np.int32), 0,
        backend="jnp")
    assert int(prog.conv_misses) == c["conv_misses"]
    assert int(prog.conv_hits) == c["conv_hits"]


def test_configs_name_their_system():
    from repro.core import cache_sim
    for name, conf in CONFIGS.items():
        spec = cache_sim.SYSTEMS[conf["system"]]
        flags = conf["flags"]
        assert (spec.conv_scale, spec.morpheus, spec.compression,
                spec.indirect_mov) == (flags["conv_scale"], flags["morpheus"],
                                       flags["compression"],
                                       flags["indirect_mov"]), name
        assert json.dumps(conf)  # plain data
