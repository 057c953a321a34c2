"""BENCHMARK.json against what the harness finds by name, and the work
counts the roofline shares are taken from."""
import json
import re

import pytest

from chipbench import harness, reference, roofline
from chipbench.kinds import sweep

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        conf = harness.load_json(harness.ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) == set(conf["reduced_from_source"])
    for m in BENCH["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    job = harness.resolve(cell)
    names = {m["name"] for m in job.end_to_end}
    assert names == {"setup_s", job.traffic["rate_metric"]}
    assert job.per_layer
    for m in job.per_layer:
        assert m["moves"] in names
    assert set(job.traffic["limits"]) == {"stats_mismatch", "float_rel_err"}


def test_table3_points():
    traffic = harness.load_json(harness.HERE / "traffic" / "sweep.json")
    conf = {n: harness.load_json(harness.HERE / "configs" / f"{n}.json")
            for n in ("morpheus-all", "ibl-4x-llc")}
    assert len(sweep.points(conf["morpheus-all"], traffic)) == 126
    assert len(sweep.points(conf["ibl-4x-llc"], traffic)) == 140
    assert len({k for _, _, k in sweep.points(conf["morpheus-all"],
                                               traffic)}) == 8


def test_points_equal_program_grid():
    from repro.core import policy
    traffic = harness.load_json(harness.HERE / "traffic" / "sweep.json")
    for name, system in (("morpheus-all", "Morpheus-ALL"),
                         ("ibl-4x-llc", "IBL-4x-LLC")):
        conf = harness.load_json(harness.HERE / "configs" / f"{name}.json")
        want = [(p.app, p.n_compute, p.n_cache) for app in traffic["apps"]
                for p in policy.grid_points(app, system, grid=traffic["grid"],
                                            length=traffic["length"])]
        assert sweep.points(conf, traffic) == want


def test_geometry_equals_program():
    from repro.core import cache_sim
    for name in ("morpheus-all", "ibl-4x-llc"):
        conf = harness.load_json(harness.HERE / "configs" / f"{name}.json")
        for n_cache in (0, 6, 51):
            geo = reference.geometry(conf, n_cache)
            cfg = cache_sim.build_config(cache_sim.SYSTEMS[conf["system"]],
                                         n_cache)
            assert (geo.conv_sets, geo.ext_sets, geo.ext_max_ways) == (
                cfg.amap.conv_sets, cfg.amap.ext_sets, cfg.ext_max_ways)


def test_work_bytes_from_config_and_counts_only():
    """Bytes of a pass: 6 B per request plus each point's state twice.
    They follow from the configuration and the request counts, so two
    traffics of equal size and grid give equal bytes whatever their
    traces (and whatever the packed shapes)."""
    conf = harness.load_json(harness.HERE / "configs" / "morpheus-all.json")
    geo = reference.geometry(conf, 51)
    assert (geo.conv_sets, geo.ext_sets, geo.ext_max_ways) == (160, 510, 128)
    per_set = 128 * 14 + 4 + 4 + 64
    assert roofline.state_bytes(geo) == 160 * 32 * 10 + 510 * per_set
    assert roofline.sweep_bytes(120_000, roofline.state_bytes(geo)) == \
        120_000 * 6 + 2 * roofline.state_bytes(geo)
    traffic = harness.load_json(harness.HERE / "traffic" / "sweep.json")
    a = sweep.Runner.work(_Shell(conf, traffic), 3)
    b = sweep.Runner.work(_Shell(conf, dict(traffic, rounds=5,
                                            apps=traffic["apps"][::-1])), 3)
    assert a == b and a["requests"] == 3 * 126 * 120_000


class _Shell:
    """A Runner's data without the program."""

    def __init__(self, config, traffic):
        self.config, self.traffic = config, traffic
        self.points = sweep.points(config, traffic)


def test_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v6 lite")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rounds_drawn_from_seed_and_pool(cell):
    job = harness.resolve(cell)
    seeds = job.pool["trace_seeds"]
    big = 2**31 + 977
    a = sweep.rounds(job.traffic, big, job.pool)
    assert a == sweep.rounds(job.traffic, big, job.pool)
    assert len(set(a)) == len(a) == job.traffic["rounds"]
    assert set(a) <= set(seeds)
    drawn = {tuple(sweep.rounds(job.traffic, s, job.pool)) for s in range(20)}
    assert len(drawn) > 10
    with pytest.raises(FileNotFoundError):
        sweep.rounds(job.traffic, big, None)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_pool_rounds_pad_alike(cell):
    """Two of the pool's rounds give the dispatch shapes the pool names,
    so every run of the cell does the same padded work."""
    from chipbench import pools
    job = harness.resolve(cell)
    assert len(job.pool["trace_seeds"]) >= 12
    for s in job.pool["trace_seeds"][:2]:
        assert pools.shapes(job.config, job.traffic, s) == \
            [tuple(x) for x in job.pool["shapes"]]
