"""``run_batch`` makes its traces ahead on a thread pool: the results do
not depend on the pool's width, a generator's error reaches the caller
unchanged, and the width follows the call's points, streams and CPUs."""
import numpy as np
import pytest

from repro import obs
from repro.core import cache_sim as cs


def _points():
    """Two configurations, so two dispatches: a chunk of three padded to
    four that holds the Unified-SM-Mem point (its config is BL's, its
    trace filtered), and one Morpheus-ALL point."""
    return [cs.RunPoint("cfd", "BL", n, 0, 3000, seed=2) for n in
            (10, 14)] + \
        [cs.RunPoint("kmeans", "Morpheus-ALL", 32, 24, 3000, seed=5),
         cs.RunPoint("histo", "Unified-SM-Mem", 20, 0, 3000, seed=7)]


def _at_width(monkeypatch, width, points):
    monkeypatch.setattr(cs, "_prefetch_width",
                        lambda n, per_stream, cpus: min(width, n))
    return cs.run_batch(points)


@pytest.fixture(scope="module")
def narrow():
    """The results at width 1: one pool thread makes every trace."""
    with pytest.MonkeyPatch.context() as mp:
        return _at_width(mp, 1, _points())


@pytest.mark.parametrize("width", [2, 3, 5])
def test_results_identical_at_any_width(monkeypatch, narrow, width):
    pts = _points()
    got = _at_width(monkeypatch, width, pts)
    assert [(r.app, r.system, r.n_compute, r.n_cache) for r in got] == \
        [(r.app, r.system, r.n_compute, r.n_cache) for r in narrow]
    for a, b in zip(narrow, got):
        for f in a.stats._fields:
            x, y = np.asarray(getattr(a.stats, f)), \
                np.asarray(getattr(b.stats, f))
            assert x.dtype == y.dtype and np.array_equal(x, y), f
        assert (a.exec_time_s, a.ipc, a.perf_per_watt, a.energy_J) == \
            (b.exec_time_s, b.ipc, b.perf_per_watt, b.energy_J)


def test_results_match_one_point_at_a_time(narrow):
    """Each point alone, through ``run``, takes no pool and gives the
    sweep's result."""
    for pt, r in zip(_points(), narrow):
        one = cs.run(pt.app, pt.system, n_compute=pt.n_compute,
                     n_cache=pt.n_cache, length=pt.length, seed=pt.seed)
        assert (one.exec_time_s, one.ipc) == (r.exec_time_s, r.ipc)


def test_unknown_app_raises_as_the_serial_prepare(monkeypatch):
    bad = cs.RunPoint("no-such-app", "BL", 10, 0, 3000)
    with pytest.raises(KeyError) as serial_err:
        cs._prepare(bad)
    with pytest.raises(KeyError) as err:
        _at_width(monkeypatch, 3, _points() + [bad])
    assert err.value.args == serial_err.value.args


def test_pool_error_reaches_the_caller_unchanged(monkeypatch):
    """An exception raised in ``_generate`` on a pool thread is the one
    ``run_batch`` raises, and no dispatch packs the failed chunk."""
    boom = RuntimeError("generator failed")
    real = cs._generate

    def failing(pt, n_compute):
        if pt.app == "kmeans":
            raise boom
        return real(pt, n_compute)
    monkeypatch.setattr(cs, "_generate", failing)
    obs.enable(trace=False)
    try:
        with pytest.raises(RuntimeError) as err:
            _at_width(monkeypatch, 3, _points())
        dispatches = obs.metrics_registry().get("engine_dispatches").total()
    finally:
        obs.disable()
    assert err.value is boom
    assert dispatches == 1          # the BL chunk ran; kmeans' never packed


@pytest.mark.parametrize("n_points, per_stream, cpus, want", [
    (1, 10**6, 64, 1),            # one point (it takes no pool)
    (0, 0, 8, 1),                 # nothing to do
    (126, 120_000 // 32, 64, 1),  # 1/8-scale sweep: short streams, capped
    (126, 120_000 // 10, 64, 1),  # its longest stream
    (14, 960_000 // 17, 64, 6),   # full scale: 56 k requests a stream
    (14, 960_000 // 17, 4, 4),    # long streams widen up to the CPUs
    (3, 10**7, 64, 3),            # never more threads than points
])
def test_prefetch_width_rule(n_points, per_stream, cpus, want):
    assert cs._prefetch_width(n_points, per_stream, cpus) == want


def test_prefetch_width_grows_with_the_stream():
    widths = [cs._prefetch_width(100, s, 64) for s in
              range(0, 200_000, 1_000)]
    assert widths == sorted(widths)
    assert widths[0] == 1 and widths[-1] > 2


def _prefetch_counts(points):
    obs.enable()
    try:
        cs.run_batch(points)
        reg = obs.metrics_registry()
        counts = dict(reg.get("trace_prefetch").values)
        dispatches = reg.get("engine_dispatches").total()
        top, = [e for e in obs.tracer().events
                if e["name"] == "cache_sim.run_batch"]
    finally:
        obs.disable()
    return counts, dispatches, top["args"]["workers"]


def test_trace_prefetch_counts_one_per_dispatch(monkeypatch):
    monkeypatch.setattr(cs, "_prefetch_width", lambda n, s, c: min(n, 3))
    counts, dispatches, workers = _prefetch_counts(_points())
    assert dispatches == 2 and workers == 3
    assert sum(counts.values()) == dispatches
    assert set(counts) <= {(("ready", "yes"),), (("ready", "no"),)}


def test_one_point_takes_no_pool(monkeypatch):
    """``run`` and every single-point call make the trace on the calling
    thread: no pool thread starts, and the one dispatch waited."""
    import threading
    started = []
    real = threading.Thread.start

    def start(self):
        started.append(self.name)
        return real(self)
    monkeypatch.setattr(threading.Thread, "start", start)
    counts, dispatches, workers = _prefetch_counts(_points()[:1])
    assert started == []
    assert dispatches == 1 and workers == 1
    assert counts == {(("ready", "no"),): 1}
