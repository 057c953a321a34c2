"""A whole run of each cell, rehearsed on the CPU at a tiny size, and the
same run with the timed path broken underneath: ``correct`` must fail."""
import argparse
import copy
import time

import numpy as np
import pytest

from chipbench import harness

CELLS = ["morpheus-all.sweep", "ibl-4x-llc.sweep"]


@pytest.fixture(autouse=True, scope="module")
def scratch_compile_cache(tmp_path_factory):
    """The rehearsal's compilation cache goes to a scratch directory, not
    the checkout's (CPU executables have no place among the chip's)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "CACHE_DIR", tmp_path_factory.mktemp("jax_cache"))
    yield
    mp.undo()
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def tiny(workload):
    """The cell as BENCHMARK.json has it, with traffic small enough for
    the CPU: two apps, two grid entries, short traces, every point
    checked."""
    job = harness.resolve(workload)
    job.traffic = dict(copy.deepcopy(job.traffic), apps=["kmeans", "cfd"],
                       grid=[24, 48], length=3000, reference_sample=8)
    return job


def run(workload, trace=0, seed=2**31 + 3):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.01,
                              trace=trace)
    return harness.run(args, time.perf_counter(), require_tpu=False,
                       job=tiny(workload))


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal(workload):
    out = run(workload)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 4 == 0
    assert set(out["metrics"]) == {"setup_s", "sweep_req_per_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["stats_mismatch"] == {"value": 0, "limit": 0}


def test_rehearsal_traced():
    out = run(CELLS[0], trace=1)
    assert out["correct"] is True
    # a CPU trace has no TPU plane: the device readers find nothing, the
    # counter reader still reads
    assert set(out["metrics"]) == {"points_per_dispatch.sweep"}
    assert out["metrics"]["points_per_dispatch.sweep"]["value"] > 0
    assert out["device"]["window_s"] > 0
    assert "device_ops" in out["breakdown"]


READERS = {
    # a counter the sweep never touched before: the program counts it here
    "epochs_in_window.sweep": 'def read(ctx):\n'
    '    return ctx.counter("epochs", path="online") or None\n',
    # a span of the program's own
    "run_batch_s_per_pass.sweep": 'def read(ctx):\n'
    '    s = ctx.span("cache_sim.run_batch")\n'
    '    return s.total_s / s.count if s.count else None\n',
}


def test_new_reader_needs_no_harness_change(monkeypatch, tmp_path):
    """A per-layer metric is a reader file and an entry: it reads any of
    the program's counters and spans in the window by name."""
    import shutil
    from repro import obs
    from repro.core import cache_sim
    for f in harness.METRICS_DIR.glob("*.py"):
        shutil.copy(f, tmp_path / f.name)
    for name, src in READERS.items():
        (tmp_path / f"{name}.py").write_text(src)
    monkeypatch.setattr(harness, "METRICS_DIR", tmp_path)
    real = cache_sim.run_batch

    def counted(points):
        obs.count("epochs", 1, path="online")
        return real(points)
    monkeypatch.setattr(cache_sim, "run_batch", counted)
    job = tiny(CELLS[0])
    job.per_layer = job.per_layer + [
        {"name": n, "unit": "x"} for n in READERS]
    args = argparse.Namespace(workload=CELLS[0], seed=11, seconds=0.01,
                              trace=1)
    out = harness.run(args, time.perf_counter(), require_tpu=False, job=job)
    window_passes = out["attempted"] // 4
    got = out["metrics"]
    assert got["epochs_in_window.sweep"]["value"] == window_passes
    assert got["run_batch_s_per_pass.sweep"]["value"] > 0
    assert got["points_per_dispatch.sweep"]["value"] > 0


def test_window_alternates_rounds():
    """Warm-up runs each round once; the window takes them in turn, and
    each sampled answer is checked on its own pass's traces."""
    job = tiny(CELLS[0])
    runner = job.kind.Runner(job.config, job.traffic, 2**31 + 5, job.pool)
    runner.warm()
    passes = [runner.run_pass() for _ in range(3)]
    r = runner.rounds
    assert len(set(r)) == 2 and [p.trace_seed for p in passes] == r + r[:1]
    numbers, failed = runner.check(passes)
    assert numbers["stats_mismatch"] == 0 and failed == 0
    swapped = [p._replace(trace_seed=r[1] if p.trace_seed == r[0] else r[0])
               for p in passes]
    assert runner.check(swapped)[0]["stats_mismatch"] > 0


def test_no_tpu_refused():
    args = argparse.Namespace(workload=CELLS[0], seed=1, seconds=1, trace=0)
    with pytest.raises(harness.NoChip):
        harness.run(args, time.perf_counter(), job=tiny(CELLS[0]))


# ------------------------------------------------------------ planted faults

def _state_unchanged(monkeypatch):
    """Every transition returns the set's state as it was."""
    import jax
    from repro.core import controller
    for name in ("conv_set_kernel", "ext_set_kernel"):
        real = getattr(controller, name)

        def frozen(cfg, row, *req, _real=real):
            _, out = _real(cfg, row, *req)
            return row, out
        monkeypatch.setattr(controller, name, frozen)
    jax.clear_caches()


def _half_batch(monkeypatch):
    """Each dispatch simulates half of its traces; the other half get the
    mean of those."""
    import jax
    from repro.core import engine
    real = engine.simulate_batch

    def half(cfg, traces, backend=None):
        k = max(len(traces) // 2, 1)
        stats = real(cfg, list(traces[:k]), backend)
        return jax.tree.map(
            lambda x: np.concatenate([np.asarray(x), np.full(
                len(traces) - k, np.asarray(x).mean(), np.asarray(x).dtype)]),
            stats)
    monkeypatch.setattr(engine, "simulate_batch", half)


def _answer_altered(monkeypatch):
    """One counter of the last trace of each dispatch is off by one."""
    from repro.core import engine
    real = engine.simulate_batch

    def altered(cfg, traces, backend=None):
        stats = real(cfg, traces, backend)
        hits = np.asarray(stats.conv_hits).copy()
        hits[len(traces) - 1] += 1
        return stats._replace(conv_hits=hits)
    monkeypatch.setattr(engine, "simulate_batch", altered)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_fault_fails_correct(monkeypatch, workload, fault):
    import jax
    fault(monkeypatch)
    try:
        out = run(workload)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert out["correct"] is False
    assert out["failed"] > 0
    assert out["checks"]["stats_mismatch"]["value"] > 0
