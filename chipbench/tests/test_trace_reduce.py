"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on hand-made events, on a small trace recorded on a TPU v5e, and on
a trace this CPU records."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace_reduce as tr

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"
RECORDED = Path(__file__).with_name("data") / "sweep_trace_events.json.gz"


def ev(plane, name, start, dur, line=OPS):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_hand_made_window():
    events = [
        ev(HOST, "chipbench.pass", 100, 900, "python"),
        ev(HOST, "np.asarray(jax.Array)", 600, 300, "python"),
        ev(DEV, "%engine_scan_ext_slot.1 = (s32[16,9,510]) custom-call()",
           150, 100),
        ev(DEV, "%engine_scan_conv_slot.3 = (s32[16,9,160]) custom-call()",
           200, 100),                       # overlaps the first
        ev(DEV, "%copy.2 = s32[1] copy(s32[1] %a)", 500, 50),
        ev(DEV, "%fusion.7 = f32[] fusion()", 950, 150),  # runs past the end
        ev(DEV, "%fusion.7 = f32[] fusion()", 10, 20),    # before the window
    ]
    s = tr.summarize(events, 1)
    assert s.window_s == pytest.approx(900e-9)
    # busy: [150, 300) + [500, 550) + [950, 1000)
    assert s.busy_s == pytest.approx(250e-9)
    assert s.kernel_s("engine_scan_") == pytest.approx(200e-9)
    assert s.op_s["copy"] == pytest.approx(50e-9)
    gaps = dict((round(d * 1e9), n) for n, d in s.gaps)
    assert set(gaps) == {50, 200, 400}
    assert gaps[400] == "np.asarray(jax.Array)"
    assert gaps[200].startswith("chipbench.pass")
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion"
    assert [d for _, d in b["idle_gaps"]] == sorted(
        [d for _, d in b["idle_gaps"]], reverse=True)


def _timeline_busy(events, lo, hi):
    """Busy time by brute force on a 1 ns grid."""
    grid = np.zeros(int(hi - lo), bool)
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            grid[int(a - lo):int(b - lo)] = True
    return grid.sum() * 1e-9


def test_union_matches_brute_force():
    rng = np.random.default_rng(3)
    events = [ev(HOST, "chipbench.pass", 1000, 8000, "python")]
    events += [ev(DEV, f"%op.{i} = f32[]", int(s), int(d)) for i, (s, d) in
               enumerate(zip(rng.integers(0, 10000, 300),
                             rng.integers(1, 200, 300)))]
    s = tr.summarize(events, 1)
    want = _timeline_busy([e for e in events if e.plane == DEV], 1000, 9000)
    assert s.busy_s == pytest.approx(want)


def test_op_names():
    assert tr.op_name("%engine_scan_ext_slot.1 = (s32[16,9,510]{2,1,0}) "
                      "custom-call(...)") == "engine_scan_ext_slot"
    assert tr.op_name("%dynamic_slice.1 = s32[1]{0} dynamic-slice()") \
        == "dynamic_slice"


def _recorded():
    rows = json.loads(gzip.decompress(RECORDED.read_bytes()))
    return [tr.Event(*r) for r in rows]


def test_recorded_v5e_trace():
    """A profiled pass of four Morpheus-ALL sweep points on one TPU v5e:
    the reduction agrees with a brute-force timeline, finds both scan
    kernels, and every number stays inside the window."""
    events = _recorded()
    s = tr.summarize(events, 1)
    mark = [e for e in events if e.name == "chipbench.pass"][0]
    dev = [e for e in events if e.plane == DEV]
    assert s.window_s == pytest.approx(mark.dur_ns * 1e-9)
    assert s.busy_s == pytest.approx(
        _timeline_busy(dev, mark.start_ns, mark.end_ns), rel=1e-6)
    assert 0 < s.busy_s < s.window_s
    ext, conv = s.kernel_s("engine_scan_ext"), s.kernel_s("engine_scan_conv")
    assert ext > 0 and conv > 0
    assert s.kernel_s("engine_scan_") == pytest.approx(ext + conv)
    assert s.kernel_s("engine_scan_") <= s.busy_s
    assert sum(d for _, d in s.gaps) <= s.window_s - s.busy_s + 1e-9
    assert len(s.breakdown()["idle_gaps"]) == tr.TOP


def test_cpu_recorded_trace(tmp_path):
    """``load_events`` reads what jax.profiler writes: the annotation's
    host thread is found; a CPU trace has no TPU plane."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.pass"):
        jnp.ones(1000).sum().block_until_ready()
    jax.profiler.stop_trace()
    s = tr.reduce_dir(str(tmp_path), 1)
    assert s.window_s > 0 and s.busy_s == 0
