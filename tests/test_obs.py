"""Observability layer tests (ISSUE 8): spans, metrics, decision
provenance, and the guarantees around them.

Headline properties (acceptance):

  * disabled observability is a true no-op: ``obs.span`` returns the
    shared null singleton, and an obs-enabled ``simulate_online`` run is
    bit-identical (telemetry rows, decision sequence, Stats) to a
    disabled one on the jnp AND pallas engine backends;
  * every governor decision path — greedy, explore, hint, phase_jump,
    ctx_reentry, churn_reset, phase_shift — emits exactly one correctly
    typed ``DecisionEvent``, and every split switch in an online run has
    exactly one attributed switch event (the audit invariant);
  * the autotuner's trajectory bytes don't change with obs enabled
    (the golden CRC guarantee extends under instrumentation);
  * spans reach the JAX profiler's timeline as disjoint, contiguous
    segments named after the innermost open span (self time), and none
    are built with tracing off; ``run_batch``'s five phase spans, one of
    each per dispatch on the calling thread, cover the call, and its
    results are bit-identical with obs on or off;
  * ``TelemetryLog`` exports oldest -> newest even after the ring wraps;
  * ``tools/obs_report.py`` renders a bundle.
"""
import json
import subprocess
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.autotune import Tuner, gov_space, make_agent
from repro.core import cache_sim as cs
from repro.core import engine
from repro.obs.decision import TRIGGERS, DecisionEvent
from repro.obs.metrics import Registry
from repro.obs.trace import NULL_SPAN, Tracer
from repro.runtime import Governor, GovernorConfig, simulate_online
from repro.runtime.telemetry import FIELDS, EpochRecord, TelemetryLog
from repro.workloads.serving import SLOBudgeter

ROOT = Path(__file__).resolve().parents[1]

_pallas_ok, _pallas_why = engine.backend_status("pallas")


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with observability disabled."""
    obs.disable()
    yield
    obs.disable()


def _total(name):
    """A counter's total over all its label sets in the active registry
    (0 for a counter the run never touched)."""
    m = obs.metrics_registry().get(name)
    return m.total() if m is not None else 0


def _record(epoch, **kw):
    base = dict(epoch=epoch, pos=epoch * 100, app="x", n_compute=32,
                n_cache=36, requests=100, hit_rate=0.5,
                ext_occupancy=0.5, pred_accuracy=0.9, bytes_saved=0.0,
                ipc=1.0, exec_time_s=1e-4, reward=1.0)
    base.update(kw)
    return EpochRecord(**base)


# ------------------------------------------------------------------ spans

def test_disabled_span_is_shared_null_singleton():
    assert not obs.enabled()
    assert obs.span("a", k=1) is NULL_SPAN
    assert obs.span("b") is NULL_SPAN
    with obs.span("c", x=2) as sp:
        sp.set(y=3)          # must be a silent no-op
    obs.instant("d", v=1)    # likewise
    obs.count("nothing", 5)
    assert obs.tracer() is None and obs.metrics_registry() is None


def test_tracer_deterministic_with_injected_clock():
    ticks = iter(range(0, 100_000, 1_000))   # ns
    t = Tracer(clock=lambda: next(ticks))
    with t.span("outer", layer="runtime"):
        with t.span("inner") as sp:
            sp.set(rows=4)
    doc = json.loads(t.to_json())
    assert doc["displayTimeUnit"] == "ms"
    inner, outer = doc["traceEvents"]        # inner completes first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["ph"] == outer["ph"] == "X"
    # clock ticks: outer t0=0, inner t0=1000, inner t1=2000, outer
    # t1=3000 ns -> microseconds
    assert (inner["ts"], inner["dur"]) == (1.0, 1.0)
    assert (outer["ts"], outer["dur"]) == (0.0, 3.0)
    assert inner["args"] == {"rows": 4, "id": 1, "parent": 0}
    assert outer["args"] == {"layer": "runtime", "id": 0, "parent": None}


def test_tracer_instant_and_summary(tmp_path):
    ticks = iter(range(0, 100_000, 1_000))
    t = Tracer(clock=lambda: next(ticks))
    with t.span("s"):
        pass
    t.instant("mark", why="because")
    ev = t.events[-1]
    assert ev["ph"] == "i" and ev["s"] == "g" and ev["name"] == "mark"
    s = t.summary()
    assert s["s"]["count"] == 1 and s["s"]["total_us"] == 1.0
    p = t.save(tmp_path / "trace.json")
    assert "traceEvents" in json.loads(p.read_text())


class _Annotations:
    """A fake profiler annotator: logs each event's begin and end."""

    def __init__(self):
        self.log = []
        outer = self

        class _Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.log.append(("B", self.name))

            def __exit__(self, *exc):
                outer.log.append(("E", self.name))
        self.cls = _Ann

    def segments(self):
        """Profiler segments in order; asserts they are disjoint (each
        ends before the next begins) and contiguous (the next begins right
        after, nothing runs unannotated in between)."""
        assert [k for k, _ in self.log] == ["B", "E"] * (len(self.log) // 2)
        begins, ends = self.log[0::2], self.log[1::2]
        assert [n for _, n in begins] == [n for _, n in ends]
        return [n for _, n in begins]


def test_profiler_segments_are_innermost_self_time():
    ann = _Annotations()
    t = Tracer(annotate=ann.cls)
    with t.span("outer", k=1):
        with t.span("a"):
            pass
        with t.span("b"):
            with t.span("c"):
                pass
    assert ann.segments() == ["outer", "a", "outer", "b", "c", "b", "outer"]


def test_span_events_carry_id_and_parent():
    t = Tracer()
    with t.span("outer"):
        with t.span("a"):
            pass
        with t.span("b"):
            with t.span("c"):
                pass
    ev = {e["name"]: e["args"] for e in t.events}
    assert len({a["id"] for a in ev.values()}) == 4
    assert ev["outer"]["parent"] is None
    assert ev["a"]["parent"] == ev["b"]["parent"] == ev["outer"]["id"]
    assert ev["c"]["parent"] == ev["b"]["id"]


def test_span_closed_out_of_order_keeps_innermost_marked():
    ann = _Annotations()
    t = Tracer(annotate=ann.cls)
    outer = t.span("outer").__enter__()
    inner = t.span("inner").__enter__()
    outer.__exit__(None, None, None)      # closes while a child is open
    inner.__exit__(None, None, None)
    assert ann.segments() == ["outer", "inner"]
    assert t._stack() == []


# ------------------------------------------------- phase spans in run_batch

PHASES = ("cache_sim.prepare", "engine.pack", "engine.dispatch",
          "cache_sim.wait", "cache_sim.unpack")


def _tiny_points(backend=""):
    """Two configurations: a padded chunk of three BL points and one
    Morpheus-ALL point, so two dispatches."""
    return [cs.RunPoint("cfd", "BL", n, 0, 3000, backend=backend)
            for n in (10, 14, 18)] + \
        [cs.RunPoint("cfd", "Morpheus-ALL", 32, 24, 3000, backend=backend)]


def test_tracing_off_builds_no_annotation(monkeypatch):
    import jax
    made = []

    class Counting:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    pts = _tiny_points()
    cs.run_batch(pts)                       # all off
    obs.enable(trace=False)                 # counters only
    cs.run_batch(pts)
    assert made == []
    obs.enable()                            # spans bind the annotator
    cs.run_batch(pts)
    assert "engine.pack" in made and "cache_sim.run_batch" in made


def test_run_batch_phase_spans_cover_the_call(monkeypatch):
    # a pool of two threads makes the traces, whatever the host's CPUs
    monkeypatch.setattr(cs, "_prefetch_width", lambda n, s, c: min(n, 2))
    pts = _tiny_points()
    cs.run_batch(pts)                       # compile outside the record
    obs.enable()
    cs.run_batch(pts)
    reg = obs.metrics_registry()
    dispatches = reg.get("engine_dispatches").total()
    assert dispatches == 2
    assert reg.get("trace_prefetch").total() == dispatches
    spans = [e for e in obs.tracer().events if e["ph"] == "X"]
    top, = [e for e in spans if e["name"] == "cache_sim.run_batch"]
    assert top["args"]["points"] == 4 and top["args"]["groups"] == 2
    assert top["args"]["workers"] == 2
    # the pool's threads emit no span: every one is the calling thread's
    assert {e["tid"] for e in spans} == {top["tid"]}
    phases = [e for e in spans if e["name"] in PHASES]
    names = [e["name"] for e in phases]
    # one prepare per dispatch: the calling thread blocked on its chunk's
    # traces (the first one also resolves every point)
    for name in PHASES:
        assert names.count(name) == dispatches, name
    assert len(phases) == len(spans) - 1
    assert all(e["args"]["parent"] == top["args"]["id"] for e in phases)
    lo, hi = top["ts"], top["ts"] + top["dur"]
    iv = sorted((e["ts"], e["ts"] + e["dur"]) for e in phases)
    assert lo <= iv[0][0] and iv[-1][1] <= hi
    assert all(a[1] <= b[0] for a, b in zip(iv, iv[1:])), "overlap"
    assert sum(e["dur"] for e in phases) >= 0.9 * top["dur"]


def test_run_batch_tags_the_modelled_gpu():
    obs.enable()
    cs.run_batch(_tiny_points())
    top, = [e for e in obs.tracer().events
            if e["ph"] == "X" and e["name"] == "cache_sim.run_batch"]
    assert top["args"]["gpu"] == cs.TABLE1.name == "Table1"


def test_device_put_bytes_equal_packed_nbytes(monkeypatch):
    """``device_put_bytes{tier}``: per dispatch, the bytes of the tier's
    packed batch arrays, padding included."""
    packed = []

    def keep(cfg, traces, *a, **kw):
        packed.append(pack(cfg, traces, *a, **kw))
        return packed[-1]
    pack = engine.pack
    monkeypatch.setattr(engine, "pack", keep)
    obs.enable(trace=False)
    cs.run_batch(_tiny_points())
    assert len(packed) == 2
    reg = obs.metrics_registry()
    for tier, fields in (("conv", ("conv_tag", "conv_write", "conv_pos",
                                   "conv_active")),
                         ("ext", ("ext_tag", "ext_write", "ext_level",
                                  "ext_pos", "ext_active"))):
        want = sum(getattr(pt, f).nbytes for pt in packed for f in fields)
        got = sum(s["value"] for s in reg.get("device_put_bytes").samples()
                  if s["labels"] == {"tier": tier})
        assert got == want, tier
    # the BL dispatch has no extended tier: nothing of it is transferred
    assert packed[0].ext_tag.size == 0


def test_packed_slots_counts_padded_slots():
    obs.enable(trace=False)
    cfg, trace, *_ = cs._prepare(cs.RunPoint("cfd", "Morpheus-ALL", 32, 24,
                                             3000))
    pt = engine.pack(cfg, [trace, trace, trace])
    b, sc, lc = pt.conv_tag.shape
    _, se, le = pt.ext_tag.shape
    assert b == 3 and sc * lc and se * le
    assert obs.metrics_registry().get("packed_slots").total() == \
        b * (sc * lc + se * le)
    assert 3 * len(trace[0]) == pt.conv_active.sum() + pt.ext_active.sum()


@pytest.mark.parametrize("backend", [
    "jnp",
    pytest.param("pallas", marks=pytest.mark.skipif(
        not _pallas_ok, reason=_pallas_why)),
])
def test_run_batch_bit_identical_under_obs(backend):
    pts = _tiny_points(backend)
    base = cs.run_batch(pts)
    obs.enable()
    on = cs.run_batch(pts)
    obs.disable()
    for a, b in zip(base, on):
        for f in a.stats._fields:
            x, y = np.asarray(getattr(a.stats, f)), \
                np.asarray(getattr(b.stats, f))
            assert x.dtype == y.dtype and np.array_equal(x, y), f
        assert (a.exec_time_s, a.ipc) == (b.exec_time_s, b.ipc)


def test_profiler_trace_holds_host_phase_events(tmp_path):
    import jax
    pts = _tiny_points()
    cs.run_batch(pts)
    obs.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        cs.run_batch(pts)
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"engine.pack", "cache_sim.unpack"} <= names


# ---------------------------------------------------------------- metrics

def test_registry_counter_gauge_histogram_exposition():
    r = Registry()
    r.counter("engine_dispatches", "dispatches issued").inc(
        3, path="epoch")
    r.counter("engine_dispatches").inc(2, path="fleet")
    r.gauge("slo_attainment").set(0.75, tenant="a")
    h = r.histogram("span_ns", buckets=(10, 100))
    h.observe(5)
    h.observe(50)
    h.observe(5000)
    text = r.to_prometheus()
    assert 'morpheus_engine_dispatches_total{path="epoch"} 3' in text
    assert 'morpheus_engine_dispatches_total{path="fleet"} 2' in text
    assert 'morpheus_slo_attainment{tenant="a"} 0.75' in text
    assert 'morpheus_span_ns_bucket{le="10"} 1' in text
    assert 'morpheus_span_ns_bucket{le="100"} 2' in text
    assert 'morpheus_span_ns_bucket{le="+Inf"} 3' in text
    assert "morpheus_span_ns_count 3" in text
    snap = r.snapshot()
    names = {m["name"] for m in snap["metrics"]}
    assert {"engine_dispatches", "slo_attainment", "span_ns"} <= names
    json.dumps(snap)    # JSON-clean


def test_registry_save_formats(tmp_path):
    r = Registry()
    r.counter("epochs").inc(7)
    j = r.save(tmp_path / "m.json")
    assert json.loads(j.read_text())["metrics"][0]["name"] == "epochs"
    p = r.save(tmp_path / "m.prom")
    assert "morpheus_epochs_total 7" in p.read_text()


def test_module_helpers_route_to_active_registry():
    obs.enable(trace=False)
    obs.count("engine_dispatches", 2, path="epoch")
    obs.set_gauge("slo_attainment", 0.5)
    obs.observe("span_ns", 42.0)
    assert _total("engine_dispatches") == 2
    assert _total("jax_compiles") >= 0 and _total("epochs") == 0
    obs.disable()
    # helpers silently drop once deactivated
    obs.count("engine_dispatches", 99)
    assert obs.metrics_registry() is None


def test_compile_hook_counts_real_xla_compiles():
    import jax
    import jax.numpy as jnp
    obs.enable(trace=False)
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7)
    f(x).block_until_ready()
    n1 = _total("jax_compiles")
    assert n1 >= 1, "compile hook missed a fresh XLA build"
    f(x).block_until_ready()    # cached: no new executable
    assert _total("jax_compiles") == n1


# -------------------------------------------------------------- telemetry

def test_telemetry_export_is_oldest_first_after_wrap(tmp_path):
    log = TelemetryLog(capacity=8)
    for i in range(20):
        log.append(_record(i))
    assert len(log) == 8 and log.total == 20
    epochs = [r.epoch for r in log.records()]
    assert epochs == list(range(12, 20)), \
        "wrapped export must start at the oldest held record"
    rows = log.to_csv(tmp_path / "t.csv").read_text().splitlines()
    assert rows[0].split(",")[0] == "epoch"
    assert [int(r.split(",")[0]) for r in rows[1:]] == epochs
    assert [r["epoch"] for r in json.loads(log.to_json())] == epochs


def test_telemetry_tail_zero_is_empty():
    log = TelemetryLog(capacity=4)
    for i in range(3):
        log.append(_record(i))
    assert log.tail(0) == []
    assert [r.epoch for r in log.tail(2)] == [1, 2]
    assert len(log.tail(99)) == 3


def test_epoch_record_has_decision_column():
    assert FIELDS[-1] == "decision"
    assert _record(0).decision == ""


# ----------------------------------------------------- decision provenance

def test_decision_event_contract():
    ev = DecisionEvent(epoch=3, trigger="hint", from_split=(32, 36),
                       to_split=(28, 40), epsilon=0.2, hint=1)
    assert ev.switched and ev.compact() == "hint:(32|36)->(28|40)"
    held = DecisionEvent(epoch=3, trigger="churn_reset",
                         from_split=(32, 36), to_split=(32, 36),
                         epsilon=0.2)
    assert not held.switched and held.compact() == "churn_reset"
    json.dumps(ev.to_dict())
    assert ev.to_dict()["from_split"] == [32, 36]
    with pytest.raises(AssertionError):
        DecisionEvent(epoch=0, trigger="vibes", from_split=0,
                      to_split=1, epsilon=0.0)


def _drive(gov, reward_fn, epochs, hint=0, sig=None, ctx=None):
    for _ in range(epochs):
        if ctx is not None:
            gov.set_context(ctx)
        kw = {} if sig is None else {"signature": sig}
        gov.observe(reward_fn(gov.current), hint=hint, **kw)
        gov.decide()


def _triggers(gov):
    return [e.trigger for e in gov.decisions]


def test_greedy_and_explore_paths_emit_typed_events():
    cands = [(n, 68 - n) for n in (10, 20, 30, 40, 50, 60)]
    peak = {c: 100.0 - abs(c[0] - 40) for c in cands}
    gov = Governor(cands, GovernorConfig(seed=3, warm_epochs=0))
    _drive(gov, lambda c: peak[c], 60)
    assert gov.current == (40, 28)
    trig = _triggers(gov)
    assert "greedy" in trig, trig
    assert "explore" in trig, trig     # epsilon draws fired along the way
    # audit invariant: one attributed switch event per switch
    switch_events = [e for e in gov.decisions if e.switched]
    assert len(switch_events) == gov.switches
    assert all(e.trigger in ("greedy", "explore", "hint", "phase_jump",
                             "ctx_reentry") for e in switch_events)
    # estimates consulted at decision time ride along
    assert any(e.estimates for e in switch_events)


def test_hint_path_emits_hint_event():
    gov = Governor(list(range(5)),
                   GovernorConfig(seed=0, warm_epochs=0), initial=2)
    _drive(gov, lambda c: 10.0, 12, hint=+1)
    hints = [e for e in gov.decisions if e.trigger == "hint"]
    assert hints and all(e.hint == +1 and e.switched for e in hints)


def test_phase_shift_and_phase_jump_events():
    gov = Governor(list(range(6)), GovernorConfig(seed=2, warm_epochs=0))
    _drive(gov, lambda c: 50.0 - 5 * c, 40, sig=0.15)
    _drive(gov, lambda c: 30.0 + 5 * c, 60, sig=0.90)
    _drive(gov, lambda c: 50.0 - 5 * c, 3, sig=0.15)   # revisit phase A
    trig = _triggers(gov)
    # a re-entry records the reset (phase_shift) AND the jump it served
    assert trig.count("phase_shift") == gov.phase_shifts
    jumps = [e for e in gov.decisions if e.trigger == "phase_jump"]
    assert jumps, "phase-memory re-entry recorded no phase_jump event"
    assert all(e.switched for e in jumps)
    shifts = [e for e in gov.decisions if e.trigger == "phase_shift"]
    assert shifts and all(not e.switched for e in shifts)


def test_churn_reset_and_ctx_reentry_events():
    gov = Governor(list(range(6)), GovernorConfig(seed=1, warm_epochs=0))
    _drive(gov, lambda c: 50.0 - 5 * c, 40, ctx=0b11)
    _drive(gov, lambda c: 30.0 + 5 * c, 50, ctx=0b01)  # churn 1
    _drive(gov, lambda c: 50.0 - 5 * c, 2, ctx=0b11)   # churn 2 + re-entry
    resets = [e for e in gov.decisions if e.trigger == "churn_reset"]
    assert len(resets) == gov.churn_resets == 2
    assert all(not e.switched and e.ctx is not None for e in resets)
    re = [e for e in gov.decisions if e.trigger == "ctx_reentry"]
    assert len(re) == 1 and re[0].switched and re[0].ctx == 0b11


def test_every_trigger_name_is_exercised_above():
    """The taxonomy is closed: tests above cover every member, so a new
    trigger string must come with a test."""
    covered = {"greedy", "explore", "hint", "phase_jump", "ctx_reentry",
               "churn_reset", "phase_shift"}
    assert covered == set(TRIGGERS)


# ------------------------------------------------- online run provenance

def _online(**kw):
    return simulate_online(("p-bfs", "spmv", "p-bfs"), "Morpheus-ALL",
                           length=12_000, epoch_len=1_500, seed=3, **kw)


def test_online_run_attributes_every_switch():
    r = _online()
    assert r.decisions, "online run recorded no decision events"
    switch_events = [e for e in r.decisions if e.switched]
    assert len(switch_events) == r.switches
    assert all(e.replica for e in r.decisions)
    # flush cost paid by each switch is attributed to its event
    assert sum(e.flush_writebacks for e in r.decisions) == \
        sum(rec.flush_writebacks for rec in r.records)
    # the telemetry decision column compacts the same events
    recs_with_switch = [rec for rec in r.records if rec.switched]
    for rec in recs_with_switch:
        assert "->" in rec.decision, rec
    assert sum("->" in (rec.decision or "") for rec in r.records) == \
        len(switch_events)


@pytest.mark.parametrize("backend", [
    "jnp",
    pytest.param("pallas", marks=pytest.mark.skipif(
        not _pallas_ok, reason=_pallas_why)),
])
def test_enabled_obs_is_bit_identical(backend):
    base = _online(backend=backend)
    obs.enable()
    on = _online(backend=backend)
    obs.disable()
    assert [rec.to_dict() for rec in base.records] == \
        [rec.to_dict() for rec in on.records]
    assert [e.to_dict() for e in base.decisions] == \
        [e.to_dict() for e in on.decisions]
    assert (base.ipc, base.switches, base.converged_split) == \
        (on.ipc, on.switches, on.converged_split)


def test_online_run_emits_trace_instants_and_counters():
    obs.enable()
    r = _online()
    t = obs.tracer()
    instants = [e for e in t.events if e["name"] == "governor.decision"]
    assert len(instants) == len(r.decisions)
    names = {e["name"] for e in t.events}
    assert "governor.decide" in names
    assert _total("engine_dispatches") == len(r.records) == \
        _total("epochs")
    assert _total("device_get_bytes") > 0
    assert _total("flush_writebacks") == \
        sum(rec.flush_writebacks for rec in r.records)


# -------------------------------------------- trajectory byte-determinism

class _SynthObjective:
    def __init__(self, space):
        self.space = space

    def evaluate(self, configs):
        return [-sum((2 * i - 3) ** 2 for i in self.space.encode(c))
                for c in configs]

    def describe(self):
        return {"objective": "synth"}


def _run_tuner(path):
    space = gov_space()
    Tuner(space, _SynthObjective(space),
          make_agent("ga", space, seed=0, pop=5),
          trajectory_path=path).run(4)
    return Path(path).read_bytes()


def test_tuner_trajectory_bytes_identical_under_obs(tmp_path):
    off = _run_tuner(tmp_path / "off.jsonl")
    obs.enable(inspect=True)       # full stack incl. the cache microscope
    on = _run_tuner(tmp_path / "on.jsonl")
    spans = [e for e in obs.tracer().events
             if e["name"] == "tuner.generation"]
    obs.disable()
    assert zlib.crc32(off) == zlib.crc32(on) and off == on
    assert len(spans) == 4
    assert spans[0]["args"]["agent"] == "ga"


# ----------------------------------------------------------- SLO budgeter

def test_slo_budgeter_tracks_attainment():
    b = SLOBudgeter(slo_ms=1.0)
    assert b.attainment() == 1.0
    b.observe(ns_per_lookup=100.0, lookups=5_000, requests=10)   # 0.5 ms
    b.observe(ns_per_lookup=100.0, lookups=20_000, requests=10)  # 2.0 ms
    assert b.rounds_observed == 2 and b.rounds_met == 1
    assert b.attainment() == 0.5
    b.observe(ns_per_lookup=100.0, lookups=0, requests=0)        # idle
    assert b.rounds_observed == 2


# ----------------------------------------- cache microscope (ISSUE 9)

def _stats_ints(stats):
    return [int(np.asarray(v)) for v in stats]


@pytest.mark.parametrize("backend", [
    "jnp",
    pytest.param("pallas", marks=pytest.mark.skipif(
        not _pallas_ok, reason=_pallas_why)),
])
def test_enabled_introspection_is_bit_identical(backend):
    """Full microscope on (per-epoch state snapshots) changes NO
    simulator output: integer Stats, telemetry rows and the decision
    sequence stay bit-identical on both backends."""
    base = _online(backend=backend)
    obs.enable(trace=False, metrics=False, inspect=True)
    on = _online(backend=backend)
    snaps = obs.inspector().snapshots
    obs.disable()
    assert snaps, "microscope recorded no snapshots"
    assert _stats_ints(base.stats) == _stats_ints(on.stats)
    assert [rec.to_dict() for rec in base.records] == \
        [rec.to_dict() for rec in on.records]
    assert [e.to_dict() for e in base.decisions] == \
        [e.to_dict() for e in on.decisions]
    assert (base.ipc, base.switches, base.converged_split) == \
        (on.ipc, on.switches, on.converged_split)


def test_snapshot_counter_and_decode_sanity():
    obs.enable(trace=False, metrics=True, inspect=True)
    r = _online()
    snaps = obs.inspector().snapshots
    n_snaps = _total("state_snapshots")
    obs.disable()
    assert len(snaps) == len(r.records) == n_snaps
    assert [s.epoch for s in snaps] == sorted(s.epoch for s in snaps)
    for s in snaps:
        assert 0.0 <= s.conv_occupancy <= 1.0
        assert 0.0 <= s.ext_occupancy <= 1.0
        assert 0.0 <= s.byte_util <= 1.0
        assert 0.0 <= s.bloom_fill <= 1.0
        assert 0.0 <= s.bloom_fp_rate <= 1.0
        assert s.expansion >= 1.0          # BDI never inflates
        if s.conv_occupancy > 0:
            # occupancy = valid / (sets * ways): recover the way count
            ways = sum(s.conv_set_occ) / (s.conv_occupancy
                                          * len(s.conv_set_occ))
            assert ways == pytest.approx(round(ways)) and ways >= 1
    # occupancy only grows on this single-phase-dominated stream prefix
    assert snaps[-1].conv_occupancy >= snaps[0].conv_occupancy
    json.dumps(snaps[-1].to_dict())        # export is JSON-clean


def test_inspect_every_strides_snapshots():
    obs.enable(trace=False, metrics=False, inspect=True, inspect_every=3)
    r = _online()
    snaps = obs.inspector().snapshots
    obs.disable()
    assert [s.epoch for s in snaps] == \
        [e for e in range(len(r.records)) if e % 3 == 0]


def test_residency_sums_to_valid_blocks_every_epoch():
    """Per-tenant residency (owners recovered from block addresses) must
    account for every valid block in both tiers, every epoch."""
    from repro.core import cache_sim as cs
    from repro.workloads import tenancy
    scale = cs.SYSTEMS["Morpheus-ALL"].sim_scale
    wl = tenancy.make_workload("cfd,kmeans", length=9_000, n_cores=32,
                               arrival="det:2e6", seed=0,
                               ws_scale=1.0 / scale)
    obs.enable(trace=False, metrics=False, inspect=True)
    simulate_online(wl, "Morpheus-ALL", epoch_len=1_500)
    snaps = obs.inspector().snapshots
    obs.disable()
    assert snaps
    names = {t.name for t in wl.tenants}
    for s in snaps:
        total = sum(s.conv_set_occ) + sum(s.ext_set_occ)
        assert sum(s.residency.values()) == total, \
            f"epoch {s.epoch}: residency does not account for all blocks"
        assert set(s.residency) <= names
    assert any(len(s.residency) == 2 for s in snaps), \
        "both tenants should hold residency at some epoch"


def test_inspector_caps_and_drops():
    from repro.obs.inspect import Inspector, Snapshot
    ins = Inspector(max_snapshots=2)
    for i in range(5):
        ins.record(Snapshot(epoch=i, pos=i))
    assert len(ins.snapshots) == 2 and ins.dropped == 3
    assert ins.to_json()["dropped"] == 3


# ------------------------------------------------------- stream profiler

def test_reuse_histogram_mass_invariant():
    from repro.obs import profile as prof
    rng = np.random.default_rng(0)
    for addrs in ([], [7], [7, 7, 7], list(range(100)),
                  rng.integers(0, 50, 1_000)):
        h = prof.reuse_histogram(addrs)
        assert h["mass"] == h["cold"] + sum(h["bins"]) == len(addrs)


def test_reuse_distances_exact_small_cases():
    from repro.obs import profile as prof
    # 1 1: re-touch distance 0; 1 2 1: one distinct block in between
    assert prof.reuse_distances([1, 1]).tolist() == [prof.COLD, 0]
    assert prof.reuse_distances([1, 2, 1]).tolist() == \
        [prof.COLD, prof.COLD, 1]
    assert prof.reuse_distances([1, 2, 3, 1, 2]).tolist() == \
        [prof.COLD, prof.COLD, prof.COLD, 2, 2]
    h = prof.reuse_histogram([1, 1, 1])
    assert h["cold"] == 1 and h["bins"][0] == 2     # distance-0 bin


def test_wss_curve_and_per_tenant_profile():
    from repro.obs import profile as prof
    addrs = [1, 2, 1, 3, 2, 4]
    tid = [0, 1, 0, 1, 1, 0]
    p = prof.profile_trace(addrs, tenant_id=tid, names=["a", "b"])
    assert p["wss"]["footprint_blocks"] == 4
    assert p["wss"]["distinct_blocks"][-1] == 4
    assert sorted(p["tenants"]) == ["a", "b"]
    # per-tenant masses sum to the global mass
    assert sum(t["reuse"]["mass"] for t in p["tenants"].values()) == \
        p["reuse"]["mass"] == len(addrs)
    assert p["tenants"]["a"]["wss"]["footprint_blocks"] == 2  # {1, 4}


# ------------------------------------------------------- fairness gauge

def test_jains_index_exact_unity_cases():
    from repro.runtime.telemetry import jains_index
    assert jains_index([]) == 1.0
    assert jains_index([3.7]) == 1.0                 # K=1: exactly 1.0
    assert jains_index([0.4] * 8) == 1.0             # identical tenants
    assert jains_index([0.0, 0.0]) == 1.0            # all-idle epoch
    assert jains_index([1.0, 0.0]) == pytest.approx(0.5)
    # bounds: 1/n <= J <= 1
    xs = [5.0, 1.0, 0.5, 0.25]
    assert 1 / len(xs) <= jains_index(xs) < 1.0


def test_fairness_column_in_epoch_records():
    from repro.core import cache_sim as cs
    from repro.workloads import tenancy
    assert "fairness" in FIELDS and FIELDS[-1] == "decision"
    r = _online()                    # single tenant: exactly 1.0
    assert all(rec.fairness == 1.0 for rec in r.records)
    scale = cs.SYSTEMS["Morpheus-ALL"].sim_scale
    wl = tenancy.make_workload("cfd,kmeans", length=9_000, n_cores=32,
                               arrival="det:2e6", seed=0,
                               ws_scale=1.0 / scale)
    m = simulate_online(wl, "Morpheus-ALL", epoch_len=1_500)
    assert all(0.0 < rec.fairness <= 1.0 for rec in m.records)


def test_fairness_gauge_registered():
    obs.enable(trace=False, metrics=True)
    _online()
    text = obs.metrics_registry().to_prometheus()
    obs.disable()
    assert "morpheus_fairness_jain" in text


def test_decision_events_carry_summary():
    r = _online()
    for e in r.decisions:
        assert {"hit_rate", "ext_occupancy", "fairness",
                "reward"} <= set(e.summary)
        assert e.to_dict()["summary"]["fairness"] == e.summary["fairness"]


# ------------------------------------------------- pool event recorder

def _pool(chips=2):
    from repro.serving.paged_kv import MorpheusPagePool, PoolConfig
    return MorpheusPagePool(PoolConfig(conv_sets=16, ext_sets_per_chip=8,
                                       num_cache_chips=chips, ways=2))


def test_pool_recorder_logs_and_is_pure(tmp_path):
    from repro.serving import paged_kv as pk
    from repro.workloads import corpus
    keys = np.arange(1, 25, dtype=np.uint32)
    ref = _pool()
    ref.lookup_batch(keys)
    ref.lookup_batch(keys)
    pool = _pool()
    rec = pool.attach_recorder()
    pool.lookup_batch(keys)
    pool.lookup_batch(keys)
    # pure logging: stats identical with and without the recorder
    assert pool.stats == ref.stats
    c = rec.counts()
    assert c["lookup"] == 2 * len(keys)
    assert c["insert"] > 0
    # every insert/evict key routes to a real set (inverse key mapping)
    ks, ev, tiers = rec.arrays()
    assert set(np.unique(ev)) <= {pk.EV_LOOKUP, pk.EV_INSERT, pk.EV_EVICT}
    p = rec.save(tmp_path / "pool.npz")
    addrs, writes, levels, meta = corpus.load_trace(p)
    assert corpus.validate_trace(p) == []
    assert meta["extra"]["kind"] == "pool_events"
    assert meta["extra"]["events"] == c
    assert int(writes.sum()) == c["insert"] + c["evict"]


def test_pool_recorder_survives_reconfigure():
    from repro.serving.paged_kv import EV_EVICT
    pool = _pool(chips=2)
    rec = pool.attach_recorder()
    pool.lookup_batch(np.arange(1, 25, dtype=np.uint32))
    resident = sum(len(k) for k in pool.resident_keys())
    evicts_before = rec.counts()["evict"]
    flushed = pool.reconfigure(1)
    assert pool.recorder is rec, "recorder must survive reconfigure"
    assert flushed == resident
    assert rec.counts()["evict"] == evicts_before + resident, \
        "a mode transition must log one evict per flushed page"


def test_pool_recorder_ring_wraps_oldest_first():
    from repro.serving.paged_kv import EV_LOOKUP, TraceRecorder
    rec = TraceRecorder(capacity=8)
    rec.record(EV_LOOKUP, np.arange(20, dtype=np.uint32), 0)
    ks, _, _ = rec.arrays()
    assert rec.total == 20 and len(rec) == 8
    assert ks.tolist() == list(range(12, 20)), "export must be oldest-first"


def test_pool_content_snapshot_residency():
    from repro.obs.inspect import Inspector
    pool = _pool()
    keys = np.arange(1, 25, dtype=np.uint32)
    pool.lookup_batch(keys)
    ins = Inspector()
    for k in keys[:10]:
        ins.note_owner(int(k), "tenantA")
    snap = pool.content_snapshot(epoch=3, owners=ins.owners)
    valid = sum(snap.conv_set_occ) + sum(snap.ext_set_occ)
    assert sum(snap.residency.values()) == valid
    assert snap.residency.get("tenantA", 0) > 0
    assert "?" in snap.residency          # un-noted keys stay visible
    assert snap.pos == pool.stats.lookups


# --------------------------------------------------------------- reporter

def test_obs_report_heatmap_and_filters(tmp_path):
    obs.enable(trace=True, metrics=False, inspect=True)
    _online()
    ins_p = obs.inspector().save(tmp_path / "inspect.json")
    trace_p = obs.tracer().save(tmp_path / "trace.json")
    obs.disable()
    tool = str(ROOT / "tools" / "obs_report.py")
    out = subprocess.run(
        [sys.executable, tool, "heatmap", str(ins_p),
         "--csv-prefix", str(tmp_path / "hm"),
         "--html", str(tmp_path / "hm.html")],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "set occupancy over epochs" in out.stdout
    assert (tmp_path / "hm_occupancy.csv").exists()
    assert (tmp_path / "hm.html").exists()
    # decision-trail selectors
    out = subprocess.run(
        [sys.executable, tool, "--trace", str(trace_p), "--decisions",
         "--filter", "trigger=explore", "--epochs", "0:99"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "trigger=explore" in out.stdout
    # unknown inspect schema exits 2, no traceback
    bad = dict(json.loads(ins_p.read_text()), schema=99)
    bad_p = tmp_path / "bad.json"
    bad_p.write_text(json.dumps(bad))
    r = subprocess.run([sys.executable, tool, "heatmap", str(bad_p)],
                       capture_output=True, text=True)
    assert r.returncode == 2 and "Traceback" not in r.stderr


def test_obs_report_unknown_metrics_schema_exits_2(tmp_path):
    p = tmp_path / "m.json"
    p.write_text(json.dumps({"schema": 9, "metrics": []}))
    r = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"),
         "--metrics", str(p)], capture_output=True, text=True)
    assert r.returncode == 2 and "Traceback" not in r.stderr
    assert "unknown metrics snapshot schema" in r.stderr


def test_obs_report_renders_bundle(tmp_path):
    obs.enable()
    _online()
    trace_p = obs.tracer().save(tmp_path / "trace.json")
    metrics_p = obs.metrics_registry().save(tmp_path / "metrics.json")
    obs.disable()
    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"),
         "--trace", str(trace_p), "--decisions",
         "--metrics", str(metrics_p)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "decision audit trail" in out.stdout
    assert "engine_dispatches" in out.stdout
    # invalid input exits 2
    bad = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_report.py"),
         "--trace", str(metrics_p)], capture_output=True, text=True)
    assert bad.returncode == 2
