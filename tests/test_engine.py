"""Equivalence tests: set-parallel engine vs. the serial lax.scan oracle.

The engine's contract (core/engine.py): requests to different (tier, set)
commute, so per-set scans in original in-set order must reproduce the
serial simulation EXACTLY on every integer counter, and up to accumulation
order (<= 1e-3 relative) on the float sums.
"""
import itertools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import address_separation as asep
from repro.core import cache_sim as cs
from repro.core import controller as ctl
from repro.core import engine


def _cfg(conv_sets=8, chips=2, sets_per_chip=4, **kw):
    amap = asep.make_map(conv_sets=conv_sets, num_cache_chips=chips,
                         sets_per_chip=sets_per_chip)
    return ctl.MorpheusConfig(amap=amap, conv_ways=4, ext_ways=4, **kw)


def _trace(n=2500, span=2048, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, span, size=n).astype(np.uint32),
            rng.random(n) < 0.3,
            rng.integers(0, 3, size=n).astype(np.int32))


def _case_seed(*parts) -> int:
    """Deterministic per-case trace seed (hash() is randomized per run)."""
    return zlib.crc32("/".join(map(str, parts)).encode()) % 1000


def _assert_stats_equal(s_ser: ctl.Stats, s_par: ctl.Stats, ctx=""):
    for f in ctl.Stats._fields:
        a = np.asarray(getattr(s_ser, f))
        b = np.asarray(getattr(s_par, f))
        if f in ctl._INT_FIELDS:
            assert a == b, f"{ctx} {f}: serial={a} parallel={b}"
        else:
            tol = 1e-3 * max(abs(float(a)), 1.0)
            assert abs(float(a) - float(b)) <= tol, \
                f"{ctx} {f}: serial={a} parallel={b}"


@pytest.mark.parametrize("pred,comp", list(itertools.product(
    list(ctl.Predictor), [False, True])))
def test_engine_matches_serial_oracle(pred, comp):
    """Exact Stats equivalence across predictor x compression, warmup>0."""
    cfg = _cfg(predictor=pred, compression=comp)
    addrs, writes, levels = _trace(seed=_case_seed(pred.value, comp))
    warmup = 311
    s_ser = ctl.simulate(cfg, jnp.asarray(addrs), jnp.asarray(writes),
                         jnp.asarray(levels), warmup)
    s_par = engine.simulate_parallel(cfg, addrs, writes, levels, warmup)
    _assert_stats_equal(s_ser, s_par, f"{pred.value}/comp={comp}")


def test_engine_conv_only_config():
    """Extended tier disabled: the engine must skip the ext kernels and
    still reproduce the serial stats."""
    amap = asep.make_map(conv_sets=8, num_cache_chips=0, sets_per_chip=0)
    cfg = ctl.MorpheusConfig(amap=amap, conv_ways=4, ext_ways=4)
    addrs, writes, levels = _trace(span=512, seed=7)
    s_ser = ctl.simulate(cfg, jnp.asarray(addrs), jnp.asarray(writes),
                         jnp.asarray(levels), 0)
    s_par = engine.simulate_parallel(cfg, addrs, writes, levels, 0)
    _assert_stats_equal(s_ser, s_par, "conv-only")


def test_engine_warmup_exceeds_trace():
    """warmup >= trace length zeroes every counter, like the oracle."""
    cfg = _cfg()
    addrs, writes, levels = _trace(n=500, seed=3)
    s_par = engine.simulate_parallel(cfg, addrs, writes, levels, 500)
    for f in ctl._INT_FIELDS:
        assert int(getattr(s_par, f)) == 0, f


def test_simulate_batch_matches_individual():
    """Batching traces must not change any per-trace result."""
    cfg = _cfg(predictor=ctl.Predictor.BLOOM)
    traces = [(_trace(seed=s)[0], _trace(seed=s)[1], _trace(seed=s)[2], 100)
              for s in (1, 2, 3)]
    batched = engine.simulate_batch(cfg, traces)
    for i, (a, w, l, warm) in enumerate(traces):
        single = engine.simulate_parallel(cfg, a, w, l, warm)
        for f in ctl.Stats._fields:
            got = np.asarray(getattr(batched, f))[i]
            want = np.asarray(getattr(single, f))
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                       err_msg=f"trace {i} field {f}")


def test_run_batch_matches_per_point_run():
    """Sweep-layer regression: run_batch == per-point run, and both equal
    the serial-oracle pipeline on the Stats."""
    pts = [
        cs.RunPoint("kmeans", "BL", 18, 0, 6000),
        cs.RunPoint("kmeans", "BL", 48, 0, 6000),
        cs.RunPoint("cfd", "Morpheus-ALL", 32, 24, 6000),
        cs.RunPoint("histo", "Unified-SM-Mem", 32, 0, 6000),
    ]
    batched = cs.run_batch(pts)
    for pt, rb in zip(pts, batched):
        r1 = cs.run(pt.app, pt.system, n_compute=pt.n_compute,
                    n_cache=pt.n_cache, length=pt.length, seed=pt.seed)
        assert r1.exec_time_s == rb.exec_time_s, pt
        assert r1.ipc == rb.ipc, pt
        # against the serial oracle
        cfg, (a, w, l, warm), n_c, n_k, n_acc = cs._prepare(pt)
        s_ser = ctl.simulate_jit(cfg, jnp.asarray(a), jnp.asarray(w),
                                 jnp.asarray(l), warm)
        _assert_stats_equal(ctl.Stats(*[np.asarray(x) for x in s_ser]),
                            rb.stats, f"{pt.app}/{pt.system}")


def test_run_batch_padding_chunk():
    """A group size that is not a power of two exercises the padded final
    chunk; padded duplicates must not leak into the results."""
    pts = [cs.RunPoint("cfd", "BL", n, 0, 4000) for n in
           (10, 14, 18, 24, 32)]  # 5 points -> chunks of 16? no: [8] pad 3
    res = cs.run_batch(pts)
    assert [r.n_compute for r in res] == [10, 14, 18, 24, 32]
    assert len({r.exec_time_s for r in res}) > 1  # distinct grid points


def test_run_batch_one_stats_readback_per_dispatch():
    """Two configs, one of them a padded chunk: one Stats readback per
    dispatch, and every per-point field equals the batch's row in value
    and dtype."""
    pts = [cs.RunPoint("cfd", "BL", n, 0, 3000) for n in (10, 14, 18)] + \
        [cs.RunPoint("cfd", "Morpheus-ALL", 32, 24, 3000)]
    obs.enable(trace=False)
    try:
        res = cs.run_batch(pts)
        reg = obs.metrics_registry()
        readbacks = reg.get("stats_readbacks").values
        dispatches = reg.get("engine_dispatches").values
        got_bytes = reg.get("device_get_bytes").total()
    finally:
        obs.disable()
    key = (("path", "batch"),)
    assert readbacks[key] == dispatches[key] == 2
    assert got_bytes > 0
    prepped = [cs._prepare(pt) for pt in pts]
    for idxs in ([0, 1, 2], [3]):
        cfg = prepped[idxs[0]][0]
        traces = [prepped[i][1] for i in idxs]
        traces += [traces[-1]] * (engine._bucket(len(idxs), minimum=1)
                                  - len(idxs))
        want_b = engine.simulate_batch(cfg, traces)
        for j, i in enumerate(idxs):
            for f in ctl.Stats._fields:
                got = getattr(res[i].stats, f)
                want = np.asarray(getattr(want_b, f)[j])
                assert isinstance(got, np.ndarray) and got.shape == ()
                assert got.dtype == want.dtype, (i, f)
                assert got == want, (i, f)


# ------------------------------------------------------- pallas backend

_pallas_ok, _pallas_why = engine.backend_status("pallas")
needs_pallas = pytest.mark.skipif(not _pallas_ok, reason=_pallas_why)


@needs_pallas
@pytest.mark.parametrize("pred,comp,warmup", list(itertools.product(
    list(ctl.Predictor), [False, True], [0, 311])))
def test_pallas_backend_matches_serial_oracle(pred, comp, warmup):
    """The fused Pallas scan (kernels/engine_scan) must reproduce the
    serial oracle bit-for-bit on integer Stats across the predictor x
    compression x warmup property grid (acceptance criterion)."""
    cfg = _cfg(predictor=pred, compression=comp)
    addrs, writes, levels = _trace(seed=_case_seed(pred.value, comp, warmup))
    s_ser = ctl.simulate(cfg, jnp.asarray(addrs), jnp.asarray(writes),
                         jnp.asarray(levels), warmup)
    s_pal = engine.simulate_parallel(cfg, addrs, writes, levels, warmup,
                                     backend="pallas")
    _assert_stats_equal(s_ser, s_pal,
                        f"pallas/{pred.value}/comp={comp}/warm={warmup}")


@needs_pallas
def test_pallas_backend_conv_only_config():
    """Extended tier disabled: the Pallas engine runs only the conv kernel
    and still matches the serial stats."""
    amap = asep.make_map(conv_sets=8, num_cache_chips=0, sets_per_chip=0)
    cfg = ctl.MorpheusConfig(amap=amap, conv_ways=4, ext_ways=4)
    addrs, writes, levels = _trace(span=512, seed=7)
    s_ser = ctl.simulate(cfg, jnp.asarray(addrs), jnp.asarray(writes),
                         jnp.asarray(levels), 0)
    s_pal = engine.simulate_parallel(cfg, addrs, writes, levels, 0,
                                     backend="pallas")
    _assert_stats_equal(s_ser, s_pal, "pallas/conv-only")


@needs_pallas
def test_run_batch_backend_threading():
    """RunPoint.backend reaches the engine: pallas and jnp points produce
    identical integer stats and identical derived metrics through the
    whole run_batch pipeline."""
    kw = dict(n_cache=8, length=3000)
    rj = cs.run_batch([cs.RunPoint("cfd", "Morpheus-ALL", 32,
                                   backend="jnp", **kw)])[0]
    rp = cs.run_batch([cs.RunPoint("cfd", "Morpheus-ALL", 32,
                                   backend="pallas", **kw)])[0]
    _assert_stats_equal(rj.stats, rp.stats, "run_batch jnp-vs-pallas")
    assert abs(rj.exec_time_s - rp.exec_time_s) <= 1e-3 * rj.exec_time_s


def test_backend_resolution():
    """Unknown / unsupported backends fail with an explanatory error, not
    a Pallas traceback; the default resolves to a supported backend."""
    b = engine.resolve_backend(None)
    assert b in engine.BACKENDS and engine.backend_status(b)[0]
    with pytest.raises(engine.BackendError, match="unknown backend"):
        engine.resolve_backend("cuda")


def test_platform_decides_interpret_and_backend(monkeypatch):
    """One resolver, from the platform: kernels interpret only on the CPU
    backend; on a TPU they compile and the engine defaults to Pallas; on
    a platform with no Pallas TPU lowering the Pallas engine is reported
    unavailable instead of replaced."""
    from repro.kernels import engine_scan, ops
    monkeypatch.delenv("REPRO_ENGINE_BACKEND", raising=False)
    assert ops.interpret_mode() is True          # the tests' CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False
    assert engine.default_backend() == "pallas"
    assert engine_scan.supported() == (True, "compiled Mosaic kernel")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert engine_scan.supported()[0] is False
    with pytest.raises(engine.BackendError, match="unavailable"):
        engine.resolve_backend("pallas")


# ------------------------------------------------------- pack edge cases

def test_pack_empty_trace():
    """A zero-length trace packs to zero-width buckets and simulates to
    all-zero stats on both backends."""
    cfg = _cfg()
    empty = (np.zeros(0, np.uint32), np.zeros(0, bool), np.zeros(0, np.int32))
    pt = engine.pack(cfg, [(empty[0], empty[1], empty[2], 0)])
    assert pt.conv_tag.shape[2] == 0 and pt.ext_tag.shape[2] == 0
    stats = engine.simulate_batch(cfg, [(*empty, 0)])
    for f in ctl.Stats._fields:
        assert float(np.asarray(getattr(stats, f))[0]) == 0.0, f


def test_pack_single_set_trace():
    """All requests landing in one conventional set: one dense row, the
    other rows fully padded, and the engine still matches the oracle."""
    cfg = _cfg()
    total = cfg.amap.total_sets
    n = 100
    addrs = (np.arange(n, dtype=np.uint32) * total + 2)  # gset == 2, conv
    writes = np.zeros(n, bool)
    levels = np.zeros(n, np.int32)
    pt = engine.pack(cfg, [(addrs, writes, levels, 0)])
    assert pt.conv_active[0, 2].sum() == n
    assert pt.conv_active[0].sum() == n          # every other row padding
    assert pt.ext_tag.shape[2] == 0              # ext tier saw nothing
    s_ser = ctl.simulate(cfg, jnp.asarray(addrs), jnp.asarray(writes),
                         jnp.asarray(levels), 0)
    s_par = engine.simulate_parallel(cfg, addrs, writes, levels, 0)
    _assert_stats_equal(s_ser, s_par, "single-set")


def test_pack_all_padding_rows_are_noops():
    """Sets with zero requests are provable no-ops: adding a second trace
    that only touches other sets must not change the first trace's row."""
    cfg = _cfg()
    total = cfg.amap.total_sets
    t1 = ((np.arange(40, dtype=np.uint32) * total + 1),
          np.zeros(40, bool), np.zeros(40, np.int32), 0)
    t2 = ((np.arange(64, dtype=np.uint32) * total + 3),
          np.zeros(64, bool), np.zeros(64, np.int32), 0)
    batched = engine.simulate_batch(cfg, [t1, t2])
    single = engine.simulate_batch(cfg, [t1])
    for f in ctl._INT_FIELDS:
        assert (np.asarray(getattr(batched, f))[0]
                == np.asarray(getattr(single, f))[0]), f


@pytest.mark.parametrize("n,expect", [(15, 16), (16, 16), (17, 32),
                                      (64, 64), (65, 128)])
def test_pack_pow2_padding_boundary(n, expect):
    """L lands exactly on the pow2 bucket when the max per-set count is a
    power of two; one extra request doubles the bucket."""
    cfg = _cfg()
    total = cfg.amap.total_sets
    addrs = (np.arange(n, dtype=np.uint32) * total)  # all -> set 0 (conv)
    pt = engine.pack(cfg, [(addrs, np.zeros(n, bool),
                            np.zeros(n, np.int32), 0)])
    assert pt.conv_tag.shape[2] == expect
    assert pt.conv_active[0, 0].sum() == n


# ---------------------------------------------- pack against a plain layout

_UNCOUNTED = -(1 << 30)


def _reference_pack(cfg, traces, pos0=None, count=None):
    """The layout ``engine.pack`` promises, one request at a time: append
    each request to its (tier, set) list in trace order, then pad every
    list to the busiest set's power-of-two length (at least 16) and each
    tier's set axis to whole scan tiles.  Returns the PackedTraces and the
    requests per tier."""
    total = max(cfg.amap.total_sets, 1)
    sc = cfg.amap.conv_sets
    lists, n_tier = [], {"conv": 0, "ext": 0}
    for i, (addrs, writes, levels, _) in enumerate(traces):
        per = {}
        for k in range(len(addrs)):
            a = int(addrs[k])
            g = a % total
            tier, s = ("ext", g - sc) if cfg.ext_enabled and g >= sc \
                else ("conv", g)
            p = (pos0[i] if pos0 is not None else 0) + k
            if count is not None and count[i] is not None \
                    and not count[i][k]:
                p = _UNCOUNTED
            per.setdefault((tier, s), []).append(
                (a // total, bool(writes[k]), int(levels[k]), p))
            n_tier[tier] += 1
        lists.append(per)

    def length(tier):
        most = max((len(v) for per in lists for (t, _), v in per.items()
                    if t == tier), default=0)
        return 0 if most == 0 else max(16, 1 << (most - 1).bit_length())

    (tc, nc), (te, ne) = engine.set_tiling(cfg)
    b, lc, le = len(traces), length("conv"), length("ext")
    conv = [np.zeros((b, tc * nc, lc), dt)
            for dt in (np.uint32, bool, np.int32, bool)]
    ext = [np.zeros((b, te * ne, le), dt)
           for dt in (np.uint32, bool, np.int32, np.int32, bool)]
    for i, per in enumerate(lists):
        for (tier, s), reqs in per.items():
            for slot, (tag, write, level, p) in enumerate(reqs):
                if tier == "conv":
                    cols = zip(conv, (tag, write, p, True))
                else:
                    cols = zip(ext, (tag, write, level, p, True))
                for arr, v in cols:
                    arr[i, s, slot] = v
    warmup = np.array([t[3] for t in traces], np.int32)
    return engine.PackedTraces(*conv, *ext, warmup), n_tier


def _layout_traces(total, lengths, seed):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 64 * total, n).astype(np.uint32),
             rng.random(n) < 0.3,
             rng.integers(0, 3, n).astype(np.int32),
             int(rng.integers(0, n + 1)))
            for n in lengths]


@pytest.mark.parametrize("system,n_cache,lengths,offsets,masked", [
    pytest.param("Morpheus-ALL", 40, [3000, 3000], False, False,
                 id="morpheus-all-1/8"),
    pytest.param("IBL-4x-LLC", 0, [3000, 3000], False, False,
                 id="ibl-4x-llc-no-ext"),
    pytest.param("Morpheus-ALL@1", 51, [6000, 6000], False, False,
                 id="fullscale-tiled"),
    pytest.param("Morpheus-ALL", 40, [3000, 0, 1700, 7], False, False,
                 id="ragged-lengths"),
    pytest.param("Morpheus-ALL", 40, [2000, 2000], True, False,
                 id="pos0"),
    pytest.param("Morpheus-ALL", 40, [2000, 1500, 2000], False, True,
                 id="count-mask"),
    pytest.param("Morpheus-ALL@1", 51, [4000, 2500], True, True,
                 id="fullscale-pos0-count"),
])
def test_pack_matches_plain_layout(system, n_cache, lengths, offsets,
                                   masked):
    """Every PackedTraces field equals the plain per-(tier, set) layout in
    value, dtype and shape, and the tier counters count each tier's
    requests."""
    cfg = cs.build_config(cs.SYSTEMS[system], n_cache)
    assert cfg.ext_enabled == (n_cache > 0)
    traces = _layout_traces(cfg.amap.total_sets, lengths,
                            _case_seed(system, lengths, offsets, masked))
    rng = np.random.default_rng(len(lengths))
    pos0 = [int(rng.integers(0, 1 << 20)) for _ in traces] \
        if offsets else None
    count = [None if i == 1 else rng.random(len(t[0])) < 0.6
             for i, t in enumerate(traces)] if masked else None
    want, n_tier = _reference_pack(cfg, traces, pos0, count)
    obs.disable()
    obs.enable(trace=False, metrics=True)
    try:
        got = engine.pack(cfg, traces, pos0=pos0, count=count)
        reg = obs.metrics_registry()
        per_tier = {dict(k)["tier"]: v
                    for k, v in reg.get("tier_requests").values.items()}
        tiers = {t: per_tier.get(t, 0) for t in ("conv", "ext")}
        slots = reg.get("packed_slots").total()
    finally:
        obs.disable()
    for f, w, g in zip(engine.PackedTraces._fields, want, got):
        assert (g.dtype, g.shape) == (w.dtype, w.shape), f
        np.testing.assert_array_equal(g, w, err_msg=f)
    assert tiers == n_tier
    assert slots == got.conv_tag.size + got.ext_tag.size


def test_pack_key_wider_than_16_bits():
    """More than 65 535 sets: the set key must not wrap at 16 bits, or set
    65 536 + s would be packed into set s."""
    amap = asep.make_map(conv_sets=66_000, num_cache_chips=2,
                         sets_per_chip=300)
    cfg = ctl.MorpheusConfig(amap=amap, conv_ways=4, ext_ways=4)
    total = amap.total_sets
    assert total > 65_535
    rng = np.random.default_rng(3)
    # sets s and 65 536 + s, both tiers, and random sets besides
    low = rng.integers(0, total - 65_536, 500)
    sets = np.concatenate([low, low + 65_536,
                           rng.integers(0, total, 2000)])
    addrs = (rng.integers(0, 8, len(sets)) * total + sets).astype(np.uint32)
    trace = (addrs, rng.random(len(addrs)) < 0.3,
             rng.integers(0, 3, len(addrs)).astype(np.int32), 0)
    want, _ = _reference_pack(cfg, [trace])
    got = engine.pack(cfg, [trace])
    assert got.ext_active.any() and got.conv_active[0, 65_536:].any()
    for f, w, g in zip(engine.PackedTraces._fields, want, got):
        assert (g.dtype, g.shape) == (w.dtype, w.shape), f
        np.testing.assert_array_equal(g, w, err_msg=f)
