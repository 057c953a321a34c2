"""Trace-driven GPU system model — the paper's nine evaluated systems (§6).

Combines the functional Morpheus controller (``controller.simulate``) with
an analytical execution-time model to produce the paper's reported metrics:
normalized execution time, IPC, perf/W, LLC throughput, NoC load, off-chip
bandwidth utilization, and MPKI.

Execution-time model (standard bottleneck/roofline composition):

    t_compute = insts / (n_compute * IPC_core * f)
    t_bw      = max(dram_bytes/BW_dram, conv_bytes/BW_conv, noc_bytes/BW_noc,
                    ext_bytes/(n_cache * BW_ext_core))
    t_lat     = sum(request latencies) / MLP,  MLP = n_compute * MLP_PER_CORE
    t_exec    = max(t_compute, t_bw, t_lat)

Memory-bound apps saturate when t_bw/t_lat dominate; the kmeans-style
perf *drop* at high core counts emerges from the simulator itself (more
interleaved streams -> longer reuse distance -> more DRAM traffic).
"""
from __future__ import annotations

import os
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, replace
from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from . import address_separation as asep
from . import engine
from .. import obs
from . import traces as tr
from .controller import MorpheusConfig, Predictor, Stats
from .energy import PaperGPU

SIM_SCALE = 8               # default ``SystemSpec.sim_scale``: a 1/8-scale
#                             memory system (capacities and working sets both
#                             scaled; behaviour of a set-associative LLC is
#                             ~invariant under this)


# --- the time model's constants, shared by every modelled GPU -------------
IPC_PER_CORE = 1.0          # warp-instructions/cycle/SM sustained
MLP_PER_CORE = 128.0        # outstanding memory requests per SM (48 warps
#                             x >2 outstanding loads; keeps the latency term
#                             from masking the bandwidth wall, Fig. 1 knee)
CONV_WAYS = 32
EXT_WAYS = 32
BW_EXT_CORE = 34e9          # §5: per cache-mode core
# §4.3.2: the native Indirect-MOV instruction turns every data-array access
# from 3 instructions (2 of them branches) into 1, raising the helper
# kernel's service throughput per cache-mode core
INDIRECT_MOV_EXT_BW_GAIN = 1.15
MAX_CACHE_FRAC = 0.75       # §4.1.3: up to 75% of SMs in cache mode


@dataclass(frozen=True)
class GPUSpec:
    """The modelled GPU: its cores, its LLC and the bandwidths of the
    analytical time model.  Field names are keys of a benchmark
    configuration's ``gpu`` block.  Latencies and energies are the paper's
    (``energy.PaperGPU``), whatever the GPU."""
    name: str
    total_cores: int
    freq_ghz: float
    conv_llc_bytes: int
    ext_bytes_per_core: int     # RF + L1/shared a cache-mode core lends
    bw_dram: float
    # Effective (not peak) conventional-LLC bandwidth: 0.4 of the L2's
    # published peak, Table 1's 120 of each partition's 300 GB/s.
    # Table 1's 1.2 TB/s sits in the ~1.2-1.9 TB/s that microbenchmarks
    # sustain on its class of part under real access mixes (Jia+ [31]);
    # using the peak would let a 4x-capacity LLC escape memory-boundedness
    # entirely, which contradicts the paper's Fig. 2 (avg 1.57x, not 4x).
    # It also makes Morpheus' extra banks matter, reproducing §7.4's split
    # between capacity and banking gains.
    bw_conv: float
    bw_noc: float               # 1.25 x bw_conv, Table 1's ratio

    @property
    def ext_sets_per_core(self) -> int:
        return self.ext_bytes_per_core // (EXT_WAYS * tr.BLOCK_BYTES)


# the paper's GPU (RTX 3080-like, Table 1); §5 'Combining': RF (32 warps)
# + L1 (16 warps), 328 KiB a cache-mode SM, 82 extended sets; L2 of 10
# partitions at 300 GB/s peak, 120 GB/s effective
TABLE1 = GPUSpec("Table1", total_cores=68, freq_ghz=1.44,
                 conv_llc_bytes=5 * (1 << 20), ext_bytes_per_core=328 * 1024,
                 bw_dram=760e9, bw_conv=10 * 120e9, bw_noc=1.5e12)
# NVIDIA A100 SXM4 40 GB (A100 whitepaper, 2020): 108 SMs at 1.41 GHz
# boost, 40 MB L2, 1 555 GB/s HBM2, L2 read bandwidth 5 120 B/clk (7.22
# TB/s peak at 1.41 GHz; bw_conv 0.4 of it, bw_noc 1.25 x bw_conv).  A
# cache-mode SM lends Table 1's share (328 of 384 KiB) of its 256 KiB RF +
# 192 KiB L1/shared, in whole 4 KiB sets: 95.
A100 = GPUSpec("A100", total_cores=108, freq_ghz=1.41,
               conv_llc_bytes=40 * (1 << 20), ext_bytes_per_core=95 * 4096,
               bw_dram=1555e9, bw_conv=2887.68e9, bw_noc=3609.6e9)


@dataclass(frozen=True)
class SystemSpec:
    name: str
    conv_scale: float = 1.0          # conventional LLC capacity multiplier
    morpheus: bool = False
    compression: bool = False
    indirect_mov: bool = False
    predictor: Predictor = Predictor.BLOOM
    mem_boost: float = 1.0           # Frequency-Boost: BW*, 1/latency*
    unified_extra_bytes: int = 0     # Unified-SM-Mem: extra per-core filter
    sim_scale: int = SIM_SCALE       # capacities and working sets at 1/scale
    gpu: GPUSpec = TABLE1


SYSTEMS: Dict[str, SystemSpec] = {
    "BL": SystemSpec("BL"),
    "IBL": SystemSpec("IBL"),
    "IBL-4x-LLC": SystemSpec("IBL-4x-LLC", conv_scale=4.0),
    "Frequency-Boost": SystemSpec("Frequency-Boost", mem_boost=1.15),
    "Unified-SM-Mem": SystemSpec("Unified-SM-Mem",
                                 unified_extra_bytes=232 * 1024),
    "Morpheus-Basic": SystemSpec("Morpheus-Basic", morpheus=True),
    "Morpheus-Compression": SystemSpec("Morpheus-Compression", morpheus=True,
                                       compression=True),
    "Morpheus-Indirect-MOV": SystemSpec("Morpheus-Indirect-MOV", morpheus=True,
                                        indirect_mov=True),
    "Morpheus-ALL": SystemSpec("Morpheus-ALL", morpheus=True,
                               compression=True, indirect_mov=True),
    # the paper's GPU at its published capacity: 1 280 conventional sets,
    # 82 extended sets per cache-mode core (up to 4 182)
    "Morpheus-ALL@1": SystemSpec("Morpheus-ALL@1", morpheus=True,
                                 compression=True, indirect_mov=True,
                                 sim_scale=1),
    # the same design on an A100-class GPU at its published capacity:
    # 10 240 conventional sets, 95 extended sets per cache-mode core
    # (7 695 at the 75% cap)
    "Morpheus-ALL@A100": SystemSpec("Morpheus-ALL@A100", morpheus=True,
                                    compression=True, indirect_mov=True,
                                    sim_scale=1, gpu=A100),
}


def build_config(spec: SystemSpec, n_cache: int) -> MorpheusConfig:
    gpu = spec.gpu
    conv_bytes = int(gpu.conv_llc_bytes * spec.conv_scale) // spec.sim_scale
    conv_sets = max(conv_bytes // (CONV_WAYS * tr.BLOCK_BYTES), 16)
    n_cache = n_cache if spec.morpheus else 0
    sets_per_chip = max(gpu.ext_sets_per_core // spec.sim_scale, 2)
    amap = asep.make_map(conv_sets=conv_sets, num_cache_chips=n_cache,
                         sets_per_chip=sets_per_chip)
    return MorpheusConfig(amap=amap, conv_ways=CONV_WAYS, ext_ways=EXT_WAYS,
                          compression=spec.compression,
                          predictor=spec.predictor,
                          indirect_mov=spec.indirect_mov)


def _unified_filter(addrs: np.ndarray, writes: np.ndarray, levels: np.ndarray,
                    n_cores: int, extra_bytes: int):
    """Unified-SM-Mem: absorb accesses that hit a per-core direct-mapped
    filter of the extra unified capacity (approximation of a bigger L1)."""
    sets = max(extra_bytes // tr.BLOCK_BYTES, 1)
    core = np.arange(len(addrs)) % max(n_cores, 1)
    set_idx = addrs % sets
    key = core.astype(np.uint64) * np.uint64(1 << 32) + set_idx.astype(np.uint64)
    order = np.argsort(key, kind="stable")
    sk, sa = key[order], addrs[order]
    hit_sorted = np.zeros(len(addrs), dtype=bool)
    same_slot = sk[1:] == sk[:-1]
    hit_sorted[1:] = same_slot & (sa[1:] == sa[:-1])
    hit = np.zeros_like(hit_sorted)
    hit[order] = hit_sorted
    keep = ~hit
    return addrs[keep], writes[keep], levels[keep]


@dataclass
class RunResult:
    app: str
    system: str
    n_compute: int
    n_cache: int
    exec_time_s: float
    ipc: float
    perf_per_watt: float
    stats: Stats
    llc_hit_rate: float
    mpki: float
    dram_GBps: float
    noc_GBps: float
    llc_throughput_GBps: float
    energy_J: float

    @property
    def llc_accesses(self) -> int:
        s = self.stats
        return int(s.conv_hits + s.conv_misses + s.ext_hits + s.ext_true_miss)


@dataclass(frozen=True)
class RunPoint:
    """One (app, system, mode-split, trace) grid point for ``run_batch``.

    ``backend`` picks the engine's inner-scan implementation ("jnp" or
    "pallas"; "" = session default, see ``engine.default_backend``) and is
    part of the batching key: points on different backends dispatch
    separately even under the same simulator config.

    ``overrides`` is the design-space hook for the autotuner: a sorted
    tuple of ``(field, value)`` pairs applied to the ``MorpheusConfig``
    after ``build_config`` (e.g. ``(("compression", True), ("ext_ways",
    16))``).  Overridable fields: ``conv_ways``, ``ext_ways``,
    ``compression``, ``predictor`` (the enum or its string value),
    ``indirect_mov``.  Points with different overrides produce different
    configs and therefore batch into different dispatch groups, exactly
    like points on different systems.
    """
    app: str
    system: str
    n_compute: int
    n_cache: int = 0
    length: int = 120_000
    seed: int = 0
    backend: str = ""
    overrides: Tuple[Tuple[str, object], ...] = ()


_OVERRIDABLE = ("conv_ways", "ext_ways", "compression", "predictor",
                "indirect_mov")


def apply_overrides(cfg: MorpheusConfig,
                    overrides: Tuple[Tuple[str, object], ...]
                    ) -> MorpheusConfig:
    """Apply a ``RunPoint.overrides`` tuple to a built config.

    Unknown fields fail loudly — a typo in a search-space knob must not
    silently search nothing.  ``predictor`` accepts the ``Predictor``
    enum or its string value (search spaces serialize to JSON)."""
    if not overrides:
        return cfg
    kw = {}
    for field_name, value in overrides:
        if field_name not in _OVERRIDABLE:
            raise ValueError(f"override of {field_name!r} not supported "
                             f"(allowed: {_OVERRIDABLE})")
        if field_name == "predictor" and not isinstance(value, Predictor):
            value = Predictor(value)
        if field_name in ("conv_ways", "ext_ways"):
            value = int(value)
        if field_name in ("compression", "indirect_mov"):
            value = bool(value)
        kw[field_name] = value
    return replace(cfg, **kw)


def _resolve(pt: RunPoint) -> Tuple[MorpheusConfig, int, int]:
    """The cheap half of preparing a point: the §7.1 mode-split override
    and the config, from which its dispatch group is known without a
    trace.  Returns (cfg, resolved n_compute, resolved n_cache)."""
    spec = SYSTEMS[pt.system]
    n_compute, n_cache = pt.n_compute, pt.n_cache
    if not tr.WORKLOADS[pt.app].memory_bound and spec.morpheus:
        n_cache = 0   # §7.1 obs. 5: all cores stay in compute mode
        n_compute = spec.gpu.total_cores
    cfg = apply_overrides(build_config(spec, n_cache), pt.overrides)
    return cfg, n_compute, n_cache


def _generate(pt: RunPoint, n_compute: int):
    """The costly half: the point's trace (the Unified-SM-Mem filter
    applied) and its post-warm-up access count.  Pure numpy, a function
    of the point alone, so ``run_batch`` runs it on pool threads.

    Returns (trace-tuple-for-engine, post-warmup access count)."""
    spec = SYSTEMS[pt.system]
    addrs, writes, levels = tr.generate(pt.app, n_cores=n_compute,
                                        length=pt.length, seed=pt.seed,
                                        ws_scale=1.0 / spec.sim_scale)
    if spec.unified_extra_bytes:
        addrs, writes, levels = _unified_filter(addrs, writes, levels,
                                                n_compute,
                                                spec.unified_extra_bytes)
    # exclude the compulsory-miss warmup (one pass over the working set,
    # capped at half the trace) so stats reflect steady state
    ws_blocks = (tr.WORKLOADS[pt.app].working_set_bytes // spec.sim_scale
                 // tr.BLOCK_BYTES)
    warmup = int(min(len(addrs) // 2, ws_blocks))
    return (addrs, writes, levels, warmup), len(addrs) - warmup


def _prepare(pt: RunPoint):
    """Resolve a point: mode-split overrides, trace generation, config.

    Returns (cfg, trace-tuple-for-engine, resolved n_compute/n_cache,
    post-warmup access count)."""
    cfg, n_compute, n_cache = _resolve(pt)
    trace, n_acc = _generate(pt, n_compute)
    return cfg, trace, n_compute, n_cache, n_acc


def _finalize(pt: RunPoint, n_compute: int, n_cache: int, n_acc: int,
              stats: Stats, *, insts: float | None = None,
              knee: float | None = None) -> RunResult:
    """Analytical execution-time / power model on top of simulated Stats.

    ``insts``/``knee`` override the app-profile-derived warp-instruction
    count and DRAM contention knee — a multi-tenant epoch mixes apps with
    different arithmetic intensities, so the workload replayer passes the
    slice's exact request-weighted values (``repro.workloads.tenancy``)
    instead of attributing the whole epoch to the dominant app.
    """
    app, spec = pt.app, SYSTEMS[pt.system]
    gpu = spec.gpu
    w = tr.WORKLOADS[app]
    if insts is None:
        insts = tr.instructions_for(app, n_acc)
    if knee is None:
        knee = w.contention_knee
    costs = PaperGPU()

    boost = spec.mem_boost
    t_compute = insts / (n_compute * IPC_PER_CORE * gpu.freq_ghz * 1e9)
    # DRAM row-buffer locality: interleaving more streams than the app's
    # knee degrades effective DRAM bandwidth (the Fig. 1 'drop' mechanism)
    row_locality = max(0.2, min(1.0, knee / max(n_compute, 1)))
    t_dram = float(stats.dram_bytes) / (gpu.bw_dram * boost * row_locality)
    t_conv = float(stats.conv_bytes) / (gpu.bw_conv * boost)
    t_noc = float(stats.noc_bytes) / (gpu.bw_noc * boost)
    ext_bw = BW_EXT_CORE * (INDIRECT_MOV_EXT_BW_GAIN if spec.indirect_mov
                            else 1.0)
    t_ext = (float(stats.noc_bytes) / (max(n_cache, 1) * ext_bw)
             if spec.morpheus and n_cache else 0.0)
    t_lat = float(stats.latency_ns) * 1e-9 / (boost * n_compute * MLP_PER_CORE)
    t_exec = max(t_compute, t_dram, t_conv, t_noc, t_ext, t_lat)

    # zero-work slice (a departed/idle tenant's epoch in the QoS
    # runtime): no instructions and no traffic means no time — report
    # zero IPC instead of 0/0
    ipc = insts / (t_exec * gpu.freq_ghz * 1e9) if t_exec > 0 else 0.0

    mem_energy_J = float(stats.energy_nJ) * 1e-9
    power = costs.static_power_W + costs.core_power_W * (n_compute + n_cache)
    if spec.morpheus:
        power *= 1.0 + costs.controller_power_frac
    power += mem_energy_J / max(t_exec, 1e-12)
    energy_J = power * t_exec
    ppw = ipc / power

    hits = float(stats.conv_hits + stats.ext_hits)
    total = float(hits + stats.conv_misses + stats.ext_true_miss)
    llc_bytes = float(stats.conv_bytes + stats.noc_bytes)
    return RunResult(
        app=app, system=pt.system, n_compute=n_compute, n_cache=n_cache,
        exec_time_s=t_exec, ipc=ipc, perf_per_watt=ppw, stats=stats,
        llc_hit_rate=hits / max(total, 1.0),
        mpki=1000.0 * float(stats.conv_misses + stats.ext_true_miss)
        / max(insts, 1.0),
        dram_GBps=float(stats.dram_bytes) / max(t_exec, 1e-12) / 1e9,
        noc_GBps=float(stats.noc_bytes) / max(t_exec, 1e-12) / 1e9,
        llc_throughput_GBps=llc_bytes / max(t_exec, 1e-12) / 1e9,
        energy_J=energy_J,
    )


# ------------------------------------------------------------ batched sweep

# Points per engine dispatch.  The last chunk of a config-group is padded
# (by repeating its final trace) to a power of two so the whole sweep
# touches at most a handful of compiled batch shapes per config.
BATCH_CHUNK = 16


def _chunk_lengths(n: int) -> List[int]:
    out = [BATCH_CHUNK] * (n // BATCH_CHUNK)
    rem = n % BATCH_CHUNK
    if rem:
        out.append(engine._bucket(rem, minimum=1))
    return out


# requests per core stream that each prefetch thread needs (PERF.md, the
# width curve on the TPU v5e host)
STREAM_PER_THREAD = 8192


def _prefetch_width(n_points: int, per_stream: int, cpus: int) -> int:
    """Threads that make a ``run_batch`` call's traces ahead of its
    dispatches: ``min(points, cpus, cap)``, at least 1.

    ``per_stream`` is the call's requests per core stream (``length //
    n_compute``, the median over its points).  It decides how much of
    ``tr.generate`` runs in numpy with the interpreter lock released.  A
    short stream spends its time in the per-core Python loop, which holds
    the lock: one thread then overlaps the calling thread's packing and
    device waits, and a second only contends with both.  A long stream
    widens the pool, one thread per ``STREAM_PER_THREAD`` requests, up to
    the CPUs."""
    cap = max(1, per_stream // STREAM_PER_THREAD)
    return max(1, min(n_points, cpus, cap))


class _TracePrefetch:
    """``run_batch``'s traces, made ahead of its dispatches on a pool.

    ``start`` submits every point's ``_generate`` in dispatch order;
    ``take`` hands over one chunk's traces, blocking until they exist.
    A single point takes no pool: ``take`` makes its trace.  Closing
    cancels what has not started, so an error does not wait for the rest
    of the sweep's traces."""

    def __init__(self, points: Sequence[RunPoint]):
        self._points = points
        self._n_compute: List[int] = []
        self._pool: ThreadPoolExecutor | None = None
        self._ahead: Dict[int, Future] = {}

    def start(self, resolved, plan) -> int:
        """Submit the generation of every point; returns the width."""
        self._n_compute = [n_compute for _, n_compute, _ in resolved]
        streams = [pt.length // max(n, 1)
                   for pt, n in zip(self._points, self._n_compute)]
        width = _prefetch_width(len(streams),
                                int(np.median(streams)) if streams else 0,
                                len(os.sched_getaffinity(0)))
        if len(streams) > 1:
            self._pool = ThreadPoolExecutor(width)
            self._ahead = {i: self._pool.submit(_generate, self._points[i],
                                                self._n_compute[i])
                           for _, _, chunk, _ in plan for i in chunk}
        return width

    def take(self, chunk: Sequence[int]) -> list:
        """(trace, post-warm-up access count) of each point of ``chunk``."""
        futures = [self._ahead.pop(i, None) for i in chunk]
        ready = self._pool is not None and all(f.done() for f in futures)
        obs.count("trace_prefetch", 1, ready="yes" if ready else "no")
        return [f.result() if f is not None
                else _generate(self._points[i], self._n_compute[i])
                for f, i in zip(futures, chunk)]

    def __enter__(self) -> "_TracePrefetch":
        return self

    def __exit__(self, *exc) -> bool:
        if self._pool is not None:
            self._pool.shutdown(cancel_futures=True)
        return False


def _dispatch_plan(points: Sequence[RunPoint], resolved) -> list:
    """The dispatches of a sweep, in order: (cfg, backend, chunk of point
    indices, compiled batch length) per dispatch, group after group."""
    groups: Dict[tuple, List[int]] = {}
    for i, (cfg, _, _) in enumerate(resolved):
        backend = engine.resolve_backend(points[i].backend or None)
        groups.setdefault((cfg, backend), []).append(i)
    plan = []
    for (cfg, backend), idxs in groups.items():
        done = 0
        for blen in _chunk_lengths(len(idxs)):
            plan.append((cfg, backend, idxs[done:done + blen], blen))
            done += blen
    return plan


def run_batch(points: Sequence[RunPoint]) -> List[RunResult]:
    """Run many grid points through the set-parallel engine, batched.

    Points are grouped by simulator config (a config is a static compile
    parameter: set counts, flags, predictor); each group becomes vmapped
    engine dispatches over its traces instead of one recompiled serial
    scan per point.  Results come back in input order.

    This is the sweep primitive everything else (``run``, the mode-split
    policy, the benchmark figures) is built on: larger grids, multi-seed
    error bars and online mode-split search are all one ``run_batch``.

    Every point is resolved first on the calling thread (``_resolve``:
    mode split and config), which fixes the groups and chunks.  The
    traces (``_generate``) are then made ahead on a pool of
    ``_prefetch_width`` threads, submitted in dispatch order, so that
    generation overlaps the packing, dispatches and readbacks of the
    chunks before; the calling thread blocks only on the traces of the
    chunk it packs next.  The pool threads run numpy alone: no JAX call,
    no span.  A single point takes no pool: the calling thread makes its
    trace.  Every result is the same at any width.

    Spans: ``cache_sim.run_batch`` around the whole call (tags ``points``,
    ``groups``, ``workers``, the pool's width, and ``gpu``, the modelled
    GPUs of its systems by ``GPUSpec.name``), and per dispatch its
    phases ``cache_sim.prepare`` (the calling thread blocked on the
    chunk's traces; the first one also resolves every point),
    ``engine.pack`` and ``engine.dispatch`` (in ``simulate_batch``),
    ``cache_sim.wait`` (the host blocked on the device) and
    ``cache_sim.unpack`` (one ``jax.device_get`` of the dispatch's Stats,
    then per-point Stats sliced from the host arrays and ``_finalize``).
    Counters, one per dispatch: ``stats_readbacks{path="batch"}``, and
    ``trace_prefetch{ready}``, ``yes`` where the pool had made every trace
    of the chunk before the calling thread asked for them.
    """
    results: List[RunResult] = [None] * len(points)  # type: ignore
    gpus = sorted({SYSTEMS[pt.system].gpu.name for pt in points})
    with obs.span("cache_sim.run_batch", points=len(points),
                  gpu=",".join(gpus)) as sp, \
            _TracePrefetch(points) as ahead:
        with obs.span("cache_sim.prepare"):
            resolved = [_resolve(pt) for pt in points]
            plan = _dispatch_plan(points, resolved)
            sp.set(groups=len({(c, b) for c, b, _, _ in plan}),
                   workers=ahead.start(resolved, plan))
            made = ahead.take(plan[0][2]) if plan else []
        for k, (cfg, backend, chunk, blen) in enumerate(plan):
            traces = [trace for trace, _ in made]
            while len(traces) < blen:     # pad to the compiled shape
                traces.append(traces[-1])
            stats_b = engine.simulate_batch(cfg, traces, backend)
            with obs.span("cache_sim.wait"):
                jax.block_until_ready(stats_b)
            with obs.span("cache_sim.unpack"):
                # one transfer for the whole (B,) Stats, then rows
                # sliced on the host: slicing the device arrays per
                # point costs a device op and a sync per field
                host = jax.device_get(stats_b)
                obs.count("stats_readbacks", 1, path="batch")
                if obs.metrics_on():
                    obs.count("device_get_bytes",
                              sum(x.nbytes for x in host))
                for j, i in enumerate(chunk):
                    stats = Stats(*[np.asarray(x[j]) for x in host])
                    _, n_compute, n_cache = resolved[i]
                    results[i] = _finalize(points[i], n_compute, n_cache,
                                           made[j][1], stats)
            if k + 1 < len(plan):
                with obs.span("cache_sim.prepare"):
                    made = ahead.take(plan[k + 1][2])
    return results


def run(app: str, system: str, *, n_compute: int, n_cache: int = 0,
        length: int = 120_000, seed: int = 0,
        backend: str = "") -> RunResult:
    """Single-point wrapper over ``run_batch`` (kept for compatibility)."""
    return run_batch([RunPoint(app, system, n_compute, n_cache,
                               length, seed, backend)])[0]
