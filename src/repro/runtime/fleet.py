"""Fleet-scale sharded serving: advance N governed replicas per dispatch.

The scalar runtime (``runtime.governor.simulate_online``) advances ONE
replica per ``engine.advance_packed`` dispatch; a fleet of N replicas in
a Python loop pays N dispatches, N Stats readbacks and N telemetry syncs
per epoch.  This module turns that loop inside out.  Each replica is an
``OnlineReplica`` (same prologue + host epilogue code as the scalar
path); per fleet step the live replicas are grouped by their current
engine config (identical ``MorpheusConfig`` means identical state
shapes), each group's trace slices are packed in ONE ``engine.pack``
call, the replicas' ``EngineState`` rows are concatenated along the
leading batch dim, padded to a power-of-two row bucket — and to the
mesh axis (``distributed.sharding.fleet_padding``) — and the whole
group advances in one jitted and, over a multi-device mesh, one
``shard_map``-sharded dispatch (``launch.mesh.make_fleet_mesh`` builds
the 1-D ``("fleet",)`` mesh; on CPU devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  Stats deltas
and the extended-tier telemetry arrays return in ONE batched
``jax.device_get`` per group, so per-epoch host syncs are O(groups),
not O(replicas).

Each state row's set-scans are independent, so the batched step is
bit-identical per replica to the scalar path: integer Stats exactly,
and the governors — fed the same numbers through the same numpy reward
path with per-replica RNG streams — make the same decisions.
``tests/test_fleet.py`` pins N=1 and N=4 against serial
``simulate_online`` on both engine backends.

Cross-replica learning: a ``SplitAdvisor`` remembers, per workload mix,
the best split and phase/context tables any replica converged to
(snapshots via ``Governor.export_state``); a new replica serving a
known mix warm-starts there instead of re-climbing the candidate
ladder.  ``benchmarks/fig_fleet.py`` ablates the advisor on/off and
reports aggregate IPC + convergence time vs. replica count.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import cache_sim as cs
from ..core import engine
from ..distributed.sharding import FLEET_AXIS, fleet_padding, fleet_spec
from .governor import GovernorConfig, OnlineReplica, OnlineResult
from .telemetry import EpochRecord, TelemetryLog, merge_logs

Split = Tuple[int, int]


@dataclass
class ReplicaSpec:
    """Constructor arguments of one fleet replica (``OnlineReplica``).

    ``phases`` is anything ``simulate_online`` accepts: one app name, a
    sequence of apps replayed back to back, or a composed multi-tenant
    ``workloads.Workload`` (each tenant contributes one state row to the
    fleet batch).
    """
    phases: object
    system: str = "Morpheus-ALL"
    length: int = 60_000
    epoch_len: int = 3_000
    window_s: Optional[float] = None
    target_epoch: Optional[int] = None
    seed: int = 0
    gcfg: GovernorConfig = field(default_factory=GovernorConfig)
    candidates: Optional[Sequence[Split]] = None
    fixed_split: Optional[Split] = None
    warm_handoff: bool = True
    burn_in: Optional[int] = None
    name: str = ""
    # optional per-tenant SLO budgeter (workloads.serving
    # TenantSLOBudgeter) — the replica feeds it per-epoch tenant costs
    # and turns envelope overruns into governor overload pressure
    # (docs/qos.md).  One instance per spec: the budgeter is mutable
    # learned state, so specs must not share it.
    slo: Optional[object] = None

    def build(self) -> OnlineReplica:
        return OnlineReplica(
            self.phases, self.system, length=self.length,
            epoch_len=self.epoch_len, window_s=self.window_s,
            target_epoch=self.target_epoch, seed=self.seed,
            gcfg=self.gcfg, candidates=self.candidates,
            fixed_split=self.fixed_split, warm_handoff=self.warm_handoff,
            burn_in=self.burn_in, name=self.name, slo=self.slo)


class SplitAdvisor:
    """Shared cross-replica split memory, keyed by workload mix.

    Replicas report their governor's best-estimated split — plus the
    phase/context tables out of a ``Governor.export_state`` snapshot —
    under their ``OnlineReplica.mix_key`` (system + sorted served apps).
    Building a replica for a known mix warm-starts it: the governor
    begins AT the advised split (the cache is still cold, so the usual
    post-transition warm-up epochs apply) and, when the candidate
    ladders match, inherits the phase/context tables so remembered
    phases jump instead of re-climbing.  The advice is a prior, not a
    constraint: estimates start fresh, and a stale advice is walked
    away from by ordinary greedy moves.
    """

    def __init__(self):
        self.table: Dict[Tuple, Dict] = {}
        self.reports = 0
        self.warm_starts = 0

    def report(self, rep: OnlineReplica) -> None:
        """Record a replica's current best estimate for its mix.  The
        mix entry keeps whichever replica's estimate is highest."""
        gov = rep.gov
        if rep.fixed_split is not None or not gov.measured:
            return
        best = gov.best_estimate()
        if best is None:
            return
        split, est = best
        self.reports += 1
        e = self.table.get(rep.mix_key)
        if e is not None and est < e["est"]:
            return
        s = gov.export_state()
        self.table[rep.mix_key] = {
            "split": tuple(split), "est": float(est),
            "candidates": tuple(gov.candidates),
            "phase_table": dict(s.phase_table),
            "ctx_table": dict(s.ctx_table)}

    def warm_start(self, rep: OnlineReplica) -> bool:
        """Seed a FRESH replica (no epochs consumed yet) from its mix's
        remembered entry; returns whether advice was applied."""
        gov = rep.gov
        e = self.table.get(rep.mix_key)
        if e is None or rep.fixed_split is not None or gov.epoch > 0:
            return False
        cands = tuple(gov.candidates)
        want = e["split"]
        j = cands.index(want) if want in cands else min(
            range(len(cands)), key=lambda k: abs(cands[k][0] - want[0]))
        # on a fresh governor this is exactly ``Governor(initial=j)``:
        # dwell 0, warm-up pending, nothing measured
        gov._i = j
        if cands == e["candidates"]:
            gov.phase_table.update(e["phase_table"])
            gov.ctx_table.update(e["ctx_table"])
        # the replica initialised its EngineState for the pre-advice
        # split; state shapes are per-config, so rebuild the (still
        # empty) state for the advised one
        rep.state = engine.init_state(
            cs.build_config(rep.spec, gov.current[1]), rep.n_tenants)
        self.warm_starts += 1
        return True


def build_replicas(specs: Sequence[ReplicaSpec],
                   advisor: Optional[SplitAdvisor] = None
                   ) -> List[OnlineReplica]:
    """Build every spec; warm-start each from the advisor when given."""
    reps = []
    for spec in specs:
        rep = spec.build()
        if advisor is not None:
            advisor.warm_start(rep)
        reps.append(rep)
    return reps


# ------------------------------------------------------------ fleet step

_EMPTY_TRACE = (np.zeros(0, np.uint32), np.zeros(0, bool),
                np.zeros(0, np.int32), 0)


@lru_cache(maxsize=None)
def _pad_state(cfg, pad: int):
    # fresh rows fed zero-length traces: provable no-ops, reused forever
    return engine.init_state(cfg, pad)


@lru_cache(maxsize=None)
def _group_step(cfg, backend: str, mesh, rows: Tuple[int, ...], pad: int):
    """The whole fleet step — concatenate replica state rows, advance,
    split back — as ONE jitted callable, so a group epoch costs one
    dispatch regardless of replica count.  Doing the concat/split
    eagerly instead costs O(replicas x state leaves) op dispatches per
    epoch, which on a slow host dwarfs the step itself.  One executable
    per (config, backend, mesh, row partition, padding) — row bucketing
    (``fleet_padding``) keeps governor-driven group churn from
    exploding this cache."""
    def inner(pt, state):
        return engine._run_packed_state(cfg, pt, state, backend)
    if mesh is not None and dict(mesh.shape).get(FLEET_AXIS, 1) > 1:
        inner = jax.shard_map(inner, mesh=mesh,
                              in_specs=(fleet_spec(), fleet_spec()),
                              out_specs=(fleet_spec(), fleet_spec()),
                              check_vma=False)

    def step(states, pt):
        state = states[0] if len(states) == 1 else \
            jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *states)
        new_state, delta = inner(pt, state)
        outs, o = [], 0
        for k in rows:
            sl = slice(o, o + k)
            outs.append(jax.tree.map(lambda x: x[sl], new_state))
            o += k
        return tuple(outs), delta, new_state.ext_used, new_state.ext_valid

    return jax.jit(step)


def _advance_group(cfg, group, backend: str, mesh) -> None:
    """Advance one same-config group of replicas in a single dispatch.

    ``group`` is ``[(replica, traces, pos0, count)]`` straight from each
    replica's ``epoch_inputs()``.  All rows pack in one call, advance in
    one jitted concat+step+split dispatch, and read back in one
    ``jax.device_get``; each replica then consumes its row slice.
    """
    traces, pos0, count, rows = [], [], [], []
    for rep, t, p, m in group:
        rows.append((rep, len(t)))
        traces.extend(t)
        pos0.extend(p)
        count.extend(m if m is not None else [None] * len(t))
    b = len(traces)
    pad = fleet_padding(b, mesh)
    with obs.span("fleet.group_step", replicas=len(group), rows=b,
                  pad=pad,
                  config=f"conv{cfg.amap.conv_sets}/"
                         f"ext{cfg.amap.ext_sets}"):
        if pad:
            traces.extend([_EMPTY_TRACE] * pad)
            pos0.extend([0] * pad)
            count.extend([None] * pad)
        pt = engine.pack(cfg, traces, pos0=pos0, count=count)
        states = [rep.state for rep, _ in rows]
        if pad:
            states.append(_pad_state(cfg, pad))
        step = _group_step(cfg, backend, mesh,
                           tuple(k for _, k in rows), pad)
        new_states, delta, ext_used, ext_valid = step(tuple(states), pt)
        # the fleet path dispatches via _run_packed_state, bypassing the
        # advance_packed counter site
        obs.count("engine_dispatches", 1, path="fleet")
        # ONE batched host readback for the whole group: the Stats delta
        # the epilogues consume plus the extended-tier telemetry arrays
        # (on the scalar path _epoch_telemetry reads those from the
        # device state, one extra sync per replica per epoch)
        host_states = None
        if obs.inspector() is not None:
            # introspection rides the same single transfer: the decoded
            # snapshots need the whole carry on host, so the per-replica
            # states join the batched readback instead of adding one
            # device sync per replica
            host_delta, host_used, host_valid, host_states = \
                jax.device_get((delta, ext_used, ext_valid, new_states))
        else:
            host_delta, host_used, host_valid = jax.device_get(
                (delta, ext_used, ext_valid))
        if obs.metrics_on():
            obs.count("device_get_bytes",
                      sum(np.asarray(x).nbytes for x in
                          jax.tree.leaves((host_delta, host_used,
                                           host_valid))))
        o = 0
        for i, ((rep, k), st) in enumerate(zip(rows, new_states)):
            sl = slice(o, o + k)
            rep.consume(st, jax.tree.map(lambda x: x[sl], host_delta),
                        ext_used=host_used[sl], ext_valid=host_valid[sl],
                        host_state=None if host_states is None
                        else host_states[i])
            o += k


# ---------------------------------------------------------------- drivers

def convergence_epoch(records: Sequence[EpochRecord]) -> int:
    """First epoch from which the run never left its final split again
    (0: started there and stayed) — the figure's convergence metric."""
    if not records:
        return 0
    final = (records[-1].n_compute, records[-1].n_cache)
    c = 0
    for i, r in enumerate(records):
        if (r.n_compute, r.n_cache) != final:
            c = i + 1
    return c


@dataclass
class FleetResult:
    """Outcome of one ``simulate_fleet`` run."""
    results: List[OnlineResult]       # per replica, spec order
    names: List[str]
    epochs: int                       # fleet steps taken (max over replicas)
    dispatches: int                   # engine dispatches issued
    mesh_devices: int
    backend: str
    advisor: Optional[SplitAdvisor] = None

    @property
    def n_replicas(self) -> int:
        return len(self.results)

    def merged_log(self, capacity: Optional[int] = None) -> TelemetryLog:
        """Every replica's telemetry in one epoch-interleaved log."""
        return merge_logs([r.log for r in self.results], capacity)

    def aggregate_ipc(self) -> float:
        """Fleet-aggregate modeled IPC: total instructions retired over
        total modeled time (the time-weighted mean of replica IPCs)."""
        t = sum(r.exec_time_s for r in self.results)
        insts_over_freq = sum(r.ipc * r.exec_time_s for r in self.results)
        return insts_over_freq / t if t > 0 else 0.0

    def convergence_epochs(self) -> List[int]:
        return [convergence_epoch(r.records) for r in self.results]

    def summary(self) -> Dict:
        conv = self.convergence_epochs()
        return {
            "replicas": self.n_replicas,
            "epochs": self.epochs,
            "dispatches": self.dispatches,
            "mesh_devices": self.mesh_devices,
            "backend": self.backend,
            "aggregate_ipc": self.aggregate_ipc(),
            "mean_convergence_epoch": float(np.mean(conv)) if conv else 0.0,
            "switches": sum(r.switches for r in self.results),
            "warm_starts": 0 if self.advisor is None
            else self.advisor.warm_starts,
        }


def simulate_fleet(specs, *, backend: Optional[str] = None,
                   mesh=None, advisor: Optional[SplitAdvisor] = None
                   ) -> FleetResult:
    """Advance a fleet of replicas, one dispatch per (config group, step).

    ``specs`` is a sequence of ``ReplicaSpec`` (or pre-built
    ``OnlineReplica``, e.g. warm-started ones).  Per step, live replicas
    running the same engine config advance together; replicas the
    governors have steered to different splits form separate groups
    (state shapes differ across configs, so they cannot share a batch).
    ``mesh``: a ``("fleet",)`` mesh from ``launch.mesh.make_fleet_mesh``
    shards each group's row dim via shard_map; None runs single-device.
    ``advisor``: warm-starts fresh replicas and collects per-epoch
    reports (cross-replica learning).
    """
    backend = engine.resolve_backend(backend)
    reps = [s if isinstance(s, OnlineReplica) else s.build() for s in specs]
    if advisor is not None:
        for rep in reps:
            advisor.warm_start(rep)
    dispatches = 0
    steps = 0
    while True:
        live = [r for r in reps if not r.done]
        if not live:
            break
        groups: Dict = {}
        for rep in live:
            cfg, traces, pos0, count = rep.epoch_inputs()
            groups.setdefault(cfg, []).append((rep, traces, pos0, count))
        for cfg, group in groups.items():
            _advance_group(cfg, group, backend, mesh)
            dispatches += 1
        if advisor is not None:
            for rep in live:
                advisor.report(rep)
        steps += 1
    n_dev = 1 if mesh is None else \
        int(np.prod(list(dict(mesh.shape).values()) or [1]))
    return FleetResult(results=[r.result() for r in reps],
                       names=[r.name for r in reps], epochs=steps,
                       dispatches=dispatches, mesh_devices=n_dev,
                       backend=backend, advisor=advisor)


def evaluate_governors(cells, gcfgs, *, system: str = "Morpheus-ALL",
                       candidates=None, target_epoch: Optional[int] = None,
                       epoch_len: int = 3_000,
                       backend: Optional[str] = None, mesh=None
                       ) -> List[List[OnlineResult]]:
    """Score K governor configs over M workload cells as ONE fleet run.

    The autotuner's batched governor-evaluation hook: every (config,
    cell) pair becomes one ``OnlineReplica`` replaying the SAME recorded
    workload under its own governor, and the whole K x M population
    advances through ``simulate_fleet`` — replicas whose governors sit
    at the same split share a dispatch group, so evaluating a
    generation costs one fleet run, not K x M serial ones.

    ``cells`` is a sequence of composed ``workloads.Workload`` (or
    anything ``OnlineReplica`` accepts as phases); ``candidates`` is one
    shared transition ladder or a per-cell sequence of ladders.
    Returns ``results[k][m]`` — config ``gcfgs[k]`` on ``cells[m]`` —
    bit-identical per replica to K x M ``simulate_online`` calls.
    """
    cells = list(cells)
    if candidates is None or (candidates and
                              isinstance(candidates[0], tuple)):
        ladders = [candidates] * len(cells)
    else:
        ladders = list(candidates)
        assert len(ladders) == len(cells), \
            f"{len(ladders)} ladders for {len(cells)} cells"
    specs = [ReplicaSpec(cell, system, epoch_len=epoch_len,
                         target_epoch=target_epoch, gcfg=gcfg,
                         candidates=ladders[m], name=f"g{k}/c{m}")
             for k, gcfg in enumerate(gcfgs)
             for m, cell in enumerate(cells)]
    fr = simulate_fleet(specs, backend=backend, mesh=mesh)
    m = len(cells)
    return [fr.results[k * m:(k + 1) * m] for k in range(len(gcfgs))]


def run_serial(specs, *, backend: Optional[str] = None
               ) -> List[OnlineResult]:
    """The Python-loop baseline: every replica advanced one at a time,
    one dispatch per replica per epoch — exactly ``simulate_online``'s
    loop.  The tests' bit-identity reference."""
    backend = engine.resolve_backend(backend)
    reps = [s if isinstance(s, OnlineReplica) else s.build() for s in specs]
    for rep in reps:
        while not rep.done:
            cfg, traces, pos0, count = rep.epoch_inputs()
            pt = engine.pack(cfg, traces, pos0=pos0, count=count)
            state, delta_b = engine.advance_packed(cfg, pt, rep.state,
                                                   backend)
            host = jax.tree.map(np.asarray, delta_b)
            if obs.metrics_on():
                obs.count("device_get_bytes",
                          sum(x.nbytes for x in jax.tree.leaves(host)))
            rep.consume(state, host)
    return [rep.result() for rep in reps]
