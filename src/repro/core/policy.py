"""Compute/cache mode-partition policy (paper Table 3 analogue).

The paper determines, offline per application, the number of cores in
compute mode that maximizes performance; the remainder go to cache mode
(bounded by 75% of cores, §4.1.3).  This module reproduces that offline
sweep against the system model, and is also what the serving launcher uses
to decide how many chips of a pod to dedicate to the extended cache tier.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

from . import cache_sim as cs
from . import traces as tr


@dataclass(frozen=True)
class ModeSplit:
    app: str
    system: str
    n_compute: int
    n_cache: int
    exec_time_s: float


DEFAULT_GRID: Sequence[int] = (10, 14, 18, 24, 32, 40, 48, 56, 62, 68)


def grid_points(app: str, system: str, *, grid: Sequence[int],
                length: int, seed: int = 0, backend: str = "",
                overrides: Sequence[tuple] = ()) -> List[cs.RunPoint]:
    """The sweep points of one (app, system): each compute-core count in
    the grid, cache mode getting the rest (Morpheus) or power-gating
    (IBL).  Grid entries whose Morpheus cache side would be empty are
    dropped.  ``backend`` (engine inner-scan implementation) and
    ``overrides`` (config-field overrides, see ``cs.RunPoint``) are
    carried on every point — the autotuner sweeps overridden design
    points through exactly this path."""
    spec = cs.SYSTEMS[system]
    w = tr.WORKLOADS[app]
    ov = tuple(sorted(tuple(o) for o in overrides))
    pts = []
    for n_compute in grid:
        n_cache = 0
        if spec.morpheus and w.memory_bound:
            n_cache = min(spec.gpu.total_cores - n_compute,
                          int(spec.gpu.total_cores * cs.MAX_CACHE_FRAC))
            if n_cache <= 0:
                continue
        pts.append(cs.RunPoint(app, system, n_compute, n_cache, length,
                               seed, backend, ov))
    return pts


def sweep(points: Sequence[cs.RunPoint]) -> Dict[tuple, ModeSplit]:
    """Run an arbitrary set of sweep points through ``cs.run_batch`` and
    reduce to the fastest split per (app, system)."""
    best: Dict[tuple, ModeSplit] = {}
    for pt, r in zip(points, cs.run_batch(points)):
        key = (pt.app, pt.system)
        if key not in best or r.exec_time_s < best[key].exec_time_s:
            best[key] = ModeSplit(pt.app, pt.system, r.n_compute, r.n_cache,
                                  r.exec_time_s)
    return best


def best_split(app: str, system: str, *, grid: Sequence[int] = DEFAULT_GRID,
               length: int = 60_000, seed: int = 0,
               backend: str = "") -> ModeSplit:
    """Sweep compute-core counts for one (app, system); one batched
    dispatch per config shape instead of a recompiled run per point."""
    pts = grid_points(app, system, grid=grid, length=length, seed=seed,
                      backend=backend)
    assert pts, f"empty sweep grid for {app}/{system}"
    return sweep(pts)[(app, system)]


def table3(systems: Sequence[str] = ("IBL", "Morpheus-Basic", "Morpheus-ALL"),
           apps: Sequence[str] | None = None, *, length: int = 120_000,
           backend: str = "") -> Dict[str, Dict[str, ModeSplit]]:
    """Paper Table 3: per-app compute-core counts for each system.

    All (system, app, grid) points go through ONE ``run_batch`` so points
    sharing a config shape share compiled executables and dispatches.
    The default ``length`` is the full-profile trace length — the batched
    engine made the sweep cheap enough to run paper-grade by default
    (pass a smaller length for smoke runs)."""
    apps = list(apps or (tr.MEMORY_BOUND + tr.COMPUTE_BOUND))
    pts: List[cs.RunPoint] = []
    for system in systems:
        for app in apps:
            pts.extend(grid_points(app, system, grid=DEFAULT_GRID,
                                   length=length, backend=backend))
    best = sweep(pts)
    return {system: {app: best[(app, system)] for app in apps}
            for system in systems}
