"""Shared helpers for the benchmark suite.

Every module reproduces one paper table/figure and emits a CSV into
``benchmarks/out/`` plus a short validation verdict against the paper's
reported numbers (soft checks: printed PASS/WARN, never a hard failure —
the deliverable is the measurement, not a gate).
"""
from __future__ import annotations

import csv
import json
import os
import time
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

OUT_DIR = Path(__file__).resolve().parent / "out"
RESULTS_DIR = Path(__file__).resolve().parents[1] / "results"

# Benchmark profile: quick (CI smoke), std (default), full (paper-grade).
# The *cheap* sweeps (fig1 / fig2 / tab3's policy sweep) default to the
# full profile — the batched engine made them affordable — while the
# expensive multi-system modules stay on std; an explicit profile
# (env REPRO_BENCH_PROFILE or --profile) overrides BOTH.
TRACE_LEN_OF = {"quick": 12_000, "std": 40_000, "full": 120_000}
GRID_OF = {
    "quick": (18, 32, 48, 68),
    "std": (10, 18, 24, 32, 40, 48, 56, 68),
    "full": (10, 14, 18, 24, 28, 32, 36, 40, 44, 48, 53, 56, 62, 68),
}
MORPHEUS_GRID_OF = {
    "quick": (32, 48),
    "std": (18, 32, 40, 48, 56),
    "full": (10, 18, 24, 32, 40, 44, 48, 56, 62),
}

_PROFILE_ENV = os.environ.get("REPRO_BENCH_PROFILE") or None
PROFILE = _PROFILE_ENV or "std"
CHEAP_PROFILE = _PROFILE_ENV or "full"
TRACE_LEN = TRACE_LEN_OF[PROFILE]
CHEAP_TRACE_LEN = TRACE_LEN_OF[CHEAP_PROFILE]


def set_profile(profile: str) -> None:
    """Override the benchmark profile after import (used by module
    __main__ blocks that parse --profile themselves, e.g. fig_serving)."""
    global PROFILE, CHEAP_PROFILE, TRACE_LEN, CHEAP_TRACE_LEN, GRID
    global MORPHEUS_GRID, CHEAP_GRID
    assert profile in TRACE_LEN_OF, profile
    PROFILE = CHEAP_PROFILE = profile
    TRACE_LEN = CHEAP_TRACE_LEN = TRACE_LEN_OF[profile]
    GRID = CHEAP_GRID = GRID_OF[profile]
    MORPHEUS_GRID = MORPHEUS_GRID_OF[profile]

# Trace seeds per grid cell (env REPRO_BENCH_SEEDS or --seeds N on
# benchmarks.run / fig1 / fig2).  >1 turns fig1/fig2 cells into
# mean±std over seeds — each extra seed is just more RunPoints through
# one run_batch call (the PR-1 engine makes this nearly free).
SEEDS = max(int(os.environ.get("REPRO_BENCH_SEEDS", "1")), 1)


def set_seeds(n: int) -> None:
    """Override the per-cell seed count (used by figure __main__ blocks,
    which parse --seeds after this module was imported)."""
    global SEEDS
    SEEDS = max(int(n), 1)


def seed_list() -> List[int]:
    return list(range(SEEDS))


def mean_std(xs: Sequence[float]) -> Tuple[float, float]:
    """(mean, population std) of a per-seed value list."""
    import numpy as np
    a = np.asarray(list(xs), float)
    return float(a.mean()), float(a.std())


def fmt_mean_std(mean: float, std: float, prec: int = 3) -> str:
    """CSV cell for a per-seed aggregate: ``m`` at one seed, ``m±s``
    when --seeds turned the cell into a distribution."""
    if SEEDS <= 1:
        return f"{mean:.{prec}f}"
    return f"{mean:.{prec}f}±{std:.{prec}f}"
GRID = GRID_OF[PROFILE]
CHEAP_GRID = GRID_OF[CHEAP_PROFILE]
# Morpheus variants recompile per distinct cache-chip count; keep that grid
# small (compile cache is shared across apps since cfg is static).
MORPHEUS_GRID = MORPHEUS_GRID_OF[PROFILE]


def write_csv(name: str, header: Sequence[str],
              rows: Iterable[Sequence]) -> Path:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / f"{name}.csv"
    with path.open("w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        for r in rows:
            w.writerow(r)
    return path


def geomean(xs: Sequence[float]) -> float:
    import numpy as np
    xs = [max(float(x), 1e-12) for x in xs]
    return float(np.exp(np.mean(np.log(xs))))


def verdict(label: str, ok: bool, detail: str) -> str:
    tag = "PASS" if ok else "WARN"
    line = f"  [{tag}] {label}: {detail}"
    print(line)
    return line


class Timer:
    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        self.t0 = time.time()
        print(f"== {self.label} ...", flush=True)
        return self

    def __exit__(self, *exc):
        print(f"== {self.label} done in {time.time() - self.t0:.1f}s",
              flush=True)


# ---------------------------------------------------------------- policy
# Mode-split (Table 3) results are expensive (grid sweep per app x system);
# cache them on disk (results/policy_cache_<profile>.json) so fig12 /
# bw_analysis / tab3 share one sweep per profile.


def mode_splits(systems: Sequence[str], apps: Sequence[str],
                *, recompute: bool = False, backend: str = "",
                profile: str | None = None
                ) -> Dict[str, Dict[str, Tuple[int, int]]]:
    """{(system) -> {app -> (n_compute, n_cache)}} via the offline policy
    sweep (core/policy.py), cached on disk per profile.

    ``profile`` overrides the session profile for this sweep alone —
    tab3 passes ``CHEAP_PROFILE`` so the policy sweep defaults to the
    full grid while fig12/bw_analysis keep the session profile (their
    multi-system sweeps are the expensive part).

    All missing (system, app, grid) points are collected into ONE
    ``policy.sweep`` / ``cache_sim.run_batch`` call: points that share a
    config shape (same system flags and cache-chip count, across apps and
    compute-core counts) run as vmapped engine dispatches instead of one
    recompiled serial scan each.  ``backend`` selects the engine's
    inner-scan implementation ("" = session default).  Note the on-disk
    cache is shared across backends: a warm cache returns whichever
    backend computed it first.  Splits come from an argmin over
    float-derived exec times, which can differ between backends by
    accumulation order on near-tie grid cells — measured agreement is
    45/45 on the Table-3 sweep, so we accept
    that tie-break caveat rather than fragment the cache per backend."""
    from repro.core import cache_sim as cs
    from repro.core import policy
    from repro.core import traces as tr

    from repro.workloads.synthetic import TRACE_SCHEMA

    profile = profile or PROFILE
    cache_path = RESULTS_DIR / f"policy_cache_{profile}.json"
    grid, mgrid = GRID_OF[profile], MORPHEUS_GRID_OF[profile]
    trace_len = TRACE_LEN_OF[profile]
    cache: Dict[str, Dict[str, List[int]]] = {}
    if cache_path.exists() and not recompute:
        cache = json.loads(cache_path.read_text())
        # splits computed from a different trace-generator schema are
        # silently wrong for today's traces: discard, resweep
        if cache.pop("_trace_schema", None) != TRACE_SCHEMA:
            cache = {}

    changed = False
    pending: List[cs.RunPoint] = []
    for system in systems:
        sys_cache = cache.setdefault(system, {})
        spec = cs.SYSTEMS[system]
        for app in apps:
            if app in sys_cache:
                continue
            w = tr.WORKLOADS[app]
            if spec.morpheus and not w.memory_bound:
                # §7.1 obs. 5: compute-bound apps keep every core in
                # compute mode (cs.run enforces this; record it directly)
                sys_cache[app] = [cs.TABLE1.total_cores, 0]
                changed = True
                continue
            g = mgrid if (spec.morpheus and w.memory_bound) else grid
            pending.extend(policy.grid_points(app, system, grid=g,
                                              length=trace_len,
                                              backend=backend))
    if pending:
        for (app, system), split in policy.sweep(pending).items():
            cache[system][app] = [split.n_compute, split.n_cache]
        changed = True
    missing = [(s, a) for s in systems for a in apps if a not in cache[s]]
    assert not missing, f"mode_splits produced no split for {missing}"
    if changed:
        cache_path.parent.mkdir(parents=True, exist_ok=True)
        cache_path.write_text(json.dumps(
            {"_trace_schema": TRACE_SCHEMA, **cache}, indent=1))
    return {s: {a: (v[0], v[1]) for a, v in cache[s].items()}
            for s in systems}
