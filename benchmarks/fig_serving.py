"""Multi-tenant bursty serving replay through the online governor.

The workload-subsystem figure: where ``fig_online`` replays clean
phase-concatenated traces, this sweeps **burstiness x tenant mix** — K
tenants' traces merged by arrival time (``repro.workloads.tenancy``),
chunked into wall-clock epochs whose sizes swing with the arrival
process — and asks whether the adaptive governor still earns its keep
under contention:

  * governor vs. best-static IPC ratio per (mix, arrival) cell: the
    governor walks the coarse transition ladder online while each static
    baseline replays the same recorded stream under one pinned split;
  * per-tenant hit rates from the exact masked-replay Stats attribution
    (a tenant mixing with ``kmeans`` should see its hit rate depressed vs.
    running alone — the contention CABA-style scheduling worries about);
  * the per-tenant integer hit counters must sum to the global run's
    (the attribution invariant, checked every run).

Outputs ``benchmarks/out/fig_serving.csv`` (one row per run) and
``benchmarks/out/fig_serving_tenants.csv`` (per-tenant attribution).

  PYTHONPATH=src python -m benchmarks.fig_serving --profile quick
  PYTHONPATH=src python -m benchmarks.run --only serving
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.runtime import simulate_online
from repro.runtime.governor import SERVING_GCFG, candidates_for
from repro.workloads import arrivals as arrlib
from repro.workloads.serving import bursty_workload

from . import common as C

SYSTEM = "Morpheus-ALL"
# Same coarse transition ladder as fig_online: a real runtime spaces its
# rungs wide because transitions flush state.
LADDER_GRID = (18, 32, 48, 68)
N_CORES = 32                 # interleave width of the recorded streams

_MIXES = {"quick": ("cfd,kmeans",),
          "std": ("cfd,kmeans", "cfd,kmeans,lib"),
          "full": ("cfd,kmeans", "cfd,kmeans,lib", "spmv,stencil")}
# Arrival sweeps: deterministic (CV 0) -> Poisson (CV 1) -> two-state
# MMPP (CV >> 1).  Rates are requests/second of simulated time; the MMPP
# sojourns make bursts span several epochs.
_ARRIVALS = {
    "quick": (("det", "det:2e6"), ("mmpp", "mmpp:4e5,6e6,2e-3,6e-4")),
    "std": (("det", "det:2e6"), ("poisson", "poisson:2e6"),
            ("mmpp", "mmpp:4e5,6e6,2e-3,6e-4")),
    "full": (("det", "det:2e6"), ("poisson", "poisson:2e6"),
             ("mmpp", "mmpp:4e5,6e6,2e-3,6e-4"),
             ("onoff", "onoff:6e6,1.5e-3,3e-3")),
}
_LEN = {"quick": 60_000, "std": 150_000, "full": 240_000}
TARGET_EPOCH = 3_000


def _hits_sum_check(r) -> bool:
    """Per-tenant integer hit counters must sum to the global run's."""
    ok = True
    for f in ("conv_hits", "conv_misses", "ext_hits", "ext_true_miss"):
        tot = sum(int(np.asarray(getattr(s, f)))
                  for s in r.tenant_stats.values())
        ok &= tot == int(np.asarray(getattr(r.stats, f)))
    return ok


def run() -> Dict[str, float]:
    length = _LEN[C.PROFILE]
    rows: List[List] = []
    tenant_rows: List[List] = []
    out: Dict[str, float] = {}
    ratios = []
    finds = []
    sums_ok = []

    for mix in _MIXES[C.PROFILE]:
        for arr_name, arr_spec in _ARRIVALS[C.PROFILE]:
            # the shared corpus cell — the autotuner's governor objective
            # (repro.autotune.objectives) scores candidates on exactly
            # this construction
            wl = bursty_workload(mix, arr_spec, length=length,
                                 n_cores=N_CORES, seed=0, system=SYSTEM)
            cv = arrlib.burstiness(wl.t_s)
            ladder = candidates_for(wl.primary_app, SYSTEM,
                                    grid=LADDER_GRID, length=length)
            gov = simulate_online(wl, SYSTEM, target_epoch=TARGET_EPOCH,
                                  candidates=ladder, gcfg=SERVING_GCFG)
            sums_ok.append(_hits_sum_check(gov))
            best_split, best_ipc, best_static = None, 0.0, None
            for s in ladder:
                st = simulate_online(wl, SYSTEM, target_epoch=TARGET_EPOCH,
                                     fixed_split=s)
                rows.append(["static", mix, arr_name, f"{cv:.2f}",
                             f"({s[0]}|{s[1]})", "", f"{st.ipc:.3f}",
                             "", "", 0])
                if st.ipc > best_ipc:
                    best_split, best_ipc, best_static = s, st.ipc, st
            ratio = gov.ipc / best_ipc
            ratios.append(ratio)
            found_best = gov.converged_split == best_split
            finds.append(found_best)
            out[f"{mix}/{arr_name}"] = ratio
            epochs = [rec.requests for rec in gov.records]
            rows.append(["governor", mix, arr_name, f"{cv:.2f}", "adaptive",
                         f"({best_split[0]}|{best_split[1]})",
                         f"{gov.ipc:.3f}", f"{best_ipc:.3f}",
                         f"{ratio:.3f}", gov.switches])
            for name, hr in gov.tenant_hit_rates().items():
                tenant_rows.append([mix, arr_name, name, "governor",
                                    f"{hr:.4f}"])
            for name, hr in best_static.tenant_hit_rates().items():
                tenant_rows.append([mix, arr_name, name, "best-static",
                                    f"{hr:.4f}"])
            print(f"  {mix:>18} x {arr_name:<7} (CV {cv:4.2f}): governor "
                  f"{gov.ipc:7.3f} vs best static {best_ipc:7.3f} "
                  f"(ratio {ratio:.3f}, {gov.switches} switches, "
                  f"epochs {min(epochs)}..{max(epochs)} reqs) | "
                  f"tenant hits: " + " ".join(
                      f"{n}={h:.3f}"
                      for n, h in gov.tenant_hit_rates().items()))

    C.verdict("fig_serving.tenant-attribution-exact", all(sums_ok),
              f"per-tenant integer hit counters sum to the global Stats "
              f"in {sum(sums_ok)}/{len(sums_ok)} runs")
    C.verdict("fig_serving.governor-finds-best-split", all(finds),
              f"governor converged to the offline-best static split in "
              f"{sum(finds)}/{len(finds)} cells (no offline sweep needed)")
    C.verdict("fig_serving.governor-competitive",
              all(x >= 0.80 for x in ratios),
              f"governor IPC / best static IPC = "
              f"{['%.3f' % x for x in ratios]} (>=0.80 expected: a "
              f"stationary tenant mix favours the pinned offline split; "
              f"the governor pays a bounded online-adaptation tax for "
              f"never running the sweep)")
    C.write_csv("fig_serving",
                ["mode", "mix", "arrival", "burstiness_cv", "split",
                 "best_static", "ipc", "best_static_ipc", "ratio",
                 "switches"], rows)
    C.write_csv("fig_serving_tenants",
                ["mix", "arrival", "tenant", "mode", "hit_rate"],
                tenant_rows)
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default=None,
                    choices=("quick", "std", "full"))
    args = ap.parse_args()
    if args.profile:
        C.set_profile(args.profile)
    with C.Timer(f"fig_serving burstiness x tenant mix ({C.PROFILE})"):
        run()
