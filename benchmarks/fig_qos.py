"""QoS governor figure: per-tenant reward weighting x tenant churn.

The QoS layer's two headline claims (docs/qos.md), measured:

  * **weights steer** — on a stationary two-tenant mix with divergent
    split preferences (a memory-bound thrasher + a compute-bound app),
    skewing ``GovernorConfig.tenant_weights`` toward one tenant moves
    the governor's converged split toward *that tenant's* offline-best
    split (the argmax of its per-tenant IPC terms over the static
    sweep), relative to the uniform-weight run;
  * **churn re-converges** — when a tenant departs mid-stream (activity
    window ``cfd@0:0.45``), the governor detects the churn boundary
    (context reset, ``OnlineResult.churn_resets``) and re-converges onto
    the remaining mix: its post-churn IPC, measured after a bounded
    re-convergence budget of epochs, reaches >= 0.9 of the best static
    split *for the post-churn region*;
  * per-tenant integer Stats still sum to the global run's bit-
    identically in every cell (the attribution invariant).

Outputs ``benchmarks/out/fig_qos.csv`` (one row per run) and
``benchmarks/out/fig_qos_tenants.csv`` (per-tenant mean IPC terms and
hit rates).

  PYTHONPATH=src python -m benchmarks.fig_qos --quick
  PYTHONPATH=src python -m benchmarks.run --only qos
"""
from __future__ import annotations

from dataclasses import replace
from typing import Dict, List

import numpy as np

from repro.core import cache_sim as cs
from repro.runtime import GovernorConfig, simulate_online
from repro.runtime.governor import candidates_for
from repro.workloads import tenancy

from . import common as C

SYSTEM = "Morpheus-ALL"
LADDER_GRID = (18, 32, 48, 68)   # the coarse transition ladder (fig_serving)
N_CORES = 32
ARRIVAL = "det:2e6"              # stationary arrivals: churn and weights
                                 # are the only moving parts of this figure

# Tenant mix with divergent preferences: cfd is a memory-bound streamer
# (earns cache capacity), lib is compute-bound (wants every core
# computing) — the widest offline-best spread the ladder can show.
MIX = "cfd,lib"
_CHURNS = {
    "quick": (("none", "cfd,lib"), ("depart0", "cfd@0:0.45,lib")),
    "std": (("none", "cfd,lib"), ("depart0", "cfd@0:0.45,lib"),
            ("arrive1", "cfd,lib@0.4:")),
    "full": (("none", "cfd,lib"), ("depart0", "cfd@0:0.45,lib"),
             ("arrive1", "cfd,lib@0.4:"), ("swap", "cfd@0:0.55,lib@0.45:")),
}
# Uniform weights converge to the compute-bound tenant's preference (its
# IPC term has the steeper slope in compute cores); skewing toward the
# memory-bound cfd must pull the split back down the ladder toward cfd's
# own offline-best — that asymmetry is the steering the figure shows.
_WEIGHTS = {
    "quick": (("1:1", (1.0, 1.0)), ("8:1", (8.0, 1.0))),
    "std": (("1:1", (1.0, 1.0)), ("8:1", (8.0, 1.0)), ("1:6", (1.0, 6.0))),
    "full": (("1:1", (1.0, 1.0)), ("8:1", (8.0, 1.0)), ("1:6", (1.0, 6.0))),
}
_LEN = {"quick": 40_000, "std": 120_000, "full": 200_000}
_EPOCH = {"quick": 1_500, "std": 3_000, "full": 3_000}
RECONVERGE_BUDGET = 6            # epochs the governor gets to re-climb


def _hits_sum_check(r) -> bool:
    """Per-tenant integer hit counters must sum to the global run's."""
    ok = True
    for f in ("conv_hits", "conv_misses", "ext_hits", "ext_true_miss"):
        tot = sum(int(np.asarray(getattr(s, f)))
                  for s in r.tenant_stats.values())
        ok &= tot == int(np.asarray(getattr(r.stats, f)))
    return ok


def _tenant_ipc_means(records) -> Dict[str, float]:
    """Time-weighted mean of the per-tenant IPC terms over a run."""
    sums: Dict[str, float] = {}
    t = 0.0
    for r in records:
        if not r.tenant_ipc:
            continue
        for part in r.tenant_ipc.split("|"):
            name, v = part.rsplit(":", 1)
            sums[name] = sums.get(name, 0.0) + float(v) * r.exec_time_s
        t += r.exec_time_s
    return {k: v / t for k, v in sums.items()} if t > 0 else {}


def _region_ipc(records, lo: int) -> float:
    """Time-weighted IPC of the epochs from ``lo`` on."""
    rs = records[lo:]
    t = sum(r.exec_time_s for r in rs)
    return sum(r.ipc * r.exec_time_s for r in rs) / t if t > 0 else 0.0


def _churn_epoch(wl, bounds) -> int:
    """First epoch whose active-tenant signature differs from epoch 0's
    (-1 when the schedule has no churn)."""
    sig0 = wl.active_signature(*bounds[0])
    for e, (lo, hi) in enumerate(bounds):
        if wl.active_signature(lo, hi) != sig0:
            return e
    return -1


def run() -> Dict[str, float]:
    length, tepoch = _LEN[C.PROFILE], _EPOCH[C.PROFILE]
    rows: List[List] = []
    tenant_rows: List[List] = []
    out: Dict[str, float] = {}
    sums_ok: List[bool] = []
    shift_ok: List[bool] = []
    strict_shift: List[bool] = []
    churn_detect_ok: List[bool] = []
    reconverge: List[float] = []

    for churn_name, spec in _CHURNS[C.PROFILE]:
        wl = tenancy.make_workload(spec, length=length, n_cores=N_CORES,
                                   arrival=ARRIVAL, seed=0,
                                   ws_scale=1.0 / cs.SYSTEMS[SYSTEM].sim_scale)
        ladder = candidates_for(wl.primary_app, SYSTEM, grid=LADDER_GRID,
                                length=length)
        bounds = wl.epoch_bounds(epoch_len=tepoch)
        churn_at = _churn_epoch(wl, bounds)
        region_lo = 0 if churn_at < 0 else churn_at + RECONVERGE_BUDGET

        statics = {}
        for s in ladder:
            st = simulate_online(wl, SYSTEM, epoch_len=tepoch,
                                 fixed_split=s)
            statics[s] = st
            rows.append(["static", churn_name, "", f"({s[0]}|{s[1]})",
                         f"{st.ipc:.3f}", "", "", 0, 0])
        # offline-best split per tenant: argmax of its own IPC terms
        best_for: Dict[str, object] = {}
        for name in wl.names:
            best_for[name] = max(
                ladder, key=lambda s: _tenant_ipc_means(
                    statics[s].records).get(name, 0.0))
        best_region = max(_region_ipc(st.records, region_lo)
                          for st in statics.values())

        govs = {}
        for w_name, weights in _WEIGHTS[C.PROFILE]:
            gcfg = replace(GovernorConfig(), objective="weighted",
                           tenant_weights=weights)
            g = simulate_online(wl, SYSTEM, epoch_len=tepoch,
                                candidates=ladder, gcfg=gcfg)
            govs[w_name] = g
            sums_ok.append(_hits_sum_check(g))
            if churn_at < 0:
                churn_detect_ok.append(g.churn_resets == 0)
            else:
                churn_detect_ok.append(g.churn_resets >= 1)
            ratio = _region_ipc(g.records, region_lo) / best_region
            if churn_at >= 0:
                reconverge.append(ratio)
            out[f"{churn_name}/{w_name}"] = ratio
            rows.append(["governor", churn_name, w_name, "adaptive",
                         f"{g.ipc:.3f}",
                         f"({g.converged_split[0]}|{g.converged_split[1]})",
                         f"{ratio:.3f}", g.switches, g.churn_resets])
            for name, mu in _tenant_ipc_means(g.records).items():
                hr = g.tenant_hit_rates().get(name, 0.0)
                tenant_rows.append([churn_name, w_name, name,
                                    f"{mu:.3f}", f"{hr:.4f}"])
            print(f"  {churn_name:>8} x w={w_name:<4}: governor "
                  f"{g.ipc:7.3f} converged ({g.converged_split[0]}|"
                  f"{g.converged_split[1]}) | post-region ratio "
                  f"{ratio:.3f} | churn resets {g.churn_resets} | "
                  f"switches {g.switches}")

        # weights steer: each skewed run's converged split must be at
        # least as close (on the ladder) to the favoured tenant's
        # offline-best as the uniform run's
        uni = govs.get("1:1")
        if uni is not None:
            idx = {s: i for i, s in enumerate(ladder)}
            for w_name, weights in _WEIGHTS[C.PROFILE]:
                if w_name == "1:1":
                    continue
                fav = wl.names[int(np.argmax(weights))]
                tgt = idx[best_for[fav]]
                d_skew = abs(idx[govs[w_name].converged_split] - tgt)
                d_uni = abs(idx[uni.converged_split] - tgt)
                shift_ok.append(d_skew <= d_uni)
                if d_uni > 0:
                    strict_shift.append(d_skew < d_uni)
                print(f"  {churn_name:>8} w={w_name}: favoured {fav} "
                      f"offline-best {best_for[fav]} | ladder distance "
                      f"skewed {d_skew} vs uniform {d_uni}")

    C.verdict("fig_qos.tenant-attribution-exact", all(sums_ok),
              f"per-tenant integer Stats sum to global bit-identically "
              f"in {sum(sums_ok)}/{len(sums_ok)} governed runs")
    C.verdict("fig_qos.weights-steer-the-split",
              all(shift_ok) and (not strict_shift or any(strict_shift)),
              f"skewed-weight governor converged at least as close to "
              f"the favoured tenant's offline-best split as the "
              f"uniform run in {sum(shift_ok)}/{len(shift_ok)} cells "
              f"({sum(strict_shift)} strictly closer where the uniform "
              f"run differed)")
    C.verdict("fig_qos.churn-detected", all(churn_detect_ok),
              f"churn context resets fired exactly on schedules with "
              f"churn in {sum(churn_detect_ok)}/{len(churn_detect_ok)} "
              f"runs")
    C.verdict("fig_qos.churn-reconverges",
              all(x >= 0.90 for x in reconverge),
              f"post-churn IPC / best-static-for-new-mix = "
              f"{['%.3f' % x for x in reconverge]} (>=0.90 after a "
              f"{RECONVERGE_BUDGET}-epoch re-convergence budget)")
    C.write_csv("fig_qos",
                ["mode", "churn", "weights", "split", "ipc",
                 "converged", "region_ratio", "switches", "churn_resets"],
                rows)
    C.write_csv("fig_qos_tenants",
                ["churn", "weights", "tenant", "mean_ipc", "hit_rate"],
                tenant_rows)
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default=None,
                    choices=("quick", "std", "full"))
    ap.add_argument("--quick", action="store_true",
                    help="shorthand for --profile quick")
    ap.add_argument("--inspect-out", default=None, metavar="PATH",
                    help="enable the cache microscope for the governed "
                         "runs and write the decoded per-epoch snapshots "
                         "here — render with 'obs_report heatmap'")
    args = ap.parse_args()
    if args.quick:
        C.set_profile("quick")
    elif args.profile:
        C.set_profile(args.profile)
    if args.inspect_out:
        from repro import obs
        obs.enable(trace=False, metrics=True, inspect=True)
    with C.Timer(f"fig_qos weights x churn ({C.PROFILE})"):
        run()
    if args.inspect_out:
        from repro import obs
        p = obs.inspector().save(args.inspect_out)
        print(f"inspect-out: {p} "
              f"({len(obs.inspector().snapshots)} snapshots)")
