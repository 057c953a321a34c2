"""Fleet-scale sharded serving: aggregate IPC + convergence vs. replica
count (runtime/fleet subsystem figure).

Three claims about ``repro.runtime.fleet``:

  1. **Identity** — the batched/sharded fleet step is bit-identical per
     replica to serial ``simulate_online`` runs (integer Stats exactly,
     same governor decision sequence).  Checked every run at N=4; the
     full matrix (backends x device counts) lives in
     ``tests/test_fleet.py``.
  2. **Batching invariance** — the replica-count sweep reuses the same
     spec list as a prefix at every count, so replica i's result must
     be independent of how many rows were batched around it (replicas
     are independent; batching must not perturb the physics).  Engine
     dispatches per epoch stay O(config groups), not O(replicas).
  3. **Advisor** — warm-starting fresh replicas from the shared
     ``SplitAdvisor`` puts them AT the fleet's converged split at epoch
     0, cutting mean governor convergence time vs. the cold ablation.

Outputs ``benchmarks/out/fig_fleet.csv`` (one row per replica-count /
ablation cell).  ``--seeds N`` turns the scaling cells into mean±std
over seed offsets, like fig1/fig2.

  PYTHONPATH=src python -m benchmarks.fig_fleet --quick
  PYTHONPATH=src python -m benchmarks.run --only fleet
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.core import controller as ctl
from repro.launch.mesh import make_fleet_mesh
from repro.runtime import (ReplicaSpec, SplitAdvisor, run_serial,
                           simulate_fleet)
from repro.runtime.governor import candidates_for

from . import common as C

SYSTEM = "Morpheus-ALL"
# Same coarse transition ladder as fig_online/fig_serving: a real
# runtime spaces its rungs wide because mode transitions flush state.
LADDER_GRID = (18, 32, 48, 68)
# All memory-bound (compute-bound apps pin to (68|0) and give the
# governor nothing to do); replicas cycle through the list.
_APPS = ("cfd", "stencil", "p-bfs", "kmeans")
_COUNTS = {"quick": (1, 4), "std": (1, 4, 16), "full": (1, 4, 16, 64)}
# Dynamics-driven (see fig_online): epochs must outlast post-switch
# warm-up, runs must outlast governor convergence.
_LEN = {"quick": 24_000, "std": 48_000, "full": 48_000}
_EPOCH = 3_000


def _ladders(length: int) -> Dict[str, list]:
    return {a: candidates_for(a, SYSTEM, grid=LADDER_GRID, length=length)
            for a in _APPS}


def _specs(n: int, length: int, ladders: Dict[str, list],
           seed0: int = 0) -> List[ReplicaSpec]:
    return [ReplicaSpec(_APPS[i % len(_APPS)], SYSTEM, length=length,
                        epoch_len=_EPOCH, seed=seed0 + i,
                        candidates=ladders[_APPS[i % len(_APPS)]],
                        name=f"r{i}:{_APPS[i % len(_APPS)]}")
            for i in range(n)]


def _ints(stats: ctl.Stats) -> Dict:
    return {f: np.asarray(getattr(stats, f)).tolist()
            for f in ctl._INT_FIELDS}


def run() -> Dict[str, float]:
    length = _LEN[C.PROFILE]
    counts = _COUNTS[C.PROFILE]
    mesh = make_fleet_mesh()
    n_dev = int(np.prod(list(dict(mesh.shape).values())))
    ladders = _ladders(length)
    rows: List[List] = []
    out: Dict[str, float] = {}

    # ---- identity: fleet (batched, sharded if devices allow) == serial
    id_specs = _specs(min(4, max(counts)), length, ladders)
    serial = run_serial(id_specs)
    fr_id = simulate_fleet(id_specs, mesh=mesh)
    same = all(
        _ints(s.stats) == _ints(f.stats)
        and [(r.n_compute, r.n_cache) for r in s.records]
        == [(r.n_compute, r.n_cache) for r in f.records]
        for s, f in zip(serial, fr_id.results))
    out["identity"] = float(same)
    C.verdict("fig_fleet.identity", same,
              f"{fr_id.n_replicas}-replica fleet over {n_dev} device(s) "
              f"bit-identical to serial runs (integer Stats + decision "
              f"sequences): {same}")

    # ---- scaling: aggregate IPC + convergence vs. replica count
    res0 = {}
    for n in counts:
        ipcs, convs = [], []
        fr = None
        for s in C.seed_list():
            fr = simulate_fleet(_specs(n, length, ladders, seed0=100 * s),
                                mesh=mesh)
            if s == 0:
                res0[n] = fr.results
            ipcs.append(fr.aggregate_ipc())
            convs.append(float(np.mean(fr.convergence_epochs())))
        m, sd = C.mean_std(ipcs)
        cm, csd = C.mean_std(convs)
        out[f"fleet/{n}"] = m
        rows.append(["scaling", n, n_dev, C.fmt_mean_std(m, sd),
                     C.fmt_mean_std(cm, csd, 1), fr.epochs, fr.dispatches,
                     "off"])
    nmax = max(counts)
    invariant = all(
        abs(res0[n][i].ipc - res0[nmax][i].ipc)
        <= 1e-9 * max(abs(res0[nmax][i].ipc), 1.0)
        and [(r.n_compute, r.n_cache) for r in res0[n][i].records]
        == [(r.n_compute, r.n_cache) for r in res0[nmax][i].records]
        for n in counts for i in range(n))
    out["batching_invariant"] = float(invariant)
    C.verdict("fig_fleet.batching-invariant", invariant,
              f"replica results independent of fleet size across counts "
              f"{counts} (shared spec prefix: same IPC to 1e-9, same "
              f"decision sequence): {invariant}")

    # ---- advisor ablation: cold fleet teaches, fresh wave warm-starts
    adv = SplitAdvisor()
    simulate_fleet(_specs(len(_APPS), length, ladders), mesh=mesh,
                   advisor=adv)
    advised = {mix: e["split"] for mix, e in adv.table.items()}
    wave = _specs(len(_APPS), length, ladders, seed0=50)
    cold = simulate_fleet(wave, mesh=mesh)
    warm = simulate_fleet(wave, mesh=mesh, advisor=adv)
    # mixes whose teacher governor never held a measured estimate (e.g.
    # still mid-switch at fleet end) have no advice — gate on coverage
    covered = [(i, r) for i, r in enumerate(warm.results)
               if (SYSTEM, (_APPS[i % len(_APPS)],)) in advised]
    started_there = all(
        (r.records[0].n_compute, r.records[0].n_cache)
        == advised[(SYSTEM, (_APPS[i % len(_APPS)],))]
        for i, r in covered)
    out["advisor/warm_starts"] = float(adv.warm_starts)
    C.verdict("fig_fleet.advisor-warm-starts",
              0 < len(covered) == adv.warm_starts and started_there,
              f"{adv.warm_starts} fresh replicas warm-started "
              f"({len(covered)}/{len(wave)} mixes had advice) and began "
              f"epoch 0 at the advised split: {started_there}")
    conv_cold = float(np.mean(cold.convergence_epochs()))
    conv_warm = float(np.mean(warm.convergence_epochs()))
    out["advisor/convergence_ratio"] = \
        conv_warm / conv_cold if conv_cold > 0 else 1.0
    C.verdict("fig_fleet.advisor-converges-faster",
              conv_warm <= conv_cold,
              f"mean convergence epoch warm {conv_warm:.1f} vs cold "
              f"{conv_cold:.1f} (warm <= cold expected; exploration "
              f"epsilon can still delay individual replicas)")
    for label, fres in (("cold", cold), ("warm", warm)):
        rows.append(["advisor", fres.n_replicas, n_dev,
                     f"{fres.aggregate_ipc():.3f}",
                     f"{np.mean(fres.convergence_epochs()):.1f}",
                     fres.epochs, fres.dispatches,
                     label if label == "cold" else
                     f"warm({fres.advisor.warm_starts})"])

    C.write_csv("fig_fleet",
                ["mode", "replicas", "devices", "aggregate_ipc",
                 "mean_convergence_epoch", "fleet_epochs", "dispatches",
                 "advisor"], rows)
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default=None,
                    choices=("quick", "std", "full"))
    ap.add_argument("--quick", action="store_true",
                    help="shorthand for --profile quick")
    ap.add_argument("--seeds", type=int, default=None,
                    help="seed offsets per scaling cell (mean±std)")
    args = ap.parse_args()
    if args.quick:
        C.set_profile("quick")
    elif args.profile:
        C.set_profile(args.profile)
    if args.seeds:
        C.set_seeds(args.seeds)
    with C.Timer(f"fig_fleet replica scaling ({C.PROFILE})"):
        run()
