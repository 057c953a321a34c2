"""Paper replay — one workload through the evaluated systems (Fig. 12 row).

Runs a single memory-bound app through BL / IBL / IBL-4x-LLC /
Morpheus-Basic / Morpheus-ALL with the offline mode split, and prints the
normalized execution-time row plus the predictor ablation (Fig. 13 row).

  PYTHONPATH=src python examples/morpheus_replay.py --app kmeans
"""
from __future__ import annotations

import argparse
from dataclasses import replace

from repro.core import cache_sim as cs
from repro.core import traces as tr
from repro.core.controller import Predictor
from repro.core.policy import best_split

SYSTEMS = ("BL", "IBL", "IBL-4x-LLC", "Morpheus-Basic", "Morpheus-ALL")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="kmeans", choices=sorted(tr.WORKLOADS))
    ap.add_argument("--length", type=int, default=30_000)
    args = ap.parse_args()

    print(f"app = {args.app} "
          f"({'memory' if tr.WORKLOADS[args.app].memory_bound else 'compute'}"
          f"-bound)\n")
    base = cs.run(args.app, "BL", n_compute=cs.TABLE1.total_cores,
                  length=args.length)
    print(f"{'system':22s} {'cores':>11s} {'norm time':>9s} "
          f"{'hit rate':>8s} {'MPKI':>7s}")
    rows = {}
    for system in SYSTEMS:
        if system == "BL":
            r, nc, nk = base, cs.TABLE1.total_cores, 0
        else:
            split = best_split(args.app, system, length=args.length)
            nc, nk = split.n_compute, split.n_cache
            r = cs.run(args.app, system, n_compute=nc, n_cache=nk,
                       length=args.length)
        rows[system] = r
        print(f"{system:22s} {nc:3d}c+{nk:3d}$ "
              f"{r.exec_time_s / base.exec_time_s:9.3f} "
              f"{r.llc_hit_rate:8.2f} {r.mpki:7.1f}")

    print("\npredictor ablation (Morpheus-Basic split):")
    split = best_split(args.app, "Morpheus-Basic", length=args.length)
    for pred in (Predictor.BLOOM, Predictor.NONE, Predictor.PERFECT):
        name = f"_MB_{pred.value}"
        if name not in cs.SYSTEMS:
            cs.SYSTEMS[name] = replace(cs.SYSTEMS["Morpheus-Basic"],
                                       name=name, predictor=pred)
        r = cs.run(args.app, name, n_compute=split.n_compute,
                   n_cache=split.n_cache, length=args.length)
        print(f"  {pred.value:10s} norm time "
              f"{r.exec_time_s / base.exec_time_s:.3f}")


if __name__ == "__main__":
    main()
