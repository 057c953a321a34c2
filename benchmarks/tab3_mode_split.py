"""Paper Table 3 — number of cores executing application threads.

The offline policy sweep (core/policy.py) picks, per app and system, the
compute-core count that minimizes execution time; the remainder go to
cache mode (Morpheus) or are power-gated (IBL).  Paper patterns checked:
  * IBL keeps all 68 cores for the 9 'saturators', fewer for the
    thrashers (kmeans 24, ..., lbm 34);
  * Morpheus-Basic uses far fewer compute cores (18..50);
  * Morpheus-ALL uses MORE compute cores than Basic (compression packs
    the same extended capacity into fewer cache chips);
  * compute-bound apps always keep all 68.
"""
from __future__ import annotations

from repro.core import cache_sim as cs
from repro.core import traces as tr

from . import common as C

SYSTEMS = ("IBL", "Morpheus-Basic", "Morpheus-ALL")


def run():
    apps = tr.MEMORY_BOUND + tr.COMPUTE_BOUND
    # cheap sweep: the policy grid defaults to the full profile (batched
    # engine); an explicit --profile / env profile overrides
    splits = C.mode_splits(list(SYSTEMS), apps, profile=C.CHEAP_PROFILE)
    rows = []
    for app in apps:
        rows.append([app] + [splits[s][app][0] for s in SYSTEMS] +
                    [splits[s][app][1] for s in SYSTEMS[1:]])
    C.write_csv("tab3_mode_split",
                ["app"] + [f"compute_{s}" for s in SYSTEMS] +
                [f"cache_{s}" for s in SYSTEMS[1:]], rows)

    mb = tr.MEMORY_BOUND
    basic_fewer = sum(splits["Morpheus-Basic"][a][0] <
                      cs.TABLE1.total_cores for a in mb)
    C.verdict("tab3.morpheus-frees-cores", basic_fewer >= len(mb) - 2,
              f"Morpheus-Basic uses <68 compute cores for {basic_fewer}/"
              f"{len(mb)} memory-bound apps")
    all_ge = sum(splits["Morpheus-ALL"][a][0] >=
                 splits["Morpheus-Basic"][a][0] for a in mb)
    C.verdict("tab3.compression-frees-cache-cores", all_ge >= len(mb) // 2,
              f"Morpheus-ALL compute-cores >= Basic for {all_ge}/{len(mb)} "
              f"apps (paper: ALL uses more compute cores)")
    cb_all68 = all(splits[s][a][0] == cs.TABLE1.total_cores
                   for s in SYSTEMS for a in tr.COMPUTE_BOUND)
    C.verdict("tab3.compute-bound-keeps-68", cb_all68,
              "all compute-bound apps keep 68 compute cores")
    return splits


if __name__ == "__main__":
    with C.Timer("table 3 mode split"):
        run()
