"""Paper Fig. 12 — end-to-end execution time and perf/W for nine systems.

Systems (§6): BL, IBL, IBL-4x-LLC, Frequency-Boost, Unified-SM-Mem,
Morpheus-{Basic, Compression, Indirect-MOV, ALL}.  Each Morpheus / IBL
variant uses its offline per-app mode split (Table 3 analogue, cached).

Paper headline numbers (memory-bound geomean):
  Morpheus-ALL vs BL:            -39% exec time  /  +58% perf/W
  Morpheus-ALL vs IBL-4x-LLC:    within 3% (ideal quadruple LLC)
  Compression vs Basic:          ~9% faster;  Indirect-MOV vs Basic: ~4%
  compute-bound apps:            unaffected (<1%)
"""
from __future__ import annotations

from typing import Dict

from repro.core import cache_sim as cs
from repro.core import traces as tr

from . import common as C

SYSTEMS = ("BL", "IBL", "IBL-4x-LLC", "Frequency-Boost", "Unified-SM-Mem",
           "Morpheus-Basic", "Morpheus-Compression", "Morpheus-Indirect-MOV",
           "Morpheus-ALL")


def run() -> Dict[str, Dict[str, cs.RunResult]]:
    apps = tr.MEMORY_BOUND + tr.COMPUTE_BOUND
    splits = C.mode_splits([s for s in SYSTEMS if s != "BL"], apps)

    # all 9 systems x 17 apps as one batched dispatch set; run_batch groups
    # the points by config shape (system flags + cache-chip count)
    pts = [cs.RunPoint(app, "BL", cs.TABLE1.total_cores, 0, C.TRACE_LEN)
           for app in apps]
    for app in apps:
        for system in SYSTEMS[1:]:
            n_c, n_k = splits[system][app]
            pts.append(cs.RunPoint(app, system, n_c, n_k, C.TRACE_LEN))

    results: Dict[str, Dict[str, cs.RunResult]] = {s: {} for s in SYSTEMS}
    for p, r in zip(pts, cs.run_batch(pts)):
        results[p.system][p.app] = r

    rows = []
    for app in apps:
        base = results["BL"][app]
        rows.append([app, tr.WORKLOADS[app].memory_bound] +
                    [f"{results[s][app].exec_time_s / base.exec_time_s:.3f}"
                     for s in SYSTEMS] +
                    [f"{results[s][app].perf_per_watt / base.perf_per_watt:.3f}"
                     for s in SYSTEMS])
    C.write_csv("fig12_endtoend",
                ["app", "memory_bound"] + [f"t_{s}" for s in SYSTEMS] +
                [f"ppw_{s}" for s in SYSTEMS], rows)

    def gm_time(system: str, apps_):
        return C.geomean([results[system][a].exec_time_s /
                          results["BL"][a].exec_time_s for a in apps_])

    def gm_ppw(system: str, apps_):
        return C.geomean([results[system][a].perf_per_watt /
                          results["BL"][a].perf_per_watt for a in apps_])

    mb = tr.MEMORY_BOUND
    t_all, t_4x = gm_time("Morpheus-ALL", mb), gm_time("IBL-4x-LLC", mb)
    t_basic = gm_time("Morpheus-Basic", mb)
    t_comp = gm_time("Morpheus-Compression", mb)
    t_imov = gm_time("Morpheus-Indirect-MOV", mb)
    speedup = 1.0 / t_all
    C.verdict("fig12.morpheus-vs-BL", speedup >= 1.25,
              f"Morpheus-ALL geomean speedup over BL = {speedup:.2f}x "
              f"(paper: 1.39x / +39%)")
    C.verdict("fig12.within-4x-LLC", t_all / t_4x <= 1.10,
              f"Morpheus-ALL exec time = {t_all / t_4x:.3f}x of ideal "
              f"IBL-4x-LLC (paper: within 3%)")
    C.verdict("fig12.beats-real-baselines",
              t_all < min(gm_time(s, mb) for s in
                          ("IBL", "Frequency-Boost", "Unified-SM-Mem")),
              f"ALL={t_all:.3f} vs IBL={gm_time('IBL', mb):.3f} "
              f"FreqBoost={gm_time('Frequency-Boost', mb):.3f} "
              f"Unified={gm_time('Unified-SM-Mem', mb):.3f}")
    C.verdict("fig12.compression-gain", t_comp < t_basic,
              f"Compression {t_basic / t_comp - 1:+.1%} vs Basic (paper: +9%)")
    C.verdict("fig12.indirect-mov-gain", t_imov < t_basic,
              f"Indirect-MOV {t_basic / t_imov - 1:+.1%} vs Basic (paper: +4%)")
    cb = tr.COMPUTE_BOUND
    cb_delta = max(abs(results["Morpheus-ALL"][a].exec_time_s /
                       results["BL"][a].exec_time_s - 1.0) for a in cb)
    C.verdict("fig12.compute-bound-unaffected", cb_delta < 0.02,
              f"max compute-bound exec-time delta = {cb_delta:.1%}")
    ppw = gm_ppw("Morpheus-ALL", mb)
    C.verdict("fig12.perf-per-watt", ppw >= 1.3,
              f"Morpheus-ALL perf/W = {ppw:.2f}x BL (paper: 1.58x)")
    return results


if __name__ == "__main__":
    with C.Timer("fig12 end-to-end (9 systems x 17 apps)"):
        run()
