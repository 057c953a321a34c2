"""Benchmark driver — one module per paper table/figure (DESIGN.md §6).

    PYTHONPATH=src python -m benchmarks.run [--only fig12,fig13] [--profile std]

Profiles (or env REPRO_BENCH_PROFILE): quick | std | full — controls trace
length and mode-split sweep grids.  Every module writes a CSV into
``benchmarks/out/`` and prints PASS/WARN verdicts against the paper's own
reported numbers.
"""
from __future__ import annotations

import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated module keys (fig1,fig2,fig5,fig11,"
                         "fig12,fig13,tab3,bw,overheads,online,"
                         "serving,qos,overload,fleet,autotune,"
                         "char_online)")
    ap.add_argument("--profile", default=None, choices=("quick", "std", "full"))
    ap.add_argument("--seeds", type=int, default=None,
                    help="trace seeds per grid cell; >1 adds mean±std "
                         "error bars to fig1/fig2")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.profile:
        os.environ["REPRO_BENCH_PROFILE"] = args.profile
    if args.seeds:
        os.environ["REPRO_BENCH_SEEDS"] = str(args.seeds)

    # import after profile env is set (common.py reads it at import time)
    from . import common as C
    from . import (bw_analysis, fig1_core_scaling, fig2_llc_size,
                   fig5_latency, fig11_characterization, fig12_endtoend,
                   fig13_predictor, fig_autotune,
                   fig_characterization_online, fig_fleet, fig_online,
                   fig_overload, fig_qos, fig_serving, tab3_mode_split,
                   tab_overheads)

    modules = {
        "fig5": ("Fig. 5 latency timelines", fig5_latency.run),
        "fig11": ("Fig. 11 extended-LLC characterization",
                  fig11_characterization.run),
        "overheads": ("§7.5 overheads", tab_overheads.run),
        "fig1": ("Fig. 1 core scaling", fig1_core_scaling.run),
        "fig2": ("Fig. 2 LLC sizes", fig2_llc_size.run),
        "tab3": ("Table 3 mode split", tab3_mode_split.run),
        "fig12": ("Fig. 12 end-to-end, 9 systems", fig12_endtoend.run),
        "fig13": ("Fig. 13 predictor ablation", fig13_predictor.run),
        "bw": ("§7.4 bandwidth analysis", bw_analysis.run),
        "online": ("Online governor vs. static splits", fig_online.run),
        "serving": ("Multi-tenant bursty replay (workload subsystem)",
                    fig_serving.run),
        "qos": ("QoS governor: weighted tenants x churn", fig_qos.run),
        "overload": ("Overload admission: graceful degradation x SLOs",
                     fig_overload.run),
        "fleet": ("Fleet-scale sharded serving: replicas x advisor",
                  fig_fleet.run),
        "autotune": ("Design-space search: regret curves + optima",
                     fig_autotune.run),
        "char_online": ("Table 2 classes from online introspection",
                        fig_characterization_online.run),
    }
    only = [k.strip() for k in args.only.split(",") if k.strip()]
    t0 = time.time()
    print(f"benchmark profile = {C.PROFILE} (trace len {C.TRACE_LEN}, "
          f"grid {C.GRID})")
    ran = 0
    for key, (label, fn) in modules.items():
        if only and key not in only:
            continue
        with C.Timer(label):
            fn()
        ran += 1
    print(f"\n{ran} benchmark modules done in {time.time() - t0:.0f}s; "
          f"CSVs in {C.OUT_DIR}")


if __name__ == "__main__":
    main()
