"""Paper Fig. 13 — effect of hit/miss prediction on execution time.

Morpheus-Basic with three predictor designs over the 14 memory-bound apps:
Bloom (the paper's double-filter scheme), No-Prediction (forward every
extended-range request to the remote tier), Perfect (oracle).

Paper: No-Prediction is ~9% slower than Bloom; Bloom is within 1% of
Perfect.
"""
from __future__ import annotations

from dataclasses import replace

from repro.core import cache_sim as cs
from repro.core import traces as tr
from repro.core.controller import Predictor

from . import common as C

VARIANTS = {
    "Bloom-Filter": Predictor.BLOOM,
    "No-Prediction": Predictor.NONE,
    "Perfect-Prediction": Predictor.PERFECT,
}


def run():
    for name, pred in VARIANTS.items():
        sysname = f"_MB_{pred.value}"
        if sysname not in cs.SYSTEMS:
            cs.SYSTEMS[sysname] = replace(cs.SYSTEMS["Morpheus-Basic"],
                                          name=sysname, predictor=pred)
    splits = C.mode_splits(["Morpheus-Basic"], tr.MEMORY_BOUND)

    # one batched dispatch set: BL baselines + all 3 predictor variants
    pts, meta = [], []
    for app in tr.MEMORY_BOUND:
        pts.append(cs.RunPoint(app, "BL", cs.TABLE1.total_cores, 0,
                               C.TRACE_LEN))
        meta.append((app, "BL"))
        n_c, n_k = splits["Morpheus-Basic"][app]
        for name, pred in VARIANTS.items():
            pts.append(cs.RunPoint(app, f"_MB_{pred.value}", n_c, n_k,
                                   C.TRACE_LEN))
            meta.append((app, name))
    res = {m: r for m, r in zip(meta, cs.run_batch(pts))}

    rows, norm = [], {v: {} for v in VARIANTS}
    for app in tr.MEMORY_BOUND:
        base = res[(app, "BL")]
        for name in VARIANTS:
            norm[name][app] = res[(app, name)].exec_time_s / base.exec_time_s
        rows.append([app] + [f"{norm[n][app]:.3f}" for n in VARIANTS])
    g = {n: C.geomean(list(norm[n].values())) for n in VARIANTS}
    rows.append(["geomean"] + [f"{g[n]:.3f}" for n in VARIANTS])
    C.write_csv("fig13_predictor", ["app"] + list(VARIANTS), rows)

    nopred_penalty = g["No-Prediction"] / g["Bloom-Filter"] - 1.0
    bloom_gap = g["Bloom-Filter"] / g["Perfect-Prediction"] - 1.0
    C.verdict("fig13.no-prediction-penalty", 0.0 < nopred_penalty < 0.25,
              f"No-Prediction is {nopred_penalty:+.1%} exec time vs Bloom "
              f"(paper: +9%)")
    C.verdict("fig13.bloom-near-perfect", bloom_gap < 0.03,
              f"Bloom within {bloom_gap:+.1%} of Perfect (paper: 1%)")
    return g


if __name__ == "__main__":
    with C.Timer("fig13 predictor ablation"):
        run()
