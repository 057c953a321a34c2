"""Paper §7.4 — on-chip & off-chip bandwidth analysis.

Measures, for BL / IBL / Morpheus-ALL / larger-LLC:
  * LLC throughput (conventional + extended tier bytes per second),
  * NoC load (extended-tier interconnect traffic),
  * off-chip DRAM bandwidth utilization,
  * LLC MPKI.

Paper: Morpheus-ALL improves LLC throughput by ~75% (up to 374%) vs BL;
larger-LLC (same capacity, same bank count) gets only ~42% — the delta is
the extra banks the cache-mode cores provide.  Off-chip bandwidth drops
~17% vs IBL; MPKI drops ~47%.
"""
from __future__ import annotations

from dataclasses import replace

from repro.core import cache_sim as cs
from repro.core import traces as tr

from . import common as C


def run():
    mb = tr.MEMORY_BOUND
    splits = C.mode_splits(["IBL", "Morpheus-ALL"], mb)

    # one batched dispatch set: BL / IBL / Morpheus-ALL / larger-LLC per app
    pts, meta = [], []
    for app in mb:
        pts.append(cs.RunPoint(app, "BL", cs.TABLE1.total_cores, 0,
                               C.TRACE_LEN))
        meta.append((app, "bl"))
        n_c, n_k = splits["IBL"][app]
        pts.append(cs.RunPoint(app, "IBL", n_c, n_k, C.TRACE_LEN))
        meta.append((app, "ibl"))
        n_c, n_k = splits["Morpheus-ALL"][app]
        pts.append(cs.RunPoint(app, "Morpheus-ALL", n_c, n_k, C.TRACE_LEN))
        meta.append((app, "mall"))
        # larger-LLC: conventional LLC scaled to Morpheus-ALL's total
        # capacity, same bank count (isolates capacity from banking)
        gpu = cs.TABLE1
        total_cap = gpu.conv_llc_bytes + n_k * gpu.ext_bytes_per_core
        scale = total_cap / gpu.conv_llc_bytes
        name = f"_larger{scale:.2f}"
        if name not in cs.SYSTEMS:
            cs.SYSTEMS[name] = replace(cs.SYSTEMS["IBL"], name=name,
                                       conv_scale=scale)
        pts.append(cs.RunPoint(app, name, n_c, 0, C.TRACE_LEN))
        meta.append((app, "larger"))
    res = {m: r for m, r in zip(meta, cs.run_batch(pts))}

    rows, ratios = [], {"llc": [], "llc_larger": [], "dram": [], "mpki": [],
                        "noc": []}
    for app in mb:
        bl, ibl = res[(app, "bl")], res[(app, "ibl")]
        mall, larger = res[(app, "mall")], res[(app, "larger")]

        ratios["llc"].append(mall.llc_throughput_GBps /
                             max(bl.llc_throughput_GBps, 1e-9))
        ratios["llc_larger"].append(larger.llc_throughput_GBps /
                                    max(bl.llc_throughput_GBps, 1e-9))
        ratios["dram"].append(mall.dram_GBps / max(ibl.dram_GBps, 1e-9))
        ratios["mpki"].append(mall.mpki / max(ibl.mpki, 1e-9))
        ratios["noc"].append(mall.noc_GBps / max(bl.noc_GBps + 1e-9, 1e-9))
        rows.append([app,
                     f"{bl.llc_throughput_GBps:.1f}",
                     f"{ibl.llc_throughput_GBps:.1f}",
                     f"{mall.llc_throughput_GBps:.1f}",
                     f"{larger.llc_throughput_GBps:.1f}",
                     f"{ibl.dram_GBps:.1f}", f"{mall.dram_GBps:.1f}",
                     f"{ibl.mpki:.2f}", f"{mall.mpki:.2f}",
                     f"{mall.noc_GBps:.1f}"])
    C.write_csv("bw_analysis",
                ["app", "llc_GBps_BL", "llc_GBps_IBL", "llc_GBps_ALL",
                 "llc_GBps_largerLLC", "dram_GBps_IBL", "dram_GBps_ALL",
                 "mpki_IBL", "mpki_ALL", "noc_GBps_ALL"], rows)

    g_llc = C.geomean(ratios["llc"])
    g_larger = C.geomean(ratios["llc_larger"])
    g_dram = C.geomean(ratios["dram"])
    g_mpki = C.geomean(ratios["mpki"])
    C.verdict("bw.llc-throughput-up", g_llc > 1.3,
              f"Morpheus-ALL LLC throughput = {g_llc:.2f}x BL (paper: 1.75x)")
    C.verdict("bw.banking-matters", g_llc > g_larger,
              f"Morpheus {g_llc:.2f}x > larger-LLC {g_larger:.2f}x "
              f"(paper: 1.75x vs 1.42x — extra banks matter)")
    C.verdict("bw.offchip-reduced", g_dram < 0.95,
              f"off-chip bandwidth = {g_dram:.2f}x IBL (paper: 0.83x)")
    C.verdict("bw.mpki-reduced", g_mpki < 0.75,
              f"LLC MPKI = {g_mpki:.2f}x IBL (paper: 0.53x)")
    return ratios


if __name__ == "__main__":
    with C.Timer("bandwidth analysis (§7.4)"):
        run()
