"""Online workload characterization from the cache microscope.

The paper's Table 2 splits the workloads into capacity-sensitive
(memory-bound) and compute-bound classes *offline* — from source-level
knowledge of each app's working set.  This figure recovers the same
classification purely from **online introspection** of a running
Morpheus system, with the class labels hidden from the measurement:

  * every app runs under one label-blind fixed split (48 compute cores,
    20 cache chips) with the cache microscope enabled
    (``obs.enable(inspect=True)`` -> per-epoch decoded ``Snapshot``s);
  * the **stream profiler** (``obs/profile.py``) measures the working
    set actually touched (exact first-touch footprint) on the replayed
    request stream — the online estimate of Table 2's working-set
    column;
  * the **snapshots** corroborate: the blocks resident across both
    tiers are the *cache's own view* of the footprint — an app that
    fits the conventional LLC never holds more than its working set,
    one that does not fills the conventional tier and parks the excess
    in the extended tier.

Classifier (online data only): *capacity-bound* iff the measured
footprint exceeds the conventional LLC capacity.  The verdicts check
(a) the classification agrees with Table 2's offline labels on every
app, (b) the snapshot-only signal (resident blocks in the final
snapshot > conventional capacity) agrees independently without ever
seeing the request stream, and (c) the profiler's mass invariant
(histogram mass == request count) holds on every stream.

Outputs ``benchmarks/out/fig_characterization_online.csv``.

  PYTHONPATH=src python -m benchmarks.fig_characterization_online --quick
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro import obs
from repro.core import cache_sim as cs
from repro.obs import profile as prof
from repro.runtime import simulate_online
from repro.workloads import synthetic

from . import common as C

SYSTEM = "Morpheus-ALL"
SPLIT = (48, 20)                 # label-blind: every app, same split
_APPS = {
    "quick": ("cfd", "kmeans", "spmv", "lib", "hotsp", "mri-q"),
    "std": tuple(synthetic.WORKLOADS),
    "full": tuple(synthetic.WORKLOADS),
}
_LEN = {"quick": 12_000, "std": 40_000, "full": 120_000}
_EPOCH = {"quick": 1_500, "std": 3_000, "full": 3_000}


def characterize(app: str, *, length: int, epoch_len: int,
                 seed: int = 0) -> Dict[str, float]:
    """One app's online measurement: footprint from the stream profiler
    + steady-state occupancy/spill from the microscope snapshots."""
    obs.disable()                        # fresh inspector per app
    obs.enable(trace=False, metrics=False, inspect=True)
    r = simulate_online(app, SYSTEM, length=length, epoch_len=epoch_len,
                        seed=seed, fixed_split=SPLIT)
    snaps = obs.inspector().snapshots
    obs.disable()
    assert snaps, f"{app}: microscope recorded no snapshots"
    # the same stream the run replayed (generate_phased with one phase
    # == generate at the split's core count) — profiled host-side
    scale = cs.SYSTEMS[SYSTEM].sim_scale
    addrs, _, _ = synthetic.generate(app, n_cores=SPLIT[0], length=length,
                                     seed=seed, ws_scale=1.0 / scale)
    p = prof.profile_trace(addrs, block_bytes=synthetic.BLOCK_BYTES)
    last = snaps[-1]
    tail = snaps[len(snaps) // 2:]       # steady state: back half
    resident = sum(last.conv_set_occ) + sum(last.ext_set_occ)
    return {
        "ipc": r.ipc,
        "footprint_bytes":
            p["wss"]["footprint_blocks"] * synthetic.BLOCK_BYTES,
        "mass_ok": p["reuse"]["mass"] == p["requests"],
        "resident_bytes": resident * synthetic.BLOCK_BYTES,
        "conv_occ": float(np.mean([s.conv_occupancy for s in tail])),
        "ext_occ": float(np.mean([s.ext_occupancy for s in tail])),
        "byte_util": float(np.mean([s.byte_util for s in tail])),
        "bloom_fill": last.bloom_fill,
        "expansion": last.expansion,
        "snapshots": len(snaps),
    }


def run() -> Dict[str, float]:
    apps = _APPS[C.PROFILE]
    length, epoch_len = _LEN[C.PROFILE], _EPOCH[C.PROFILE]
    spec = cs.SYSTEMS[SYSTEM]
    conv_bytes = spec.gpu.conv_llc_bytes // spec.sim_scale
    rows: List[List] = []
    out: Dict[str, float] = {}
    agree: List[bool] = []
    snap_agree: List[bool] = []
    mass_ok: List[bool] = []
    utils = {True: [], False: []}        # offline label -> byte_utils

    print(f"  conventional LLC (scaled): {conv_bytes // 1024} KiB; "
          f"split {SPLIT[0]} compute / {SPLIT[1]} cache chips")
    for app in apps:
        m = characterize(app, length=length, epoch_len=epoch_len)
        online = m["footprint_bytes"] > conv_bytes
        by_snap = m["resident_bytes"] > conv_bytes
        offline = synthetic.WORKLOADS[app].memory_bound
        agree.append(online == offline)
        snap_agree.append(by_snap == offline)
        mass_ok.append(bool(m["mass_ok"]))
        utils[offline].append(m["byte_util"])
        out[app] = float(online)
        cls = "capacity" if online else "compute"
        rows.append([app, cls, "capacity" if offline else "compute",
                     f"{m['footprint_bytes'] / 1024:.0f}",
                     f"{m['resident_bytes'] / 1024:.0f}",
                     f"{conv_bytes / 1024:.0f}",
                     f"{m['conv_occ']:.3f}", f"{m['ext_occ']:.3f}",
                     f"{m['byte_util']:.3f}", f"{m['bloom_fill']:.3f}",
                     f"{m['expansion']:.2f}", m["snapshots"]])
        mark = "==" if online == offline else "!="
        print(f"  {app:>8}: footprint {m['footprint_bytes'] / 1024:6.0f} "
              f"KiB, resident {m['resident_bytes'] / 1024:6.0f} KiB -> "
              f"{cls:>8} {mark} offline | conv occ {m['conv_occ']:.3f} "
              f"| ext util {m['byte_util']:.3f}")

    C.verdict("fig_char_online.classification-agrees", all(agree),
              f"online footprint classifier matches Table 2 labels on "
              f"{sum(agree)}/{len(agree)} apps")
    C.verdict("fig_char_online.snapshot-signal-agrees", all(snap_agree),
              f"snapshot-only signal (resident blocks > conventional "
              f"capacity) matches on {sum(snap_agree)}/{len(snap_agree)} "
              f"apps")
    C.verdict("fig_char_online.profiler-mass-invariant", all(mass_ok),
              f"reuse-histogram mass == request count on "
              f"{sum(mass_ok)}/{len(mass_ok)} streams")
    lo_cap = min(utils[True], default=1.0)
    hi_cmp = max(utils[False], default=0.0)
    C.verdict("fig_char_online.spill-separates-classes", lo_cap > hi_cmp,
              f"extended-tier byte_util: min capacity-bound "
              f"{lo_cap:.3f} > max compute-bound {hi_cmp:.3f}")
    C.write_csv("fig_characterization_online",
                ["app", "online_class", "offline_class", "footprint_KiB",
                 "resident_KiB", "conv_llc_KiB", "conv_occ", "ext_occ",
                 "byte_util", "bloom_fill", "expansion", "snapshots"],
                rows)
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default=None,
                    choices=("quick", "std", "full"))
    ap.add_argument("--quick", action="store_true",
                    help="shorthand for --profile quick")
    args = ap.parse_args()
    if args.quick:
        C.set_profile("quick")
    elif args.profile:
        C.set_profile(args.profile)
    with C.Timer(f"fig_characterization_online ({C.PROFILE})"):
        run()
