"""Trace-seed pools: rounds of traces that give a sweep cell equal work.

    python3 chipbench/pools.py --workload morpheus-all.sweep

A pass of a sweep cell simulates one round: every point's trace made from
one trace seed.  The engine pads each dispatch (up to 16 points of one
configuration, in grid order) to a power of two of its busiest set, so a
trace seed changes the padded work: over a dozen seeds IBL-4x-LLC's padded
elements per pass range over 19%.  This tool replays the candidate seeds
with the benchmark's own trace generator and geometry, keeps the seeds
whose padded dispatch shapes all equal the most common ones, and writes
them to ``pools/<workload>.json``, which a sweep cell needs: a run draws
its rounds from that pool by ``--seed``.  The padding rule is the
program's today; where a later program pads otherwise, the pool's rounds
still run correctly, only less alike.  It runs on the CPU and needs no
chip.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
from pathlib import Path
from typing import List, Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness, reference, tracegen  # noqa: E402
from chipbench.kinds import sweep  # noqa: E402

DISPATCH = 16
CANDIDATES = 96          # trace seeds 0..95; about a third pad alike


def bucket(n: int) -> int:
    return 16 if n <= 16 else 1 << (int(n) - 1).bit_length()


def shapes(config: dict, traffic: dict, trace_seed: int
           ) -> List[Tuple[int, int]]:
    """(conventional, extended) padded length of every dispatch of a
    round: points grouped by configuration in grid order, 16 a dispatch,
    each length a power of two of the dispatch's busiest set."""
    groups = collections.defaultdict(list)
    for app, n_compute, n_cache in sweep.points(config, traffic):
        groups[n_cache].append((app, n_compute))
    out = []
    for n_cache, pts in groups.items():
        geo = reference.geometry(config, n_cache)
        busiest = []
        for app, n_compute in pts:
            addrs, _, _ = tracegen.generate(
                app, n_cores=n_compute, length=traffic["length"],
                seed=trace_seed, ws_scale=1.0 / config["sim_scale"])
            g = addrs % np.uint32(geo.total_sets)
            conv = np.bincount(g[g < geo.conv_sets], minlength=1).max()
            ext = (np.bincount(g[g >= geo.conv_sets] - geo.conv_sets,
                               minlength=1).max() if geo.ext_sets else 0)
            busiest.append((int(conv), int(ext)))
        for lo in range(0, len(busiest), DISPATCH):
            c, e = np.max(busiest[lo:lo + DISPATCH], axis=0)
            out.append((bucket(c), bucket(e) if geo.ext_sets else 0))
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    job = harness.resolve(args.workload)
    found = {s: tuple(shapes(job.config, job.traffic, s))
             for s in range(CANDIDATES)}
    common, _ = collections.Counter(found.values()).most_common(1)[0]
    pool = {"workload": args.workload, "candidates": CANDIDATES,
            "shapes": [list(s) for s in common],
            "trace_seeds": [s for s, v in found.items() if v == common]}
    path = harness.HERE / "pools" / f"{args.workload}.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(pool) + "\n")
    print(f"{path.name}: {len(pool['trace_seeds'])} of {CANDIDATES} "
          f"seeds pad to {pool['shapes']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
