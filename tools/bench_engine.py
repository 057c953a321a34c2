"""Serial vs set-parallel vs Pallas timing for the mode-split sweep.

Times the Table-3 style offline policy sweep (IBL / Morpheus-Basic /
Morpheus-ALL over all 17 workloads) three ways:

  * serial        — the seed implementation: one ``controller.simulate_jit``
                    (per-request ``lax.scan``) per grid point;
  * batched[jnp]  — ``cache_sim.run_batch``: points grouped by config shape
                    and dispatched through the vmapped set-parallel engine;
  * batched[pallas] — the same sweep with the engine's inner scan fused
                    into the ``kernels/engine_scan`` Pallas kernel
                    (interpret mode off-TPU).

  PYTHONPATH=src python tools/bench_engine.py [quick|std|full] [backend ...]

Optional ``backend`` args restrict the batched paths (default: every
backend supported on this host).  The selected backends are printed up
front; requesting an unsupported one fails with a one-line explanation,
not a Pallas traceback.  Prints a table (path, wall-clock, speedup); the
result table is recorded in CHANGES.md.
"""
import os
import sys
import time
from dataclasses import replace
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "tools"))

_args = sys.argv[1:]
PROFILE = _args[0] if _args and _args[0] in ("quick", "std", "full") \
    else "std"
os.environ["REPRO_BENCH_PROFILE"] = PROFILE
REQUESTED = [a for a in _args if a not in ("quick", "std", "full")]

from repro import obs                            # noqa: E402
from repro.core import cache_sim as cs           # noqa: E402
from repro.core import controller as ctl         # noqa: E402
from repro.core import engine                    # noqa: E402
from repro.core import policy                    # noqa: E402
from repro.core import traces as tr              # noqa: E402

from benchmarks import common as C               # noqa: E402

import bench_schema as bs                        # noqa: E402

SYSTEMS = ("IBL", "Morpheus-Basic", "Morpheus-ALL")


def sweep_points():
    pts = []
    for system in SYSTEMS:
        spec = cs.SYSTEMS[system]
        for app in tr.MEMORY_BOUND + tr.COMPUTE_BOUND:
            w = tr.WORKLOADS[app]
            if spec.morpheus and not w.memory_bound:
                continue  # recorded directly by mode_splits, no sweep
            grid = C.MORPHEUS_GRID if (spec.morpheus and w.memory_bound) \
                else C.GRID
            pts.extend(policy.grid_points(app, system, grid=grid,
                                          length=C.TRACE_LEN))
    return pts


def run_serial(pts):
    import jax.numpy as jnp
    out = []
    for pt in pts:
        cfg, (addrs, writes, levels, warmup), n_c, n_k, n_acc = \
            cs._prepare(pt)
        stats = ctl.simulate_jit(cfg, jnp.asarray(addrs),
                                 jnp.asarray(writes), jnp.asarray(levels),
                                 warmup)
        stats = ctl.Stats(*[x.block_until_ready() for x in stats])
        out.append(cs._finalize(pt, n_c, n_k, n_acc, stats))
    return out


def best_splits(pts, results):
    best = {}
    for pt, r in zip(pts, results):
        key = (pt.app, pt.system)
        if key not in best or r.exec_time_s < best[key][1]:
            best[key] = (r.n_compute, r.exec_time_s)
    return best


def pick_backends():
    """Resolve the requested backend list, failing with a clear message.

    Default: every backend that runs *natively* here, plus pallas
    interpret mode only on the quick profile (interpret emulates the grid
    sequentially — on std/full sweeps that is tens of minutes, so it must
    be requested explicitly: ``bench_engine.py std pallas``)."""
    if REQUESTED:
        try:
            return [engine.resolve_backend(b) for b in REQUESTED]
        except engine.BackendError as e:
            print(f"error: {e}", file=sys.stderr)
            sys.exit(2)
    out = ["jnp"]
    if engine.backend_status("pallas")[0] and (
            PROFILE == "quick" or engine.default_backend() == "pallas"):
        out.append("pallas")
    return out


def main():
    # metrics-only (no spans): the counters land in the bench document,
    # while the committed timings stay free of span-recording overhead
    obs.enable(trace=False)
    backends = pick_backends()
    for b in engine.BACKENDS:
        ok, detail = engine.backend_status(b)
        sel = "selected" if b in backends else \
            ("available" if ok else "unavailable")
        print(f"backend {b:7s} [{sel}] — {detail}")

    pts = sweep_points()
    print(f"profile={PROFILE}  trace_len={C.TRACE_LEN}  points={len(pts)}")

    timings = {}   # label -> (seconds, results-or-None)
    for b in backends:
        bpts = [replace(pt, backend=b) for pt in pts]
        t0 = time.time()
        rb = cs.run_batch(bpts)
        timings[f"run_batch[{b}] cold+jit"] = (time.time() - t0, rb)
        # warm = best of 3: single-shot wall-clock on a shared host is
        # too noisy to compare
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            rb = cs.run_batch(bpts)
            best = min(best, time.time() - t0)
        timings[f"run_batch[{b}] warm"] = (best, rb)

    t0 = time.time()
    rs = run_serial(pts)
    t_serial = time.time() - t0

    # sanity: every path must agree on every best split
    ref = best_splits(pts, rs)
    agreement = {}
    for label, (_, rb) in timings.items():
        got = best_splits(pts, rb)
        agree = sum(got[k][0] == ref[k][0] for k in ref)
        agreement[label] = f"{agree}/{len(ref)}"
        print(f"best-split agreement serial vs {label}: {agree}/{len(ref)}")

    print(f"{'path':26s} {'wall-clock':>12s} {'speedup':>9s}")
    print(f"{'serial lax.scan':26s} {t_serial:11.1f}s {1.0:8.1f}x")
    for label, (secs, _) in timings.items():
        print(f"{label:26s} {secs:11.1f}s {t_serial / secs:8.1f}x")

    flat = {"serial lax.scan": t_serial}
    flat.update({label: secs for label, (secs, _) in timings.items()})
    out = bs.write_bench("engine", PROFILE, flat,
                         counters=obs.bench_counters(), extra={
        "points": len(pts), "trace_len": C.TRACE_LEN,
        "backends": backends, "best_split_agreement": agreement})
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
