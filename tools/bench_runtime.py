"""Benchmark the online runtime: epoch-streaming overhead + governor demo.

  PYTHONPATH=src python tools/bench_runtime.py [quick|std] [--backend jnp]
  PYTHONPATH=src python tools/bench_runtime.py --backend pallas

Part 1 times the epoch-streaming engine (``runtime.stream.EpochStream``)
against one monolithic ``engine.simulate_parallel`` dispatch over the same
trace, across epoch lengths, and checks the integer Stats are
bit-identical (the ``EngineState`` resume contract).  Each epoch length is
timed twice — per-epoch host packing (``ring 0``, the old behaviour) vs.
the device-resident ring of pre-packed epochs (``ring 8``), so the output
shows the per-epoch host packing + position-readback overhead the ring
removes.

Part 2 runs the adaptive governor (``runtime.governor.simulate_online``)
on a phase-shifting trace, prints the telemetry summary and exports the
per-epoch log to ``results/runtime_telemetry.{csv,json}``.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_schema as bs                                   # noqa: E402

from repro import obs                                       # noqa: E402
from repro.core import cache_sim as cs                      # noqa: E402
from repro.core import controller as ctl                    # noqa: E402
from repro.core import engine                               # noqa: E402
from repro.core import traces as tr                         # noqa: E402
from repro.runtime import EpochStream, simulate_online      # noqa: E402

RESULTS = Path(__file__).resolve().parents[1] / "results"

PROFILES = {
    "quick": dict(length=30_000, epochs=(1_000, 3_000), phased=60_000),
    "std": dict(length=120_000, epochs=(3_000, 12_000), phased=200_000),
}


def bench_stream(length: int, epoch_lens, backend: str) -> dict:
    spec = cs.SYSTEMS["Morpheus-ALL"]
    cfg = cs.build_config(spec, 36)
    addrs, writes, levels = tr.generate("cfd", n_cores=32, length=length,
                                        ws_scale=1.0 / spec.sim_scale)
    warmup = length // 4

    def ints(s):
        return {f: int(np.asarray(getattr(s, f)))
                for f in ctl._INT_FIELDS}

    t0 = time.time()
    mono = engine.simulate_parallel(cfg, addrs, writes, levels, warmup,
                                    backend=backend)
    mono_ints = ints(mono)
    t_mono_cold = time.time() - t0
    t0 = time.time()
    engine.simulate_parallel(cfg, addrs, writes, levels, warmup,
                             backend=backend)
    t_mono = time.time() - t0
    print(f"monolithic [{backend}]: cold {t_mono_cold:.2f}s / "
          f"warm {t_mono:.2f}s ({length} reqs)")
    timings = {f"monolithic[{backend}] cold+jit": t_mono_cold,
               f"monolithic[{backend}] warm": t_mono}

    for elen in epoch_lens:
        # compile this epoch shape once so neither variant pays it
        EpochStream(cfg, addrs, writes, levels, warmup=warmup,
                    epoch_len=elen, backend=backend).step()
        times = {}
        for ring in (0, 8):
            stream = EpochStream(cfg, addrs, writes, levels, warmup=warmup,
                                 epoch_len=elen, backend=backend, ring=ring)
            t0 = time.time()
            stream.run()
            times[ring] = time.time() - t0
            got = ints(stream.stats)
            if got != mono_ints:
                raise SystemExit(
                    f"bit-identity violated at epoch_len={elen} "
                    f"ring={ring}: {got} vs {mono_ints}")
        saved = times[0] - times[8]
        timings[f"stream[{backend}] epoch{elen} ring0 warm"] = times[0]
        timings[f"stream[{backend}] epoch{elen} ring8 warm"] = times[8]
        print(f"epoch_len {elen:>6}: {stream.epoch:>3} epochs | "
              f"host-pack-per-epoch {times[0]:6.2f}s -> prepacked ring "
              f"{times[8]:6.2f}s (saves {saved:+5.2f}s, "
              f"{times[8] / max(t_mono, 1e-9):4.1f}x warm monolithic) | "
              f"int-stats identical: True")
    return timings


def bench_governor(phased_len: int, backend: str) -> dict:
    phases = ("kmeans", "lib")
    t0 = time.time()
    r = simulate_online(phases, "Morpheus-ALL", length=phased_len,
                        epoch_len=3_000, backend=backend)
    dt = time.time() - t0
    print(f"\ngovernor on {'+'.join(phases)} ({phased_len} reqs, "
          f"{len(r.records)} epochs) in {dt:.1f}s")
    for k, v in r.log.summary().items():
        print(f"  {k}: {v}")
    RESULTS.mkdir(parents=True, exist_ok=True)
    csv_p = r.log.to_csv(RESULTS / "runtime_telemetry.csv")
    r.log.to_json(RESULTS / "runtime_telemetry.json")
    print(f"telemetry exported to {csv_p} (+ .json)")
    return {f"governor[{backend}] cold+jit": dt}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("profile", nargs="?", default="quick",
                    choices=sorted(PROFILES))
    ap.add_argument("--backend", default="",
                    help="engine backend (jnp|pallas; default session)")
    args = ap.parse_args()
    try:
        backend = engine.resolve_backend(args.backend or None)
    except engine.BackendError as e:
        print(f"error: {e}")
        raise SystemExit(2)
    p = PROFILES[args.profile]
    print(f"profile={args.profile} backend={backend}")
    obs.enable(trace=False)     # counters into the bench doc, no spans
    timings = bench_stream(p["length"], p["epochs"], backend)
    # governor leg runs with the cache microscope on (strided) so the
    # committed baseline exercises the snapshots counter too; the timed
    # stream sweeps above stay microscope-free
    obs.enable(trace=False, inspect=True, inspect_every=4)
    timings.update(bench_governor(p["phased"], backend))
    out = bs.write_bench("runtime", args.profile, timings,
                         counters=obs.bench_counters(),
                         extra={"backend": backend,
                                "length": p["length"],
                                "phased_len": p["phased"]})
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
