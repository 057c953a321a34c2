"""Smoke test on one TPU: the simulator and the serving pool, compiled.

    python chip_smoke.py             # one chip: sweep, online, fleet, serve
    python chip_smoke.py --chips 4   # the fleet's shard_map path on 4 chips

Drives the system's main paths once through the entry points a user calls,
in one process, and checks what comes out by the repo's own means:

  sweep   the Table-3 mode-split sweep (``policy.grid_points`` ->
          ``cache_sim.run_batch``) at the ``full`` benchmark profile: three
          memory-bound apps on Baseline and Morpheus-ALL over the full
          Morpheus grid, run on the compiled Pallas engine (twice: cold,
          then steady) and on the jnp engine.  Integer Stats must agree at
          every point; one short point must match the serial oracle
          ``controller.simulate``.
  online  ``simulate_online`` on a phased trace at epoch lengths 3 000 and
          24 000; integer Stats must equal one monolithic engine run.
  fleet   ``simulate_fleet`` with 16 governed replicas on one device; each
          replica must be bit-identical to its ``simulate_online`` loop.
  serve   the ``launch/serve.py`` host path (reduced model widths,
          ``--split auto``, two tenants); hit pages must read back their
          payloads, and all five pool kernels must have run compiled.

``--chips 4`` runs only the fleet on a 4-device ``("fleet",)`` mesh and the
same 16 replicas on one device, which must be bit-identical.

Each phase prints one line (wall time, XLA compiles, agreement counts).  Any
failed check raises.  Without a TPU it exits non-zero before any phase.  The
last line of a passing run is the JSON verdict with the device JAX reports.
Timings here are smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

APPS = ("kmeans", "cfd", "stencil")          # memory-bound Table-2 apps
SYSTEMS = ("BL", "Morpheus-ALL")
ORACLE_LEN = 4_000                           # the serial oracle's point
ONLINE_LEN = 120_000
ONLINE_EPOCHS = (3_000, 24_000)
FLEET_REPLICAS = 16
FLEET_LEN = 24_000
SPLITS = ((32, 36), (48, 20))                # the fleet governors' ladder


def _ints(stats) -> dict:
    from repro.core import controller as ctl
    import numpy as np
    return {f: np.asarray(getattr(stats, f)).tolist()
            for f in ctl._INT_FIELDS}


def _compiles() -> int:
    from repro import obs
    c = obs.metrics_registry().get("jax_compiles")
    return int(c.total()) if c is not None else 0


def run_phase(name: str, fn, *args) -> None:
    """Run one phase and print its line; its checks raise on failure."""
    c0, t0 = _compiles(), time.perf_counter()
    detail = fn(*args)
    print(f"[{name}] wall {time.perf_counter() - t0:.1f}s | "
          f"compiles {_compiles() - c0} | {detail}", flush=True)


# ------------------------------------------------------------------ phases

def sweep(length: int, grid, oracle_len: int) -> str:
    import jax.numpy as jnp
    from repro.core import cache_sim as cs, controller as ctl, policy
    from repro.kernels import ops

    def points(backend):
        return [p for system in SYSTEMS for app in APPS
                for p in policy.grid_points(app, system, grid=grid,
                                            length=length, backend=backend)]

    walls = {}
    results = {}
    for label, backend in (("pallas cold", "pallas"),
                           ("pallas steady", "pallas"), ("jnp", "jnp")):
        t0 = time.perf_counter()
        results[label] = cs.run_batch(points(backend))
        walls[label] = time.perf_counter() - t0
    pal, again, ref = (results["pallas cold"], results["pallas steady"],
                       results["jnp"])
    n = len(pal)
    same = sum(_ints(a.stats) == _ints(b.stats) for a, b in zip(pal, ref))
    rerun = sum(_ints(a.stats) == _ints(b.stats) for a, b in zip(pal, again))
    assert same == n, f"pallas vs jnp integer Stats agree at {same}/{n}"
    assert rerun == n, f"pallas rerun agrees at {rerun}/{n}"

    def best(res):
        out = {}
        for r in res:
            key = (r.app, r.system)
            if key not in out or r.exec_time_s < out[key].exec_time_s:
                out[key] = r
        return {k: (r.n_compute, r.n_cache) for k, r in out.items()}
    splits = best(pal)
    assert splits == best(ref), "best splits differ between engines"

    pt = cs.RunPoint(APPS[0], "Morpheus-ALL", 32, 36, oracle_len,
                     backend="pallas")
    cfg, (addrs, writes, levels, warmup), *_ = cs._prepare(pt)
    oracle = ctl.simulate_jit(cfg, jnp.asarray(addrs), jnp.asarray(writes),
                              jnp.asarray(levels), warmup)
    assert _ints(cs.run_batch([pt])[0].stats) == _ints(oracle), \
        "pallas engine disagrees with the serial oracle"
    assert not ops.interpret_mode(), "the Pallas engine would interpret"
    return (f"{n} points ({len(splits)} app x system, length {length}) | "
            f"pallas=jnp {same}/{n} | rerun {rerun}/{n} | "
            f"oracle 1/1 | best splits "
            + " ".join(f"{a}/{s}={c[0]}+{c[1]}"
                       for (a, s), c in sorted(splits.items()))
            + " | " + " ".join(f"{k} {v:.1f}s" for k, v in walls.items()))


def online(length: int, epoch_lens) -> str:
    from repro.core import cache_sim as cs, engine
    from repro.runtime import OnlineReplica, simulate_online
    phases, system, split = list(APPS[:2]), "Morpheus-ALL", SPLITS[0]
    rep = OnlineReplica(phases, system, length=length, fixed_split=split)
    addrs, writes, levels = rep.trace_of[split[0]]
    mono = engine.simulate_batch(cs.build_config(rep.spec, split[1]),
                                 [(addrs, writes, levels, 0)])
    mono = {f: v[0] for f, v in _ints(mono).items()}
    walls, same = [], 0
    for elen in epoch_lens:
        t0 = time.perf_counter()
        r = simulate_online(phases, system, length=length, epoch_len=elen,
                            fixed_split=split)
        walls.append(f"epoch {elen}: {len(r.records)} epochs "
                     f"{time.perf_counter() - t0:.1f}s")
        same += _ints(r.stats) == mono
    assert same == len(epoch_lens), \
        f"epoch streams equal the monolithic run at {same}/{len(epoch_lens)}"
    return (f"{'+'.join(phases)} on {system} {split[0]}+{split[1]}, length "
            f"{length} | epochs=monolithic {same}/{len(epoch_lens)} | "
            + " | ".join(walls))


def _fleet_specs(length: int):
    from repro.runtime import ReplicaSpec
    return [ReplicaSpec(APPS[i % len(APPS)], "Morpheus-ALL", length=length,
                        epoch_len=3_000, seed=i, candidates=list(SPLITS))
            for i in range(FLEET_REPLICAS)]


def _same_replica(a, b) -> bool:
    return (_ints(a.stats) == _ints(b.stats)
            and [(r.n_compute, r.n_cache) for r in a.records]
            == [(r.n_compute, r.n_cache) for r in b.records]
            and a.switches == b.switches)


def fleet(length: int) -> str:
    from repro.runtime import run_serial, simulate_fleet
    specs = _fleet_specs(length)
    t0 = time.perf_counter()
    fr = simulate_fleet(specs)
    t1 = time.perf_counter()
    serial = run_serial(specs)
    t2 = time.perf_counter()
    same = sum(_same_replica(a, b) for a, b in zip(serial, fr.results))
    assert same == len(specs), \
        f"fleet replicas equal simulate_online at {same}/{len(specs)}"
    return (f"{len(specs)} replicas, {fr.epochs} steps, {fr.dispatches} "
            f"dispatches, {sum(r.switches for r in fr.results)} switches "
            f"| fleet=simulate_online {same}/{len(specs)} | fleet "
            f"{t1 - t0:.1f}s, serial {t2 - t1:.1f}s")


def fleet_mesh(length: int) -> str:
    from repro.launch.mesh import make_fleet_mesh
    from repro.runtime import simulate_fleet
    specs = _fleet_specs(length)
    mesh = make_fleet_mesh()
    t0 = time.perf_counter()
    sharded = simulate_fleet(specs, mesh=mesh)
    t1 = time.perf_counter()
    one = simulate_fleet(specs)
    t2 = time.perf_counter()
    same = sum(_same_replica(a, b)
               for a, b in zip(one.results, sharded.results))
    assert sharded.mesh_devices == len(mesh.devices.flat) > 1
    assert same == len(specs), \
        f"sharded fleet equals one device at {same}/{len(specs)}"
    return (f"{len(specs)} replicas on a {sharded.mesh_devices}-device "
            f"fleet mesh, {sharded.dispatches} dispatches | sharded=one "
            f"device {same}/{len(specs)} | mesh {t1 - t0:.1f}s, one device "
            f"{t2 - t1:.1f}s")


POOL_KERNELS = ("tag_lookup", "bloom_query", "bdi_compress",
                "bdi_decompress", "gather_blocks")


def serve(rounds: int) -> str:
    from repro import obs
    from repro.launch import serve as serve_cli
    eng = serve_cli.main(["--arch", "qwen3-4b", "--split", "auto",
                          "--workload", "tenantA,tenantB",
                          "--rounds", str(rounds)])
    calls = {}
    for smp in obs.metrics_registry().get("pallas_calls").samples():
        key = (smp["labels"]["kernel"], smp["labels"]["interpret"])
        calls[key] = calls.get(key, 0) + int(smp["value"])
    ran = {k for k, it in calls if it == "False"}
    interpreted = {k for k, it in calls if it != "False"}
    assert not interpreted, f"pool kernels interpreted: {interpreted}"
    missing = set(POOL_KERNELS) - ran
    assert not missing, f"pool kernels never ran: {missing}"
    assert eng.pages_reused > 0 and eng.pages_mismatched == 0, \
        (f"{eng.pages_mismatched} of {eng.pages_reused} hit pages read "
         f"back wrong")
    return (f"{rounds} rounds | hit pages read back "
            f"{eng.pages_reused - eng.pages_mismatched}/{eng.pages_reused}"
            f" | compiled kernel calls "
            + " ".join(f"{k}:{calls[(k, 'False')]}" for k in POOL_KERNELS))


# -------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"'{devices[0].platform}'", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from benchmarks.common import MORPHEUS_GRID_OF, TRACE_LEN_OF
    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    obs.enable(trace=False, metrics=True)
    print(f"device: {devices[0].device_kind} x{len(devices)}")

    if args.chips == 4:
        run_phase("fleet-mesh", fleet_mesh, FLEET_LEN)
    else:
        run_phase("sweep", sweep, TRACE_LEN_OF["full"],
                  MORPHEUS_GRID_OF["full"], ORACLE_LEN)
        run_phase("online", online, ONLINE_LEN, ONLINE_EPOCHS)
        run_phase("fleet", fleet, FLEET_LEN)
        run_phase("serve", serve, 4)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
