"""Traffic kind ``sweep``: a mode-split search as one ``run_batch``.

One pass simulates every (app, compute-core count) point of the traffic
file on the configuration's system through ``cache_sim.run_batch``, the
primitive ``policy.table3`` is built on, on one round of traces: every
point's trace made from one trace seed.  ``--seed`` draws the run's
rounds (``rounds`` of them, from the cell's pool, see ``pools.py``), which the warm-up runs once each and the window takes in
turn, so that no pass of the window repeats the one before it; and it
draws the sample of answers the reference checks.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from .. import reference, roofline

Point = Tuple[str, int, int]          # (app, compute cores, cache cores)


def points(config: dict, traffic: dict) -> List[Point]:
    out = []
    for app in traffic["apps"]:
        for n_compute in traffic["grid"]:
            n_cache = reference.grid_split(config, app, n_compute)
            if n_cache is not None:
                out.append((app, n_compute, n_cache))
    return out


def answer(result) -> Dict[str, float]:
    """A ``RunResult`` as plain numbers: Stats, execution time, IPC."""
    out = {f: np.asarray(getattr(result.stats, f)).item()
           for f in reference.INT_FIELDS + reference.FLOAT_FIELDS}
    out["exec_time_s"] = float(result.exec_time_s)
    out["ipc"] = float(result.ipc)
    return out


def compare(program: Sequence[Dict[str, float]],
            expected: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """The numbers ``correct`` is decided on: integer Stats that differ,
    and the widest relative gap of a float Stat, the execution time or
    the IPC."""
    mismatch, rel = 0, 0.0
    for got, ref in zip(program, expected):
        mismatch += sum(int(got[f]) != int(ref[f])
                        for f in reference.INT_FIELDS)
        for f in reference.FLOAT_FIELDS + ("exec_time_s", "ipc"):
            gap = abs(float(got[f]) - float(ref[f]))
            rel = max(rel, gap / abs(ref[f]) if ref[f] else gap)
    return {"stats_mismatch": mismatch, "float_rel_err": rel}


def rounds(traffic: dict, seed: int, pool: dict | None) -> List[int]:
    """The trace seeds of a run's rounds: distinct seeds of the cell's
    pool, drawn from ``--seed``."""
    if pool is None:
        raise FileNotFoundError("a sweep cell draws its rounds from "
                                "pools/<cell>.json: run chipbench/pools.py")
    rng = np.random.default_rng([seed, 1])
    return [int(x) for x in rng.choice(pool["trace_seeds"],
                                       size=traffic["rounds"], replace=False)]


class Pass(NamedTuple):
    trace_seed: int
    results: list


class Runner:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 pool: dict | None):
        from repro.core import cache_sim
        self.config, self.traffic, self.seed = config, traffic, seed
        self.points = points(config, traffic)
        self.rounds = rounds(traffic, seed, pool)
        self._cache_sim = cache_sim
        self._done = 0

    # ------------------------------------------------------------- work
    def run_pass(self) -> Pass:
        """One pass on the next round."""
        trace_seed = self.rounds[self._done % len(self.rounds)]
        self._done += 1
        return Pass(trace_seed, self._cache_sim.run_batch([
            self._cache_sim.RunPoint(app, self.config["system"], n_compute,
                                     n_cache, self.traffic["length"],
                                     trace_seed)
            for app, n_compute, n_cache in self.points]))

    def warm(self) -> None:
        """One pass of each round: every shape the window will use."""
        for _ in self.rounds:
            self.run_pass()

    def work(self, passes: int) -> Dict[str, float]:
        """Work of ``passes`` passes, from the traffic's own parameters."""
        n = len(self.points)
        length = self.traffic["length"]
        state = sum(roofline.state_bytes(reference.geometry(self.config, k))
                    for _, _, k in self.points)
        return {"passes": passes, "points": passes * n,
                "requests": passes * n * length,
                "bytes": passes * roofline.sweep_bytes(n * length, state)}

    # ---------------------------------------------------------- checking
    def sample(self, n_passes: int) -> List[Tuple[int, int]]:
        """(pass, point) pairs the reference checks, drawn from the seed:
        distinct points, each from a random pass of the window."""
        rng = np.random.default_rng(self.seed)
        k = min(self.traffic["reference_sample"], len(self.points))
        idx = rng.choice(len(self.points), size=k, replace=False)
        return [(int(rng.integers(n_passes)), int(i)) for i in sorted(idx)]

    def expected(self, i: int, trace_seed: int,
                 round_floats=None) -> Dict[str, float]:
        app, n_compute, n_cache = self.points[i]
        return reference.run_point(self.config, app, n_compute, n_cache,
                                   self.traffic["length"], trace_seed,
                                   round_floats=round_floats)

    def check(self, passes: List[Pass]) -> Tuple[Dict[str, float], int]:
        """Compare the sampled answers of the window with the reference;
        returns the numbers and how many sampled answers differ."""
        picks = self.sample(len(passes))
        got = [answer(passes[p].results[i]) for p, i in picks]
        ref = [self.expected(i, passes[p].trace_seed) for p, i in picks]
        limits = self.traffic["limits"]
        failed = sum(any(v > limits[k] for k, v in compare([g], [r]).items())
                     for g, r in zip(got, ref))
        return compare(got, ref), failed
