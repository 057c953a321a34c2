"""Readings the ``correct`` limits are set from (run on the chip).

    python3 chipbench/control.py --workload morpheus-all.sweep --seeds 1 2 3 ...

For a cell, one process runs, for every seed, one pass of the program at
the cell's own size on that seed's first round of traces, and checks the
sample of answers that seed's runs check.  It prints, per seed, the
numbers ``correct`` is decided on for

* the program (sound runs: the lower readings), and
* the control: the plain reference put in the program's place with its
  float Stats held in bfloat16, the precision below the float32 the
  configuration states (the upper readings),

and, for the first seed, the program's numbers over every point of the
pass.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness  # noqa: E402


def bf16(x: float) -> float:
    import ml_dtypes
    return float(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16))


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    job = harness.resolve(args.workload)
    harness.enable_compile_cache()
    harness.devices(job.cell["chips"], require_tpu=True)
    sys.path.insert(0, str(harness.ROOT / "src"))
    kind, rows, spent = job.kind, [], []
    for seed in args.seeds:
        runner = kind.Runner(job.config, job.traffic, seed, job.pool)
        pass_ = runner.run_pass()

        def ref(i, round_floats=None):
            t = time.perf_counter()
            out = runner.expected(i, pass_.trace_seed, round_floats)
            spent.append(time.perf_counter() - t)
            return out
        picks = [i for _, i in runner.sample(1)]
        want = [ref(i) for i in picks]
        rows.append({
            "seed": seed, "trace_seed": pass_.trace_seed,
            "program": kind.compare(
                [kind.answer(pass_.results[i]) for i in picks], want),
            "control": kind.compare([ref(i, bf16) for i in picks], want)})
        if seed == args.seeds[0]:
            every = range(len(runner.points))
            rows[-1]["program_all_points"] = kind.compare(
                [kind.answer(pass_.results[i]) for i in every],
                [ref(i) for i in every])
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": rows,
                      "reference_s_per_point": sum(spent) / len(spent)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
