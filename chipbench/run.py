"""Benchmark entry point: run one cell once (see ``harness.py``).

    python3 chipbench/run.py --workload morpheus-all.sweep --seed 7 --seconds 10 --trace 0
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
