"""Schema-versioned benchmark result files (``BENCH_*.json`` at repo root).

Every ``tools/bench_*`` script records its wall-clock timings through
``write_bench`` so performance is diffable across commits:

  * the committed files are the current baselines;
  * ``tools/bench_compare.py`` diffs a baseline against a fresh run and
    flags warm-path regressions (>10% by default);
  * CI validates every committed ``BENCH_*.json`` against this schema
    (``bench_compare.py --validate``).

Timing labels are free-form, but labels containing ``"warm"`` mark
steady-state measurements — those are the regression-gated ones
(cold/jit labels include compilation and are machine-noisy).

Schema v2 adds an optional ``counters`` dict — non-negative numbers from
the observability probes (``repro.obs.bench_counters()``: dispatches,
compiles, device_get bytes, flush writebacks, epochs) — so a perf diff
can distinguish "same work, slower" from "more dispatches".  v1 files
(no ``counters``) stay valid; ``bench_compare --validate`` accepts both.

``REPRO_BENCH_PATH`` redirects ``write_bench``'s default output, so a
throwaway document leaves the committed baselines untouched.
"""
from __future__ import annotations

import json
import os
import platform
from pathlib import Path
from typing import Dict, Optional

SCHEMA = 2
KNOWN_SCHEMAS = (1, 2)
ROOT = Path(__file__).resolve().parents[1]
REQUIRED = ("schema", "bench", "profile", "created", "machine", "timings")


def bench_path(name: str) -> Path:
    return ROOT / f"BENCH_{name}.json"


def machine_info() -> Dict:
    import jax
    import numpy
    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "numpy": numpy.__version__,
        "cpu_count": os.cpu_count() or 1,
        "jax_backend": jax.default_backend(),
        "devices": len(jax.devices()),
    }


def write_bench(name: str, profile: str, timings: Dict[str, float], *,
                extra: Optional[Dict] = None,
                counters: Optional[Dict[str, float]] = None,
                path: Optional[Path] = None) -> Path:
    """Write one bench document; ``timings`` maps label -> seconds,
    ``counters`` maps probe name -> count (``obs.bench_counters()``).
    ``path`` (or the ``REPRO_BENCH_PATH`` env var) overrides the default
    committed-baseline location."""
    import time
    doc = {
        "schema": SCHEMA,
        "bench": name,
        "profile": profile,
        "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "machine": machine_info(),
        "timings": {k: round(float(v), 4) for k, v in timings.items()},
    }
    if counters is not None:
        doc["counters"] = {k: round(float(v), 4) if v != int(v)
                           else int(v) for k, v in counters.items()}
    if extra:
        doc["extra"] = extra
    validate(doc, name)
    if path is not None:
        p = Path(path)
    elif os.environ.get("REPRO_BENCH_PATH"):
        p = Path(os.environ["REPRO_BENCH_PATH"])
    else:
        p = bench_path(name)
    p.write_text(json.dumps(doc, indent=1) + "\n")
    return p


def load_bench(path) -> Dict:
    doc = json.loads(Path(path).read_text())
    validate(doc, str(path))
    return doc


def validate(doc: Dict, ctx: str = "bench file") -> None:
    """Raise AssertionError unless ``doc`` is a valid bench document."""
    missing = [k for k in REQUIRED if k not in doc]
    assert not missing, f"{ctx}: missing keys {missing}"
    assert doc["schema"] in KNOWN_SCHEMAS, (
        f"{ctx}: schema {doc['schema']!r} not in {KNOWN_SCHEMAS} "
        f"(regenerate the file)")
    t = doc["timings"]
    assert isinstance(t, dict) and t, f"{ctx}: timings empty or not a dict"
    bad = [k for k, v in t.items()
           if not isinstance(v, (int, float)) or v < 0]
    assert not bad, f"{ctx}: non-numeric/negative timings {bad}"
    if "counters" in doc:
        assert doc["schema"] >= 2, \
            f"{ctx}: counters require schema >= 2"
        c = doc["counters"]
        assert isinstance(c, dict), f"{ctx}: counters not a dict"
        badc = [k for k, v in c.items()
                if not isinstance(v, (int, float)) or v < 0]
        assert not badc, f"{ctx}: non-numeric/negative counters {badc}"
