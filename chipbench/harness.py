"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, its traffic file (``traffic/<traffic>.json``),
the module of the traffic's kind (``kinds/<kind>.py``), the cell's
trace-seed pool if it has one (``pools/<cell>.json``) and one reader per
per-layer metric (``metrics/<metric>.py``).  A run:

1. refuses to start without a TPU holding the cell's chips;
2. keeps JAX's persistent compilation cache in ``<checkout>/.jax_cache``,
   and freed host memory in the process (``keep_freed_memory``);
3. warms up with one pass of each input the window will use, which
   compiles or loads every program the window runs (set-up ends here);
4. repeats passes of the same work for ``--seconds`` (profiled, with the
   program's spans and counters on, with ``--trace 1``) and counts
   compilations inside that window;
5. reads the device's peak memory, then checks a sample of the window's
   answers against the plain reference;
6. prints the check numbers with their limits as the last lines of
   standard error and the JSON result as the last line of standard output.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import resource
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
METRICS_DIR = HERE / "metrics"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str) -> SimpleNamespace:
    """The cell, its configuration, traffic, kind module and metrics."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])
    pool = HERE / "pools" / f"{workload}.json"
    return SimpleNamespace(
        cell=cell, config=load_json(ROOT / conf_entry["file"]),
        traffic=traffic, pool=load_json(pool) if pool.is_file() else None,
        kind=importlib.import_module(f"chipbench.kinds.{traffic['kind']}"),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found '{devs[0].platform}'")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"cell asks for {chips} chips; JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Counts XLA compilations: JAX reports a backend compile for every
    executable it builds or loads, and a cache hit for each one loaded
    from the persistent cache."""

    def __init__(self):
        from jax import monitoring
        self.built = self.loaded = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    @property
    def n(self) -> int:
        return self.built - self.loaded

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1


class ObsWindow:
    """What the program's own spans and counters (``repro.obs``) recorded
    between ``start()`` and ``stop()``: every counter sample and every
    completed span, whatever their names, so that a per-layer reader can
    take any of them without a change here."""

    def __init__(self):
        from repro import obs
        obs.enable(trace=True, metrics=True)
        self._obs = obs
        self._c0 = self._c1 = {}
        self._spans = []
        self._n0 = 0

    def _counters(self) -> dict:
        reg = self._obs.metrics_registry()
        return {(m["name"], tuple(sorted(s["labels"].items()))): s["value"]
                for m in reg.snapshot()["metrics"] if m["kind"] == "counter"
                for s in m["samples"]}

    def start(self) -> None:
        self._c0 = self._counters()
        self._n0 = len(self._obs.tracer().events)

    def stop(self) -> None:
        self._c1 = self._counters()
        self._spans = [e for e in self._obs.tracer().events[self._n0:]
                       if e["ph"] == "X"]

    def counter(self, name: str, **labels) -> float:
        """Increase of counter ``name`` in the window, summed over its
        samples whose labels include ``labels``."""
        def total(snap):
            return sum(v for (n, lab), v in snap.items() if n == name
                       and all(dict(lab).get(k) == x
                               for k, x in labels.items()))
        return total(self._c1) - total(self._c0)

    def span(self, name: str) -> SimpleNamespace:
        """Spans named ``name`` completed in the window: their number and
        summed seconds."""
        durs = [e["dur"] for e in self._spans if e["name"] == name]
        return SimpleNamespace(count=len(durs), total_s=sum(durs) * 1e-6)


def keep_freed_memory() -> None:
    """Serve every allocation from glibc's heap and never give freed memory
    back to the kernel, so that identical passes reuse the same pages.
    Without this the program's large per-dispatch arrays are mapped fresh
    on every pass, and what those page faults cost varies from process to
    process on the chip's host, by up to a quarter of a pass.  The program
    as its users run it has no such setting: its rates here are those of a
    heap-tuned process, and a change to how it allocates host memory
    shows less here than it would to them."""
    import ctypes
    mallopt = ctypes.CDLL("libc.so.6").mallopt
    mallopt.argtypes, mallopt.restype = [ctypes.c_int, ctypes.c_int], \
        ctypes.c_int
    m_trim_threshold, m_mmap_max = -1, -4
    for param, value in ((m_mmap_max, 0), (m_trim_threshold, 2**31 - 1)):
        if mallopt(param, value) != 1:
            raise OSError(f"mallopt({param}, {value}) failed")


def run_window(runner, seconds: float, trace_dir: str | None,
               window: ObsWindow | None = None):
    """Passes until ``seconds`` have elapsed; returns (results of each
    pass, window seconds)."""
    import jax
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if window is not None:
        window.start()
    passes, ends = [], []
    r0 = resource.getrusage(resource.RUSAGE_SELF)
    t0 = time.perf_counter()
    while not passes or ends[-1] - t0 < seconds:
        with jax.profiler.TraceAnnotation("chipbench.pass"):
            passes.append(runner.run_pass())
        ends.append(time.perf_counter())
    if window is not None:
        window.stop()
    if trace_dir:
        jax.profiler.stop_trace()
    r1 = resource.getrusage(resource.RUSAGE_SELF)
    walls = [b - a for a, b in zip([t0] + ends, ends)]
    print("pass seconds: " + " ".join(f"{w:.3f}" for w in walls),
          file=sys.stderr)
    print(f"window host: user {r1.ru_utime - r0.ru_utime:.3f} s, system "
          f"{r1.ru_stime - r0.ru_stime:.3f} s, max resident "
          f"{r1.ru_maxrss / 2**20:.2f} GiB", file=sys.stderr)
    return passes, ends[-1] - t0


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run(args, t_start: float, require_tpu: bool = True,
        job: SimpleNamespace | None = None) -> dict:
    """One run of a cell; returns the result object.  Tests pass
    ``require_tpu=False`` and a ``job`` with smaller traffic to rehearse
    a run on the CPU."""
    job = job or resolve(args.workload)
    keep_freed_memory()
    enable_compile_cache()
    devs = devices(job.cell["chips"], require_tpu)
    used = devs[:job.cell["chips"]]
    sys.path.insert(0, str(ROOT / "src"))
    compiles = CompileCounter()
    window = ObsWindow() if args.trace else None
    import jax
    runner = job.kind.Runner(job.config, job.traffic, args.seed, job.pool)
    t_warm = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.warm"):
        runner.warm()
    gc.collect()
    setup_s = time.perf_counter() - t_start
    print(f"setup {setup_s:.3f} s (warm pass {time.perf_counter() - t_warm:.3f}"
          f" s, {compiles.n} compiled, {compiles.loaded} loaded from the "
          f"cache)", file=sys.stderr)
    c0 = compiles.n
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    try:
        passes, window_s = run_window(runner, args.seconds, trace_dir,
                                      window)
        in_window = compiles.n - c0
        peak = memory_peak(used)
        trace = None
        if trace_dir:
            from . import trace_reduce
            trace = trace_reduce.reduce_dir(trace_dir, len(used))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"compiles in window: {in_window}", file=sys.stderr)
    t_check = time.perf_counter()
    numbers, failed = runner.check(passes)
    print(f"reference check {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)
    limits = job.traffic["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)
    work = runner.work(len(passes))
    if args.trace:
        metrics = per_layer(job, work, trace, window, devs[0])
    else:
        rate = job.traffic["rate_metric"]
        values = {"setup_s": setup_s, rate: work["requests"] / window_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in job.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": work["points"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = trace.breakdown()
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    return result


def per_layer(job, work, trace, window: ObsWindow, dev) -> dict:
    """Each per-layer metric from its reader, ``metrics/<name>.py``,
    whose ``read(ctx)`` returns a number or None (nothing to read).  The
    context has the window's work (``work``), the reduced device trace
    (``trace``), the device's peaks (``peak()``) and the program's spans
    and counters in the window (``counter(name, **labels)``,
    ``span(name)``)."""
    from . import roofline
    ctx = SimpleNamespace(
        work=work, trace=trace,
        peak=lambda: roofline.peaks(dev.device_kind),
        counter=window.counter, span=window.span)
    out = {}
    for m in job.per_layer:
        reader = load_module(METRICS_DIR / f"{m['name']}.py",
                             "chipbench_metric_" + m["name"].replace(
                                 ".", "_").replace("-", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        result = run(args, t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
