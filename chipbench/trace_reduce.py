"""Reduce a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX (``jax.profiler.ProfileData``).  Two sources in it are used:

* the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane: the operations
  that ran on the chip, named after their HLO instruction (a Pallas
  kernel under its ``name``, e.g. ``engine_scan_ext_slot``);
* the host thread that carries the benchmark's ``chipbench.*``
  annotations: they bound the traced window, and the program's host
  events on that thread say what the host was doing in each device gap.

Busy time is the union of the device operations' intervals within the
window, averaged over the chips used; idle share is one minus busy over
the window.  The reduction works on plain event lists so a recorded trace
can be checked without a chip.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

ANNOTATION = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def op_name(hlo: str) -> str:
    """``%engine_scan_ext_slot.1 = (s32[...]) custom-call(...)`` ->
    ``engine_scan_ext_slot``."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def load_events(path: str) -> List[Event]:
    """Device operations of every TPU plane, and the events of the host
    thread that holds the benchmark's annotations."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.extend(Event(plane.name, line.name, e.name,
                                     e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(line.events)
                if any(e.name.startswith(ANNOTATION) for e in evs):
                    out.extend(Event(plane.name, line.name, e.name,
                                     e.start_ns, e.duration_ns)
                               for e in evs)
    return out


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


@dataclass
class Summary:
    window_s: float
    busy_s: float
    op_s: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def kernel_s(self, prefix: str) -> float:
        """Device seconds of the operations whose name starts with
        ``prefix``."""
        return sum(s for n, s in self.op_s.items() if n.startswith(prefix))

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _label(lo: float, hi: float, host: Sequence[Event]) -> str:
    """What the host was doing in a device gap: the program's host event
    that overlaps it most, else the innermost benchmark annotation."""
    best, best_ov = None, 0.0
    for e in host:
        ov = min(hi, e.end_ns) - max(lo, e.start_ns)
        if ov > best_ov and not e.name.startswith(ANNOTATION):
            best, best_ov = e.name, ov
    if best is not None and best_ov >= 0.5 * (hi - lo):
        return best
    around = [e for e in host if e.name.startswith(ANNOTATION)
              and e.start_ns <= lo and e.end_ns >= hi]
    inner = min(around, key=lambda e: e.dur_ns).name if around else "host"
    return f"{inner}: host code without trace events" if best is None \
        else f"{inner}: {best} and untraced host code"


def summarize(events: Sequence[Event], n_devices: int) -> Summary:
    """The window is bounded by the benchmark's pass annotations."""
    marks = [e for e in events if e.name == ANNOTATION + "pass"]
    if not marks:
        raise ValueError("trace holds no chipbench.pass annotation")
    lo = min(e.start_ns for e in marks)
    hi = max(e.end_ns for e in marks)
    host = [e for e in events if not DEVICE_PLANE.match(e.plane)]
    busy, op_s = [], {}
    for i in range(n_devices):
        plane = f"/device:TPU:{i}"
        ops = [e for e in events if e.plane == plane]
        busy.append(union(clip([(e.start_ns, e.end_ns) for e in ops],
                               lo, hi)))
        for e in ops:
            if lo <= e.start_ns < hi:
                name = op_name(e.name)
                op_s[name] = op_s.get(name, 0.0) + e.dur_ns * 1e-9
    busy_s = sum(b - a for iv in busy for a, b in iv) * 1e-9 / n_devices
    edges = [lo] + [x for a, b in busy[0] for x in (a, b)] + [hi]
    longest = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                      if b > a), reverse=True)[:TOP]
    gaps = [(_label(a, b, host), d * 1e-9) for d, a, b in longest]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_s, op_s=op_s,
                   gaps=gaps)


def reduce_dir(trace_dir: str, n_devices: int) -> Summary:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return summarize(load_events(files[0]), n_devices)
