"""The readers of the program's host phases, on a synthetic context: each
reads its span or counter per point where the trace saw a device, and
returns nothing without one (on a CPU backend the device phases run on
the host) or where the program lacks the span or counter."""
from types import SimpleNamespace

import pytest

from chipbench import harness

POINTS, REQUESTS = 252, 30_240_000
SPANS = {"gen_ms_per_point.sweep": "cache_sim.prepare",
         "pack_ms_per_point.sweep": "engine.pack",
         "dispatch_ms_per_point.sweep": "engine.dispatch",
         "wait_ms_per_point.sweep": "cache_sim.wait",
         "unpack_ms_per_point.sweep": "cache_sim.unpack"}
READERS = list(SPANS) + ["pad_fill_pct.sweep"]


def reader(name):
    return harness.load_module(harness.METRICS_DIR / f"{name}.py",
                               "test_reader_" + name.replace(".", "_"))


def ctx(busy_s, spans=None, counters=None):
    """Spans: name -> (count, total seconds); counters: name -> value."""
    spans, counters = spans or {}, counters or {}

    def span(name):
        count, total = spans.get(name, (0, 0.0))
        return SimpleNamespace(count=count, total_s=total)
    return SimpleNamespace(
        work={"points": POINTS, "requests": REQUESTS},
        trace=None if busy_s is None else SimpleNamespace(busy_s=busy_s),
        span=span, counter=lambda name, **labels: counters.get(name, 0))


def full(busy_s):
    return ctx(busy_s,
               spans={s: (18, 0.001 * k) for k, s in
                      enumerate(SPANS.values(), start=1)},
               counters={"packed_slots": 78_643_200})


@pytest.mark.parametrize("name", READERS)
def test_reads_with_a_device(name):
    got = reader(name).read(full(busy_s=1.6))
    if name in SPANS:
        k = list(SPANS).index(name) + 1
        assert got == pytest.approx(1e3 * 0.001 * k / POINTS)
    else:
        assert got == pytest.approx(100.0 * REQUESTS / 78_643_200)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("busy_s", [None, 0.0])
def test_nothing_without_a_device_plane(name, busy_s):
    assert reader(name).read(full(busy_s)) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_where_the_program_lacks_it(name):
    assert reader(name).read(ctx(busy_s=1.6)) is None
