"""The program's host spans in a trace, on small traces built by hand:
interval arithmetic, self time, idle overlap, and the four readers."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spans as sp  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.harness import BENCH, MetricInput, load_module  # noqa: E402


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur), name)


def make(host, ops=(), window=(0.0, 100.0)):
    return tr.Trace(ops={0: list(ops)}, modules={0: []},
                    host=[ev("bench.window", *window)] + list(host),
                    window=window)


def mi(trace, batches=2):
    return MetricInput(cell=None, peaks={}, work={"global_batches": batches},
                       trace=trace, chips=1)


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 4), (6, 8)], [(0, 2), (4, 6), (8, 10)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [(-5, 15)], []),
    ([(10, 20)], [(0, 5), (25, 30)], [(10, 20)]),
])
def test_subtract(a, b, want):
    assert sp.subtract(a, b) == want


def test_overlap_of_interval_lists():
    assert sp.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert sp.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert sp.overlap_ns([], [(0, 5)]) == 0


def test_a_span_is_clipped_to_the_window_and_matched_before_its_hash():
    t = make([ev("ltp.masks#iteration=0#", -10, 20), ev("ltp.masks", 95, 10),
              ev("ltp.masksx", 40, 10)])
    assert sp.intervals(t, ("ltp.masks",)) == [(0.0, 10.0), (95.0, 100.0)]


def test_self_time_removes_nested_and_overlapping_children_once():
    # children [10, 30] and [20, 40] overlap: 30 removed, not 40; the
    # JAX event inside a child is no program span and changes nothing
    t = make([ev("ltp.sim.run", 0, 90), ev("ltp.step.inputs", 10, 20),
              ev("ltp.step.dispatch", 20, 20), ev("PjitFunction(step)", 25, 5),
              ev("ltp.masks", 60, 10)])
    own = sp.self_intervals(t, "ltp.sim.run")
    assert own == [(0.0, 10.0), (40.0, 60.0), (70.0, 90.0)]
    assert sp.length_ns(own) == 50


def test_idle_intervals_are_the_window_minus_the_ops():
    t = make([], ops=[ev("a", 10, 20), ev("b", 20, 20), ev("c", 90, 20)])
    assert sp.idle_intervals(t) == [(0.0, 10.0), (40.0, 90.0)]


def test_a_missing_span_gives_none():
    t = make([ev("PjitFunction(step)", 10, 20)], ops=[ev("a", 0, 10)])
    assert sp.intervals(t, ("ltp.masks",)) is None
    assert sp.self_intervals(t, "ltp.sim.run") is None
    for name in ("des_self_ms", "idle_in_des_share", "mask_host_ms",
                 "step_host_ms"):
        assert reader(name).read(mi(t)) is None
        assert reader(name).read(mi(None)) is None


def _window():
    """A 100 ns window of 2 global batches: the loop runs [5, 95]; masks
    [10, 20] and [50, 60]; the step's inputs [20, 25] and [60, 65], its
    dispatch [25, 30] and [65, 70]; the chip busy [25, 50] and [65, 90]."""
    host = [ev("ltp.sim.run", 5, 90),
            ev("ltp.masks", 10, 10), ev("ltp.masks", 50, 10),
            ev("ltp.step.inputs", 20, 5), ev("ltp.step.inputs", 60, 5),
            ev("ltp.step.dispatch", 25, 5), ev("ltp.step.dispatch", 65, 5),
            ev("PjitFunction(step)", 26, 3)]
    ops = [ev("fusion", 25, 25), ev("fusion", 65, 25)]
    return make(host, ops)


def test_the_readers_split_the_loop():
    t = _window()
    # self: [5, 10] + [30, 50] + [70, 95] = 50 ns over 2 batches
    assert reader("des_self_ms").read(mi(t)) == pytest.approx(25e-6)
    assert reader("mask_host_ms").read(mi(t)) == pytest.approx(10e-6)
    assert reader("step_host_ms").read(mi(t)) == pytest.approx(10e-6)
    # idle [0, 25], [50, 65], [90, 100] against self: [5, 10], [90, 95]
    assert reader("idle_in_des_share").read(mi(t)) == pytest.approx(10.0)


def test_the_split_adds_up_to_the_loop_and_idle_share_bounds_it():
    t = _window()
    total = sum(reader(n).read(mi(t)) for n in
                ("des_self_ms", "mask_host_ms", "step_host_ms"))
    assert total * 1e6 * 2 == pytest.approx(90.0)
    idle = 100.0 * tr.idle_share(t)
    assert reader("idle_in_des_share").read(mi(t)) <= idle
