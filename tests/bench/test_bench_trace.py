"""The benchmark's trace reduction on small traces built by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace as tr  # noqa: E402
from bench.harness import BENCH, MetricInput, load_module  # noqa: E402

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur), name)


def make(ops, window=(0.0, 100.0), host=(), modules=()):
    return tr.Trace(ops={0: list(ops)}, modules={0: list(modules)},
                    host=list(host), window=window)


def test_idle_share_counts_overlapping_ops_once():
    # [10, 30] and [20, 40] overlap: busy 30; [50, 60] adds 10
    t = make([ev("a", 10, 20), ev("b", 20, 20), ev("c", 50, 10)])
    assert tr.busy_ns(t) == pytest.approx(40.0)
    assert tr.idle_share(t) == pytest.approx(0.6)


def test_ops_outside_the_window_are_clipped():
    t = make([ev("a", -10, 20), ev("b", 95, 20)], window=(0.0, 100.0))
    assert tr.busy_ns(t) == pytest.approx(15.0)


def test_an_empty_trace_is_an_error_not_zero():
    with pytest.raises(tr.NotInTrace):
        tr.busy_ns(make([]))


def test_top_ops_and_idle_gaps_are_named():
    host = [ev("PjitFunction(step)", 35, 20), ev("bench.window", 0, 100)]
    t = make([ev("fusion.1", 0, 30), ev("fusion.1", 60, 10),
              ev("copy.2", 90, 5)], host=host)
    top = tr.top_ops(t)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(40e-9)
    gaps = tr.idle_gaps(t)
    # the longest gap [30, 60] is named by the host event at its middle
    assert gaps[0] == ["PjitFunction(step)", pytest.approx(30e-9)]
    assert [g[0] for g in gaps[1:]] == ["host", "host"]


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


def _mi(trace, **work):
    return MetricInput(cell=None, peaks=PEAKS, work=work, trace=trace,
                       chips=1)


def test_readers_return_nothing_where_nothing_is_traced():
    t = make([ev("fusion.1", 0, 10)])
    assert _reader("step_device_ms").read(
        _mi(t, global_batches=3, wall_s=1.0)) is None
    assert _reader("device_idle_share").read(_mi(None)) is None
    assert _reader("mfu").read(_mi(None, samples=10, wall_s=1.0)) is None
    assert _reader("des_packets_per_s").read(_mi(None, packets=0,
                                                 wall_s=1.0)) is None


def test_step_device_ms_sums_the_runtime_modules():
    mods = [ev("jit_step(12)", 0, 2e6), ev("jit_step(12)", 5e6, 2e6),
            ev("jit_other", 9e6, 1e6)]
    t = make([ev("fusion", 0, 1)], window=(0.0, 1e7), modules=mods)
    got = _reader("step_device_ms").read(_mi(t, global_batches=2))
    assert got == pytest.approx(2.0)


def test_idle_share_reader_is_a_percentage():
    t = make([ev("a", 0, 25)])
    assert _reader("device_idle_share").read(_mi(t)) == pytest.approx(75.0)


def test_mfu_is_model_flops_over_the_peak():
    # 1e9 FLOPs a sample at 19,700 samples/s is a tenth of 197e12
    got = _reader("mfu").read(_mi(None, flops_per_sample=1e9,
                                  samples=39_400, wall_s=2.0))
    assert got == pytest.approx(10.0)
