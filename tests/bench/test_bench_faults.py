"""The correctness comparison catches a broken timed path.

Each fault is planted in the program underneath a whole run of a cell
(past the harness's look for a chip, at the configuration's reduced size
on the CPU), and the run must come out not correct: a step that returns
its state unchanged; half of each worker's batch left out, the mean
taken over the rest; the exchange between workers left out (the PS
reduces worker 0's gradient alone).
"""
import collections
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CELLS = [w["name"] for w in harness.load_spec()["workloads"]
         if harness.load_cell(w["name"]).traffic["driver"] == "cosim"]


def _isolate(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    harness.prepare_process()
    from repro.runtime import step as stp

    # the runtime memoizes its jitted programs: build them anew
    monkeypatch.setattr(stp, "_JIT_CACHE", collections.OrderedDict())
    return stp


def plant_unchanged(monkeypatch, stp):
    fused = stp.build_fused_step

    def build_fused(*a, **k):
        step = fused(*a, **k)

        def unchanged(params, opt_state, residual, *rest):
            out = step(params, opt_state, residual, *rest)
            return (params, opt_state, residual) + tuple(out[3:])
        return unchanged

    monkeypatch.setattr(stp, "build_fused_step", build_fused)


def plant_half_batch(monkeypatch, stp):
    import jax

    fused = stp.build_fused_step

    def build_fused(*a, **k):
        step = fused(*a, **k)

        def half(params, opt_state, residual, batch, *rest):
            batch = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batch)
            return step(params, opt_state, residual, batch, *rest)
        return half

    monkeypatch.setattr(stp, "build_fused_step", build_fused)


def plant_no_exchange(monkeypatch, stp):
    import jax.numpy as jnp
    from repro.core import ltp_sync as ls

    reduce = ls.reduce_packet_stream

    def worker0_only(packets_w, masks_w, *a, **k):
        keep = (jnp.arange(packets_w.shape[0]) == 0).astype(packets_w.dtype)
        return reduce(packets_w * keep[:, None, None], masks_w, *a, **k)

    monkeypatch.setattr(ls, "reduce_packet_stream", worker0_only)


FAULTS = {"unchanged": plant_unchanged, "half_batch": plant_half_batch,
          "no_exchange": plant_no_exchange}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    stp = _isolate(monkeypatch)
    FAULTS[fault](monkeypatch, stp)
    r = harness.run_cell(workload, 2 ** 31 + 4321, 0.2, False, t_start=0.0,
                         require_tpu=False, reduced=True, log=lambda s: None)
    assert r["correct"] is False, r["checks"]
