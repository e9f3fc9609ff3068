"""The correctness comparison's control fails it: the plain reference one
precision lower (its products in three bfloat16 passes, as JAX's "high"
computes them, where the configuration states "highest") put in the
program's place reads above one of the cell's limits, while the program
reads below all of them. On the CPU at the configuration's own depth and
widths, with 32 images a worker in place of 128: the control's gaps
grow with depth, and at the reduced size of the other tests they stay
under the limits set on the chip."""
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


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_fails_the_limits(workload, monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    harness.prepare_process()
    from repro.runtime import step as stp

    monkeypatch.setattr(stp, "_JIT_CACHE", collections.OrderedDict())
    cell = harness.load_cell(workload)
    cell.traffic = {**cell.traffic, "per_worker_batch": 32}
    import jax

    run = cell.driver.prepare(cell, 2 ** 31 + 5678, jax.devices()[:1],
                              lambda s: None)
    run.release()
    got = run.compare(("prog", "control"))
    limits = cell.traffic["limits"]
    assert all(got["prog"][k] <= limits[k] for k in limits), got["prog"]
    assert any(got["control"][k] > limits[k] for k in limits), got["control"]
