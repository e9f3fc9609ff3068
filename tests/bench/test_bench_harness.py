"""The benchmark harness: BENCHMARK.json's names resolve to files, the
command refuses to run without a TPU, and each driver's window runs on
the CPU at its configuration's reduced size and prints the contract's
keys."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def test_benchmark_json_shape():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_loads_by_name(workload):
    cell = harness.load_cell(workload)
    assert cell.driver.prepare and cell.model.build
    assert set(cell.traffic["limits"]) == {"loss", "grad", "delta"}
    reported = {m["name"] for m in cell.e2e}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_metric_has_a_reader(metric):
    reader = harness.load_module(harness.reader_path(metric))
    assert callable(reader.read)


def test_a_split_metric_finds_the_reader_of_its_stem():
    metrics = harness.BENCH / "metrics"
    assert harness.reader_path("mfu.analytic") == metrics / "mfu.py"
    assert harness.reader_path("des_packets_per_s") == \
        metrics / "des_packets_per_s.py"


def test_every_cell_states_its_precision_and_window():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["matmul_precision"] == "highest"
        assert cell.traffic["iterations_per_s"] > 0


def test_the_host_clock_counts_cpu_time():
    clock = harness.HostClock()
    sum(i * i for i in range(200_000))
    got = clock.since()
    assert got["cpu_s"] > 0 and got["wall_s"] >= got["cpu_s"] * 0.5
    assert got["involuntary_switches"] >= 0


def _run_command(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3000000001", "--seconds",
         "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") for line in stdout.splitlines())


def test_command_refuses_the_cpu():
    p = _run_command(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout), p.stderr[-2000:]


def test_command_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run_command(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout), p.stderr[-2000:]


def _cpu_run(workload, monkeypatch, seconds=1.0):
    """One run of the cell at its reduced size on the CPU, past the
    harness's look for a chip, without the persistent cache."""
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    return harness.run_cell(workload, 2 ** 31 + 1234, seconds, False,
                            t_start=0.0, require_tpu=False, reduced=True,
                            log=lambda s: None)


@pytest.mark.parametrize(
    "workload", [w["name"] for w in SPEC["workloads"]
                 if harness.load_cell(w["name"]).traffic["driver"] == "cosim"])
def test_cosim_window_runs_on_the_cpu(workload, monkeypatch):
    r = _cpu_run(workload, monkeypatch)
    assert list(r) == RESULT_KEYS
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    cell = harness.load_cell(workload)
    assert set(r["metrics"]) == {m["name"] for m in cell.e2e}
    rate = [v["value"] for k, v in r["metrics"].items()
            if harness.stem(k) == "samples_per_s"]
    assert len(rate) == 1 and rate[0] > 0
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
