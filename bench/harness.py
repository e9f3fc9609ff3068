"""The benchmark's harness: one cell, one seed, one run.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
harness finds every piece by that name:

* ``bench/configs/<config>.json``: the sizes as run, and
  ``bench/configs/<config>.py``: the model built through the program,
  the weights made from the seed, and the model FLOPs;
* ``bench/traffic/<traffic>.json``: the traffic parameters, the driver
  that runs them and the limits of the correctness comparison;
* ``bench/drivers/<driver>.py``: set-up, window and reference comparison;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric; a
  metric split by the end-to-end metric it moves (``mfu.des``,
  ``mfu.analytic``) may share the reader of its stem (``mfu.py``).

The configuration states its matrix products' precision
(``matmul_precision``); the whole run computes at it.

A run sets up (timed as ``setup_s``), measures for ``--seconds``, reads
the device's peak memory, frees the program's state, and compares what
the first steps of the timed path produced with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
OUT = BENCH / "out"
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = OUT / "jax_cache"
#: the longest window a traced run measures: over a 30 s window of the
#: analytic transport's cell the profiler kept no device plane
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ----------------------------------------------------------------------------
# what BENCHMARK.json names
# ----------------------------------------------------------------------------

def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config_name: str
    config: dict              # bench/configs/<config>.json
    traffic_name: str
    traffic: dict             # bench/traffic/<traffic>.json
    e2e: List[dict]           # the end-to-end metrics this cell reports
    per_layer: List[dict]     # the per-layer metrics this cell reports

    @property
    def model(self):
        """bench/configs/<config>.py"""
        return load_module(BENCH / "configs" / f"{self.config_name}.py")

    @property
    def driver(self):
        return load_module(BENCH / "drivers" / f"{self.traffic['driver']}.py")


def load_cell(workload: str, spec: Optional[dict] = None) -> Cell:
    spec = spec or load_spec()
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = by_name[workload]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in moved)]
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=json.loads((ROOT / cfg["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        e2e=e2e, per_layer=per_layer)


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ----------------------------------------------------------------------------
# process set-up
# ----------------------------------------------------------------------------

def prepare_process() -> None:
    """The program's sources on the path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_cache() -> None:
    """JAX's persistent compilation cache in ``bench/out/jax_cache``,
    whatever the environment says, for every program of the run: the
    program's own entry points read ``JAX_COMPILATION_CACHE_DIR``, so it
    is set to the same directory."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is "
                     f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    import jax

    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count()}


def seed_key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class HostClock:
    """What the host did over a stretch of the run, to tell a slow host
    from slow work: this process's CPU seconds, the times the kernel took
    the CPU from it, and the host's steal and idle time (``/proc/stat``)."""

    def __init__(self):
        self.t0, self.cpu0 = time.perf_counter(), time.process_time()
        self.ru0, self.stat0 = self._rusage(), self._stat()

    @staticmethod
    def _rusage():
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        return ru.ru_nivcsw, ru.ru_nvcsw

    @staticmethod
    def _stat() -> List[int]:
        try:
            with open("/proc/stat") as f:
                return [int(x) for x in f.readline().split()[1:]]
        except OSError:
            return []

    def since(self) -> dict:
        wall = time.perf_counter() - self.t0
        ru, stat = self._rusage(), self._stat()
        out = {"wall_s": wall, "cpu_s": time.process_time() - self.cpu0,
               "involuntary_switches": ru[0] - self.ru0[0],
               "voluntary_switches": ru[1] - self.ru0[1]}
        if len(stat) >= 8 and len(self.stat0) >= 8:
            d = [a - b for a, b in zip(stat, self.stat0)]
            total = sum(d[:8]) or 1
            # user nice system idle iowait irq softirq steal
            out["host_idle_share"] = (d[3] + d[4]) / total
            out["host_steal_share"] = d[7] / total
        return out


class CompileCounter:
    """Counts programs lowered while active (a new jit trace and lowering:
    a compile, or a load from the persistent cache)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        self.active = False
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == self.EVENT:
            self.n += 1


# ----------------------------------------------------------------------------
# the comparison's arithmetic
# ----------------------------------------------------------------------------

def worst_leaf_gap(prog: List[float], ref: List[float],
                   keep: Optional[List[bool]] = None) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref): the gap
    between two norms of a leaf, measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    idx = [i for i in range(len(ref)) if keep is None or keep[i]]
    med = sorted(ref[i] for i in idx)[len(idx) // 2]
    gaps = [abs(prog[i] - ref[i]) / max(ref[i], med) for i in idx]
    return max(gaps) if all(math.isfinite(g) for g in gaps) else math.inf


def moving_leaves(ref_grad_norms: List[float]) -> List[bool]:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's. The others (a bias under a normalisation) move under
    the optimizer by round-off alone and are left out of the change."""
    med = sorted(ref_grad_norms)[len(ref_grad_norms) // 2]
    return [g >= 1e-3 * med for g in ref_grad_norms]


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30) if math.isfinite(a) else math.inf


def training_readings(prog: dict, ref: dict) -> Dict[str, float]:
    """The three numbers compared in a training cell. ``prog`` and ``ref``
    each hold ``losses`` (the first three steps), ``grad_norms`` (per leaf,
    the first gradient as the optimizer got it) and ``delta_norms`` (per
    leaf, the parameters' change after three steps)."""
    keep = moving_leaves(ref["grad_norms"])
    return {
        "loss": max(rel_gap(a, b) for a, b in
                    zip(prog["losses"], ref["losses"])),
        "grad": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "delta": worst_leaf_gap(prog["delta_norms"], ref["delta_norms"],
                                keep),
    }


# ----------------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class MetricInput:
    """What a per-layer metric's reader may read."""
    cell: Cell
    peaks: dict
    work: dict                # the driver's account of the window
    trace: Any                # bench.trace.Trace, or None
    chips: int


def stem(name: str) -> str:
    """A metric's name without its split: ``mfu.des`` -> ``mfu``."""
    return name.split(".", 1)[0]


def reader_path(metric: str) -> Path:
    """bench/metrics/<metric>.py, or the reader of its stem."""
    own = BENCH / "metrics" / f"{metric}.py"
    return own if own.exists() else BENCH / "metrics" / f"{stem(metric)}.py"


def read_per_layer(cell: Cell, mi: MetricInput) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        v = load_module(reader_path(m["name"])).read(mi)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             reduced: bool = False,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr)
             ) -> dict:
    """Set up, measure, compare; returns the result line as a dict.
    The CPU tests run the configuration's ``reduced`` sizes."""
    prepare_process()
    cell = load_cell(workload)
    if reduced:
        cell.config = cell.model.reduced(cell.config)
    limits = cell.traffic["limits"]
    import jax

    devs = devices(cell.chips, require_tpu)
    enable_cache()
    peaks = load_peaks(devs[0].device_kind) if require_tpu else {}
    counter = CompileCounter()

    precision = cell.config["matmul_precision"]

    log(f"set-up: {time.perf_counter() - t_start:.3f} s to JAX and the chip")
    with jax.default_matmul_precision(precision):
        run = cell.driver.prepare(cell, seed, devs, log)
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    # what set-up made is not the window's to collect: the collector's
    # passes over it would land in the window at times that vary
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")

    trace_dir = OUT / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    counter.active = True
    with jax.profiler.TraceAnnotation("bench.window"), \
            jax.default_matmul_precision(precision):
        work = run.window(seconds)
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    log(f"window: {json.dumps(work)}")
    log(f"compiles_in_window {counter.n}")
    mem = memory_peak(devs)
    run.release()

    readings = run.readings()
    checks = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())

    device = {**device_record(devs), "memory_peak_bytes": mem}
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": work["attempted"],
                              "failed": work["failed"]}
    if trace:
        from bench import trace as tr

        t = tr.load(tr.find_xplane(str(trace_dir)),
                    chips=list(range(cell.chips)))
        busy = [tr.busy_ns(t, c) for c in range(cell.chips)]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = t.window_ns * 1e-9
        mi = MetricInput(cell=cell, peaks=peaks, work=work, trace=t,
                         chips=cell.chips)
        result["metrics"] = read_per_layer(cell, mi)
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(t),
                               "idle_gaps": tr.idle_gaps(t)}
    else:
        values = {**work["e2e"], "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values.get(m["name"], values.get(
                stem(m["name"]))), "unit": m["unit"]}
            for m in cell.e2e}
        result["device"] = device
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0
