"""Kernel microbench — the PS-side hot loop as Pallas tiles (DESIGN.md §7).

Times ``kernels.dropfill`` (bubble-fill + compensation gate) and
``kernels.packet_reduce`` (fused masked multi-worker reduction) through
the ``ops.py`` padding wrappers, plus the end-to-end sync step
(``core.ltp_sync.reduce_packet_stream``) under the python, pallas, AND
auto backends at two stream sizes.

The auto contract (DESIGN.md §9) is asserted in-run: at BOTH bench
sizes ``sync_backend="auto"`` must land within ``AUTO_TOLERANCE`` (1.1x)
of the better of python/pallas — the kernel path is never a regression.
The record also carries ``sync_crossover_elems``, the stream size at
which auto switches to pallas (0 when pallas never wins at the probed
sizes — the interpret-mode/CPU situation).

On CPU the kernels run in interpret mode, so the GB/s figures are the
*interpreter's* — a stable regression baseline for CI, not hardware
numbers; on a TPU the same calls compile the kernels.

Writes ``BENCH_kernels.json`` at the repo root (consumed by
``benchmarks.check_regression``) and the usual rows under results/.

  PYTHONPATH=src python -m benchmarks.run --only kernel_bench
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import LTPConfig
from repro.core import ltp_sync as ls
from repro.core.ltp_sync import reduce_packet_stream
from repro.kernels import ops

from benchmarks.common import emit
from benchmarks.sweep_scenarios import write_bench

#: auto may cost at most this factor over min(python, pallas) per size
AUTO_TOLERANCE = 1.1


def _time(fn, *args, reps: int = 3, **kw) -> float:
    """Best-of-reps wall seconds, after one compile/warmup call."""
    jax.block_until_ready(fn(*args, **kw))
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(fn(*args, **kw))
        best = min(best, time.time() - t0)
    return best


def run(quick: bool = True):
    rng = np.random.default_rng(0)
    w = 8
    n = 1024 if quick else 8192
    n_small = max(64, n // 4)     # second size: the auto gate needs two
    p = 360                       # non-lane-aligned: exercises ops padding
    pkts_w = jnp.asarray(rng.normal(size=(w, n, p)).astype(np.float32))
    masks_w = jnp.asarray((rng.random((w, n)) < 0.8).astype(np.float32))
    pkts = pkts_w[0]
    mask = masks_w[0]
    scale = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))

    rows = []
    metrics = {}

    t = _time(ops.ltp_dropfill, pkts, mask, scale)
    gb = 2 * n * p * 4 / 1e9      # one read + one write of the stream
    rows.append({"kernel": "dropfill", "shape": f"({n},{p})",
                 "wall_s": round(t, 4), "gbps": round(gb / t, 3)})
    metrics["dropfill_wall_s"] = round(t, 4)
    metrics["dropfill_gbps"] = round(gb / t, 3)

    t = _time(ops.ltp_packet_reduce, pkts_w, masks_w)
    gb = (w + 1) * n * p * 4 / 1e9    # W reads + one write per output tile
    rows.append({"kernel": "packet_reduce", "shape": f"({w},{n},{p})",
                 "wall_s": round(t, 4), "gbps": round(gb / t, 3)})
    metrics["packet_reduce_wall_s"] = round(t, 4)
    metrics["packet_reduce_gbps"] = round(gb / t, 3)

    ltp = LTPConfig(compensation="count")
    crossover = 0
    # small size first: the recorded crossover must be the SMALLEST
    # probed stream size where pallas wins, not whichever won first
    for size_tag, nn in (("_small", n_small), ("", n)):
        pw, mw = pkts_w[:, :nn], masks_w[:, :nn]
        fns = {}
        for backend in ("python", "pallas", "auto"):
            fn = jax.jit(lambda a, b, be=backend: reduce_packet_stream(
                a, b, ltp, w, backend=be))
            jax.block_until_ready(fn(pw, mw))       # compile/warm
            fns[backend] = fn
        # interleaved best-of-reps: a noisy-neighbor slowdown on a
        # shared runner hits every backend's samples alike, so the
        # auto-vs-best comparison below measures dispatch, not load.
        # The 1.1x contract is re-measured up to 3 times before failing:
        # CPU-frequency jitter can make two runs of the IDENTICAL
        # computation differ >10%, while a genuinely wrong auto dispatch
        # (the pallas interpreter, ~5-10x here) fails every attempt.
        for attempt in range(3):
            walls = {b: float("inf") for b in fns}
            for _ in range(5):
                for backend, fn in fns.items():
                    t0 = time.time()
                    jax.block_until_ready(fn(pw, mw))
                    walls[backend] = min(walls[backend], time.time() - t0)
            best = min(walls["python"], walls["pallas"])
            if walls["auto"] <= best * AUTO_TOLERANCE + 2e-3:
                break
        assert walls["auto"] <= best * AUTO_TOLERANCE + 2e-3, (
            f"sync_backend='auto' regressed at n={nn}: "
            f"{walls['auto']:.4f}s vs best backend {best:.4f}s "
            f"(budget {AUTO_TOLERANCE}x + 2ms, 3 attempts) — "
            f"auto must never lose")
        for backend, t in walls.items():
            rows.append({"kernel": f"sync_{backend}{size_tag}",
                         "shape": f"({w},{nn},{p})", "wall_s": round(t, 4)})
            metrics[f"sync_{backend}{size_tag}_wall_s"] = round(t, 4)
        if walls["pallas"] < walls["python"] and crossover == 0:
            crossover = w * nn * p
    # 0 = pallas never won at the probed sizes (interpret mode / CPU);
    # on a compiled-kernel backend this records the measured switch point
    # that calibrates ltp_sync.AUTO_CROSSOVER_ELEMS
    metrics["sync_crossover_elems"] = crossover
    metrics["sync_auto_resolves_interpret"] = (
        1 if ls.resolve_backend("auto", w * n * p, True) == "python" else 0)

    write_bench(metrics, quick, "BENCH_kernels.json")
    emit(rows, "kernel_bench")
    return rows


if __name__ == "__main__":
    run(quick=True)
