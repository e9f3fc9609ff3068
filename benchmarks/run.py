"""Benchmark harness — one module per paper table/figure + the roofline.

  python -m benchmarks.run            # quick mode (CI-sized)
  python -m benchmarks.run --full     # paper-sized sweeps
  python -m benchmarks.run --only fig4_loss_tolerance

Output: CSV-ish lines `<figure>,<k>=<v>,...` on stdout and JSON blobs in
benchmarks/results/.
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (
    fault_sweep,
    fig3_incast_fct,
    fig4_loss_tolerance,
    fig5_randomk_topk,
    fig12_throughput,
    fig13_tta,
    fig15_fairness,
    kernel_bench,
    roofline,
    runtime_sweep,
    sweep_scenarios,
)

MODULES = {
    "fig3_14_incast_fct_bst": fig3_incast_fct,
    "fig4_loss_tolerance": fig4_loss_tolerance,
    "fig5_randomk_topk": fig5_randomk_topk,
    "fig12_throughput": fig12_throughput,
    "fig13_tta": fig13_tta,
    "fig15_fairness": fig15_fairness,
    "roofline": roofline,
    "scenario_sweep": sweep_scenarios,
    "kernel_bench": kernel_bench,
    "runtime_sweep": runtime_sweep,
    "fault_sweep": fault_sweep,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    names = [args.only] if args.only else list(MODULES)
    for name in names:
        t0 = time.time()
        print(f"### {name} (quick={not args.full})", flush=True)
        MODULES[name].run(quick=not args.full)
        print(f"### {name} done in {time.time()-t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
