"""The readings that the correctness limits are set from.

For each seed, one set-up of the cell (no measured window) and the
comparison of its checked steps with the plain reference; on the first
``--faults`` seeds also the control (the reference one precision lower,
in the program's place) and the faults planted in the reference. All
seeds run in one process, so the programs compile once.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--faults 3] [--precision high] [--out bench/out/calibrate.jsonl]

``--precision`` runs the program at another matrix precision than the
configuration states: the program's own path one precision lower, read
as a second witness to the control.

Each line of the output file is one seed: {"seed", "prog": {number:
reading}, "control": {...}, ...}.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parent.parent)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds (the first ones) on which to read the "
                         "control and the faults")
    ap.add_argument("--precision", default=None,
                    help="the program's matrix precision, if not the "
                         "configuration's")
    ap.add_argument("--out", default=str(harness.OUT / "calibrate.jsonl"))
    args = ap.parse_args(argv)

    harness.prepare_process()
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.devices(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    import jax

    driver = cell.driver
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as f:
        for i, seed in enumerate(args.seeds):
            t0 = time.perf_counter()
            with jax.default_matmul_precision(
                    args.precision or cell.config["matmul_precision"]):
                run = driver.prepare(cell, seed, devs, lambda s: None)
            run.release()
            variants = (("prog",) + driver.FAULTS) if i < args.faults \
                else ("prog",)
            line = {"workload": args.workload, "seed": seed,
                    "precision": args.precision
                    or cell.config["matmul_precision"],
                    **run.compare(variants),
                    "seconds": time.perf_counter() - t0}
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(json.dumps(line), flush=True)
            del run
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
