import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination with ShapeDtypeStruct stand-ins — no allocation — and
extract the roofline inputs from the compiled artifact.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
                                               [--ltp]   # LTP-sync train step

Outputs one JSON per combination under benchmarks/dryrun_results/.
"""  # noqa: E402

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import LTPConfig
from repro.configs import ARCH_IDS, get_config
from repro.launch import hlo_analysis
from repro.launch.mesh import (
    HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh,
)
from repro.models import build
from repro.models.api import input_specs, shape_supported
from repro.models.sharding import ShardCtx, dp_axes, param_specs, spec_for
from repro.optim import sgd_momentum
from repro.shapes import SHAPES, get_shape
from repro.train.trainer import TrainState, make_ltp_train_step, make_plain_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../..", "benchmarks",
                           "dryrun_results")


# ----------------------------------------------------------------------------
# Sharding of inputs
# ----------------------------------------------------------------------------


def _fits(n: int, k: int) -> bool:
    return k > 1 and n % k == 0


def batch_spec(name: str, sds, shape, mesh, *, dp) -> P:
    """PartitionSpec for one input leaf by name/shape convention."""
    dims = sds.shape
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    dpspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    nm = mesh.shape["model"] if "model" in mesh.axis_names else 1
    if name == "positions3":
        return P(None, dpspec if _fits(dims[1], ndp) else None, None)
    spec = [None] * len(dims)
    if dims and _fits(dims[0], ndp):
        spec[0] = dpspec
    if name in ("patch_embeds", "frames") and _fits(dims[-1], nm):
        spec[-1] = "model"
    return P(*spec)


def cache_spec(sds, global_batch: int, mesh, *, dp) -> P:
    """Heuristic cache sharding: batch dim over dp, largest remaining
    model-divisible dim over 'model'."""
    dims = sds.shape
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    dpspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    nm = mesh.shape["model"] if "model" in mesh.axis_names else 1
    spec: list = [None] * len(dims)
    for i, d in enumerate(dims):
        if d == global_batch and _fits(d, ndp):
            spec[i] = dpspec
            break
    best = -1
    for i, d in enumerate(dims):
        if spec[i] is None and _fits(d, nm):
            if best < 0 or d > dims[best]:
                best = i
    if best >= 0:
        spec[best] = "model"
    return P(*spec)


def input_shardings(cfg, shape, mesh) -> Any:
    dp = dp_axes(mesh)
    specs = input_specs(cfg, shape)

    def assign(path, sds):
        name = ""
        for p in path:
            if isinstance(p, jax.tree_util.DictKey):
                name = str(p.key)
                break   # top-level name decides ('cache' subtree handled below)
        if name == "cache":
            return cache_spec(sds, shape.global_batch, mesh, dp=dp)
        if name == "pos":
            return P()
        return batch_spec(name, sds, shape, mesh, dp=dp)

    return specs, jax.tree_util.tree_map_with_path(assign, specs)


# ----------------------------------------------------------------------------
# Step builders
# ----------------------------------------------------------------------------


def build_train(cfg, shape, mesh, *, ltp: bool, zero: bool = False):
    if ltp:
        # XLA:CPU's AllReducePromotion pass CHECK-fails on the bf16
        # all-reduces the partitioner emits inside manual shard_map
        # regions (CloneAllReduce/"copy"). The LTP variant therefore
        # lowers with f32 activations on this backend — matmul partial
        # sums are f32 on real TPUs anyway; byte terms reported by the
        # dry-run are f32-inflated on this backend accordingly.
        cfg = cfg.replace(dtype="float32")
    api = build(cfg)
    opt = sgd_momentum()
    key = jax.random.PRNGKey(0)
    state_sds = jax.eval_shape(
        lambda: TrainState(
            params=(p := api.init(key)),
            opt_state=opt.init(p),
            step=jnp.zeros((), jnp.int32),
        )
    )
    fsdp = not ltp   # LTP workers hold replicated weights (PS semantics)
    state_specs = jax.tree_util.tree_map_with_path(
        lambda path, x: spec_for(path, x.shape, mesh, fsdp=fsdp), state_sds
    )
    in_sds, in_specs = input_shardings(cfg, shape, mesh)
    lr_sds = jax.ShapeDtypeStruct((), jnp.float32)

    if not ltp:
        step = make_plain_train_step(api, opt, mesh)
        args = (state_sds, in_sds, lr_sds)
        shardings = (state_specs, in_specs, P())
        fn = step
    else:
        # every data-parallel rank is one of the paper's workers; on the
        # multi-pod mesh that covers the cross-pod DCN link (XLA:CPU's
        # partitioner CHECK-fails on a pod-only manual submesh, so the
        # worker set is (pod, data) rather than pod alone)
        worker = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        nw = 1
        for a in worker:
            nw *= mesh.shape[a]
        ltp_cfg = LTPConfig()
        if zero:
            # ZeRO-style packet-space momentum, sharded over the workers
            from repro.core.ltp_sync import zero_momentum_shapes
            m_sds = zero_momentum_shapes(state_sds.params, ltp_cfg, nw)
            wspec = worker if len(worker) > 1 else worker[0]
            state_sds = TrainState(
                params=state_sds.params,
                opt_state={"m_pkts": m_sds},
                step=state_sds.step,
            )
            state_specs = TrainState(
                params=state_specs.params,
                opt_state={"m_pkts": [P(wspec, None)] * len(m_sds)},
                step=P(),
            )
        step = make_ltp_train_step(
            api, opt, mesh, ltp_cfg, worker, in_specs
        )
        frac_sds = jax.ShapeDtypeStruct((nw,), jnp.float32)
        key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        args = (state_sds, in_sds, frac_sds, key_sds, lr_sds)
        shardings = (state_specs, in_specs, P(), P(), P())
        fn = step
    return fn, args, shardings


def build_prefill(cfg, shape, mesh):
    api = build(cfg)
    ctx = ShardCtx(mesh)
    in_sds, in_specs = input_shardings(cfg, shape, mesh)
    params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    p_specs = param_specs(params_sds, mesh)

    def fn(params, inputs):
        return api.prefill(params, inputs, ctx=ctx)

    return fn, (params_sds, in_sds), (p_specs, in_specs)


def build_decode(cfg, shape, mesh):
    api = build(cfg)
    ctx = ShardCtx(mesh)
    in_sds, in_specs = input_shardings(cfg, shape, mesh)
    params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    p_specs = param_specs(params_sds, mesh)

    def fn(params, cache, token, pos):
        return api.decode_step(params, cache, token, pos, ctx=ctx)

    args = (params_sds, in_sds["cache"], in_sds["token"], in_sds["pos"])
    shardings = (p_specs, in_specs["cache"], in_specs["token"], in_specs["pos"])
    return fn, args, shardings


# ----------------------------------------------------------------------------
# Runner
# ----------------------------------------------------------------------------


def to_named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def run_one(arch: str, shape_name: str, *, multi_pod: bool, ltp: bool = False,
            zero: bool = False, save: bool = True) -> Dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "step": {"train": "train_step", "prefill": "prefill",
                 "decode": "serve_step"}[shape.kind],
        "ltp": ltp, "zero": zero, "ok": False,
    }
    sup, why = shape_supported(cfg, shape)
    if not sup:
        rec["skipped"] = why
        rec["ok"] = True
        _save(rec, save)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        if shape.kind == "train":
            fn, args, specs = build_train(cfg, shape, mesh, ltp=ltp, zero=zero)
        elif shape.kind == "prefill":
            fn, args, specs = build_prefill(cfg, shape, mesh)
        else:
            fn, args, specs = build_decode(cfg, shape, mesh)
        shardings = to_named(mesh, specs)
        t0 = time.time()
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)

        mem = compiled.memory_analysis()
        if mem is not None:
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(mem, f, None)
                if v is not None:
                    rec.setdefault("memory", {})[f] = int(v)
        ca = compiled.cost_analysis() or {}
        rec["xla_cost"] = {k: float(ca[k]) for k in ("flops", "bytes accessed")
                          if k in ca}
        t0 = time.time()
        cost = hlo_analysis.analyze(compiled.as_text())
        rec["analyze_s"] = round(time.time() - t0, 1)
        rec["walker"] = {
            "flops": cost.flops,
            "bytes": cost.bytes,
            "collective_bytes": cost.collective_bytes,
            "by_collective": cost.by_collective,
        }
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _save(rec, save)
    return rec


def _save(rec: Dict, save: bool):
    if not save:
        return
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = "_ltpzero" if rec.get("zero") else ("_ltp" if rec.get("ltp") else "")
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{tag}.json"
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def roofline_terms(rec: Dict, n_chips: int) -> Dict[str, float]:
    """Three roofline terms in seconds (per-device walker numbers)."""
    w = rec.get("walker", {})
    return {
        "compute_s": w.get("flops", 0) / PEAK_FLOPS_BF16,
        "memory_s": w.get("bytes", 0) / HBM_BW,
        "collective_s": w.get("collective_bytes", 0) / ICI_BW,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--ltp", action="store_true",
                    help="lower the LTP-sync train step instead of plain")
    ap.add_argument("--ltp-zero", action="store_true",
                    help="LTP with packet-space reduce-scatter + sharded "
                         "momentum (beyond-paper, see EXPERIMENTS §Perf)")
    args = ap.parse_args(argv)

    archs = [a for a in ARCH_IDS if a != "papernet"] if args.arch is None \
        else [args.arch]
    shapes = list(SHAPES) if args.shape is None else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]
    if not args.all and args.arch is None and args.shape is None:
        ap.error("pass --all or --arch/--shape")

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                t0 = time.time()
                rec = run_one(arch, shape, multi_pod=mp,
                              ltp=args.ltp or args.ltp_zero, zero=args.ltp_zero)
                status = "SKIP" if "skipped" in rec else (
                    "OK" if rec["ok"] else "FAIL")
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
                mem = rec.get("memory", {}).get("temp_size_in_bytes", 0) / 2**30
                print(f"[{status:4s}] {arch:18s} {shape:12s} "
                      f"{rec['mesh']:8s}{' ltp' if args.ltp else ''} "
                      f"temp={mem:6.2f}GiB wall={time.time()-t0:5.1f}s "
                      f"{rec.get('error','')}", flush=True)
    print(f"done: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
