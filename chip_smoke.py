"""Chip smoke: the LTP training path, driven once on a TPU.

Default run (one chip): the paper's job through its public entry point.
``PSTrainer`` trains papernet at its published width (``CONFIG``) on 8
workers and 1 PS, with LTP over the packet-level DES transport and a
batch of 128: a few steps under the bsp policy and a few under async.
Each policy runs twice from the same seed, once with the compiled Pallas
reduction (``sync_backend="pallas"``) and once with the jnp reference
(``"python"``). The DES is deterministic, so both runs see the same
delivery masks, and their parameters must agree.

``--chips 4`` runs only the sharded phase: on a (data=4, model=1) mesh,
``make_ltp_train_step`` (Pallas gate, every packet delivered) and
``make_plain_train_step`` (exact all-reduce) train smollm_360m at full
width in float32 from the same init on the same batches, and their
losses must agree.

The step times printed are smoke times, not benchmark metrics. The last
line of standard output is one JSON object naming the device; it is
printed only when every check passed.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded phase on four chips
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.config import LTPConfig, NetConfig, TrainConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data import SyntheticCIFAR, batches  # noqa: E402
from repro.kernels import common  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build  # noqa: E402
from repro.models.api import demo_inputs  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.runtime import step as stp  # noqa: E402
from repro.shapes import InputShape  # noqa: E402
from repro.train import PSTrainer  # noqa: E402
from repro.train.trainer import (  # noqa: E402
    init_state, make_ltp_train_step, make_plain_train_step,
)

#: pallas vs python params: max |difference| over max |param|
PARAM_RTOL = 1e-5
#: LTP (every packet delivered) vs plain all-reduce losses, relative
LOSS_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def _max_abs(tree) -> float:
    return max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(tree))


def _max_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _train(api, opt, tc, policy: str, backend: str, steps: int,
           workers: int, seed: int):
    ltp = LTPConfig(sync_backend=backend)
    net = NetConfig(bandwidth_gbps=10, rtprop_ms=1, loss_rate=0.001,
                    queue_pkts=4096)
    tr = PSTrainer(api, opt, tc, ltp, net, n_workers=workers,
                   protocol="ltp", transport="des", policy=policy,
                   compute_time=0.05, seed=seed)
    t0 = time.perf_counter()
    hist = tr.run(batches(SyntheticCIFAR(seed=seed), tc.batch, steps))
    jax.block_until_ready(tr.params)
    return tr, hist, time.perf_counter() - t0


def _runs_compiled_kernel(tr, policy: str, batch: int) -> bool:
    """Whether the PS reduction ``tr`` ran lowers to a compiled Pallas
    kernel (a ``tpu_custom_call``) rather than the interpreter or jnp.
    The step factories are memoized, so this lowers the very function
    the run used."""
    w, plan = tr.w, tr.plan

    def spec(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    masks = f32(w, plan.n_packets)
    if policy == "bsp":
        fn = stp.build_fused_step(tr.api, tr.opt, tr.ltp, plan, w, "ltp")
        shard = {"images": f32(w, batch // w, 32, 32, 3),
                 "labels": jax.ShapeDtypeStruct((w, batch // w), jnp.int32)}
        args = (spec(tr.params), spec(tr.opt_state), None, shard, masks,
                f32(w), f32())
    else:
        fn = stp.build_apply_fn(tr.api, tr.opt, tr.ltp, plan, w)
        args = (spec(tr.params), spec(tr.opt_state),
                f32(w, plan.n_packets, plan.packet_floats), masks, f32(w),
                f32(), f32())
    return "tpu_custom_call" in fn.lower(*args).as_text()


def papernet_phase(cfg, *, steps: int = 4, workers: int = 8,
                   batch: int = 128, seed: int = 0) -> dict:
    """bsp and async, each with the pallas and the python reduction.

    Per run it prints the per-step losses; per policy, the wall time of
    a first, compiling one-step run, of the ``steps``-step run, and the
    max parameter difference between the backends. Raises
    ``SmokeFailure`` on a non-finite loss, a difference above
    ``PARAM_RTOL`` of the largest parameter, or a pallas run whose
    reduction is not a compiled kernel on a TPU (or is one elsewhere).
    """
    api = build(cfg)
    tc = TrainConfig(batch=batch, lr=0.05, steps=steps)
    opt = make_optimizer(tc)
    out = {}
    for policy in ("bsp", "async"):
        params = {}
        for backend in ("pallas", "python"):
            # the one-step run compiles; the jit caches keyed on
            # (api, opt, config) hand its programs to the timed run
            _, _, t_first = _train(api, opt, tc, policy, backend, 1,
                                   workers, seed)
            tr, hist, t_run = _train(api, opt, tc, policy, backend, steps,
                                     workers, seed)
            losses = [float(h["loss"]) for h in hist]
            print(f"papernet {policy}/{backend}: losses "
                  f"{[round(x, 6) for x in losses]}")
            print(f"papernet {policy}/{backend}: first step (compiles) "
                  f"{t_first:.3f} s; {steps} steps {t_run:.3f} s "
                  f"(smoke times, not metrics)")
            if not losses or not all(math.isfinite(x) for x in losses):
                raise SmokeFailure(f"{policy}/{backend}: loss not finite: "
                                   f"{losses}")
            params[backend] = tr.params
            if backend == "pallas":
                compiled = _runs_compiled_kernel(tr, policy, batch)
                print(f"papernet {policy}/pallas: reduction is a compiled "
                      f"kernel: {compiled}")
                if compiled == common.interpret_mode():
                    raise SmokeFailure(
                        f"{policy}/pallas: compiled kernel {compiled} on "
                        f"{jax.default_backend()}")
        diff = _max_diff(params["pallas"], params["python"])
        scale = _max_abs(params["python"])
        print(f"papernet {policy}: max |pallas - python| = {diff:.3e} "
              f"(max |param| {scale:.3e}, limit {PARAM_RTOL * scale:.3e})")
        if not diff <= PARAM_RTOL * scale:
            raise SmokeFailure(f"{policy}: pallas and python params differ "
                               f"by {diff:.3e} > {PARAM_RTOL * scale:.3e}")
        out[policy] = {"max_param_diff": diff, "max_param": scale,
                       "compiled_kernel": compiled}
    return out


def sharded_phase(cfg, devices, *, steps: int = 3, batch: int = 8,
                  seq: int = 256, seed: int = 0) -> dict:
    """LTP (Pallas gate, frac=1) vs plain all-reduce on a (data=len(
    devices), model=1) mesh. Both train ``steps`` steps from one init on
    the same batches; raises ``SmokeFailure`` when their losses differ by
    more than ``LOSS_RTOL`` relative, or when the gate is not a compiled
    kernel on a TPU (or is one elsewhere)."""
    n = len(devices)
    mesh = make_mesh((n, 1), ("data", "model"), devices=devices)
    api = build(cfg)
    tc = TrainConfig(batch=batch, seq=seq, lr=3e-4, optimizer="adamw")
    opt = make_optimizer(tc)
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    batch_specs = {"tokens": P("data"), "labels": P("data")}
    lr = jnp.float32(tc.lr)
    frac = jax.device_put(jnp.ones((n,)), rep)
    batches_ = [
        jax.device_put(demo_inputs(cfg, InputShape("smoke", seq, batch,
                                                   "train"),
                                   jax.random.PRNGKey(seed + 1 + s)), data)
        for s in range(steps)]
    ltp = LTPConfig(sync_backend="pallas")
    steps_fn = {
        "plain": jax.jit(make_plain_train_step(api, opt, mesh),
                         donate_argnums=0),
        "ltp": jax.jit(make_ltp_train_step(api, opt, mesh, ltp, ("data",),
                                           batch_specs),
                       donate_argnums=0),
    }
    losses = {}
    with jax.set_mesh(mesh):
        for name, fn in steps_fn.items():
            # one run at a time: two live train states would not fit
            state = jax.jit(lambda k: init_state(api, opt, k),
                            out_shardings=rep)(jax.random.PRNGKey(seed))
            key = jax.random.PRNGKey(seed + 100)
            if name == "ltp":
                text = fn.lower(state, batches_[0], frac, key, lr).as_text()
                compiled = "tpu_custom_call" in text
                print(f"{cfg.name} ltp: gate is a compiled kernel: "
                      f"{compiled}")
                if compiled == common.interpret_mode():
                    raise SmokeFailure(f"ltp: compiled kernel {compiled} "
                                       f"on {jax.default_backend()}")
            losses[name], walls = [], []
            for b in batches_:
                t0 = time.perf_counter()
                if name == "plain":
                    state, m = fn(state, b, lr)
                else:
                    key, sub = jax.random.split(key)
                    state, m = fn(state, b, frac, sub, lr)
                losses[name].append(float(m["loss"]))
                walls.append(time.perf_counter() - t0)
            del state
            print(f"{cfg.name} {name} on {n} chips: losses "
                  f"{losses[name]}; step walls "
                  f"{[round(w, 3) for w in walls]} s "
                  f"(first compiles; smoke times, not metrics)")
    rel = max(abs(a - b) / max(abs(b), 1e-12)
              for a, b in zip(losses["ltp"], losses["plain"]))
    print(f"{cfg.name}: max relative |ltp - plain| loss = {rel:.3e} "
          f"(limit {LOSS_RTOL:.0e})")
    if not all(math.isfinite(x) for v in losses.values() for x in v):
        raise SmokeFailure(f"loss not finite: {losses}")
    if not rel <= LOSS_RTOL:
        raise SmokeFailure(f"ltp and plain losses differ by {rel:.3e} "
                           f"relative > {LOSS_RTOL:.0e}")
    return {"losses": losses, "max_rel_diff": rel}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}")
    try:
        if args.chips == 4:
            sharded_phase(get_config("smollm_360m").replace(dtype="float32"),
                          devices[:4])
        else:
            papernet_phase(get_config("papernet"))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
