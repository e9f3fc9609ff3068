"""Training launcher.

Two modes:

* host (default): the paper's PS training loop on this host — W vmapped
  workers, LTP transport (or a TCP baseline), synthetic data, checkpoints.

      PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \
          --reduced --steps 100 --protocol ltp --loss-rate 0.001

* sharded: the pod-scale LTP `shard_map` train step on whatever devices
  this process has (a real TPU slice, or host devices via XLA_FLAGS) —
  the same code path the dry-run lowers at 256/512 chips.

      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.train --mode sharded \
          --arch smollm_360m --reduced --steps 10 --n-data 8
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint import save_checkpoint
from repro.config import LTPConfig, NetConfig, TrainConfig
from repro.configs import get_config, get_reduced
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.optim import make_optimizer
from repro.train import PSTrainer
from repro.train.trainer import init_state, make_ltp_train_step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=["host", "sharded"], default="host")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--protocol", default="ltp",
                    choices=["ltp", "bbr", "cubic", "reno"])
    ap.add_argument("--loss-rate", type=float, default=0.001)
    ap.add_argument("--compensation", default="paper",
                    choices=["paper", "count", "expected"])
    ap.add_argument("--n-data", type=int, default=0,
                    help="sharded mode: data-axis size (0 = all devices)")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_reduced if args.reduced else get_config)(args.arch)
    cfg = cfg.replace(dtype="float32")
    api = build(cfg)
    tc = TrainConfig(batch=args.batch, seq=args.seq, lr=args.lr,
                     optimizer="adamw", steps=args.steps)
    opt = make_optimizer(tc)
    lm = SyntheticLM(vocab=cfg.vocab, seed=0)
    ltp = LTPConfig(compensation=args.compensation)

    if args.mode == "host":
        net = NetConfig(10, 1, args.loss_rate, 4096)
        tr = PSTrainer(api, opt, tc, ltp, net, n_workers=args.workers,
                       protocol=args.protocol, compute_time=0.05, seed=0)
        gen = (lm.train_batch(args.batch, args.seq, s)
               for s in range(args.steps))
        tr.run(gen, epoch_steps=max(1, args.steps // 3), log_every=10)
        print(f"final loss {tr.history[-1]['loss']:.4f} | "
              f"throughput {tr.throughput(args.batch):.1f} seq/s (simulated)")
        if args.ckpt:
            save_checkpoint(args.ckpt, tr.params, tr.step_idx)
        return 0

    # sharded mode
    n_data = args.n_data or jax.device_count()
    mesh = make_mesh((n_data, jax.device_count() // n_data),
                     ("data", "model"))
    print(f"mesh: {dict(mesh.shape)}; LTP workers = data axis ({n_data})")
    batch_specs = {"tokens": P("data"), "labels": P("data")}
    step = make_ltp_train_step(api, opt, mesh, ltp, ("data",), batch_specs)
    state = init_state(api, opt, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    frac = jnp.ones((n_data,))
    with jax.set_mesh(mesh):
        for s in range(args.steps):
            b = lm.train_batch(args.batch, args.seq, s)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            key, sub = jax.random.split(key)
            # a simple loss-rate-driven delivered fraction per step
            frac = jnp.clip(1.0 - args.loss_rate * 10
                            + 0.0 * frac, 0.5, 1.0) * jnp.ones((n_data,))
            state, m = step(state, b, frac, sub, jnp.float32(args.lr))
            if s % 10 == 0:
                print(f"step {s:4d} loss {float(m['loss']):.4f} "
                      f"delivered {float(m['delivered_frac']):.3f}",
                      flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, state.params, args.steps)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
