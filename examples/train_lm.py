"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
LTP-synced gradients (deliverable b).

The model is the smollm-360m family at ~100M scale; data is the synthetic
bigram corpus (loss floor = chain entropy, so the curve shows real
learning). Gradient sync uses the Early-Close controller + packet masks;
checkpoints are written at the end.

  PYTHONPATH=src python examples/train_lm.py --steps 300 [--tiny]
"""
import argparse
import time

import jax
import numpy as np

from repro.config import LTPConfig, NetConfig, TrainConfig
from repro.configs import get_config
from repro.checkpoint import save_checkpoint
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.optim import make_optimizer
from repro.train import PSTrainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true",
                    help="2-layer model for a fast demo run")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    base = get_config("smollm_360m")
    if args.tiny:
        cfg = base.replace(n_layers=2, d_model=128, n_heads=4, n_kv=2,
                           head_dim=32, d_ff=256, vocab=512)
    else:
        # ~100M params: 12 layers of d_model 768
        cfg = base.replace(n_layers=12, d_model=768, n_heads=12, n_kv=4,
                           head_dim=64, d_ff=2048, vocab=8192)
    cfg = cfg.replace(dtype="float32")
    api = build(cfg)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(
            jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0))))
    )
    print(f"model: {cfg.name} ({n_params/1e6:.1f}M params)")

    lm = SyntheticLM(vocab=cfg.vocab, seed=0)
    print(f"bigram entropy floor: {lm.entropy_floor:.3f} nats "
          f"(init loss ~ {np.log(cfg.vocab):.3f})")

    tc = TrainConfig(batch=args.batch, seq=args.seq, lr=3e-4,
                     optimizer="adamw", steps=args.steps)
    net = NetConfig(10, 1, 0.001, 4096)
    tr = PSTrainer(api, make_optimizer(tc), tc, LTPConfig(), net,
                   n_workers=args.workers, protocol="ltp",
                   compute_time=0.05, seed=0)

    def gen():
        for step in range(args.steps):
            yield lm.train_batch(args.batch, args.seq, step)

    t0 = time.time()
    tr.run(gen(), epoch_steps=100, log_every=10)
    print(f"wall {time.time()-t0:.0f}s, simulated {tr.sim_time:.0f}s, "
          f"final loss {tr.history[-1]['loss']:.4f} "
          f"(floor {lm.entropy_floor:.3f})")
    save_checkpoint(args.ckpt, tr.params, step=tr.step_idx)
    print(f"checkpoint -> {args.ckpt}.npz")


if __name__ == "__main__":
    main()
