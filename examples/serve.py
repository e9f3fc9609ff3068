"""Batched serving demo: prefill + decode with the KV-cache serve path
(the same serve_step the decode dry-runs lower at pod scale).

  PYTHONPATH=src python examples/serve.py [--arch smollm_360m] [--new 24]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch).replace(dtype="float32")
    api = build(cfg)
    if api.decode_step is None:
        raise SystemExit(f"{args.arch} has no serve path")
    key = jax.random.PRNGKey(0)
    params = api.init(key)
    max_seq = args.prompt_len + args.new
    cache = api.init_cache(args.batch, max_seq, jnp.float32)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab)

    decode = jax.jit(api.decode_step)
    # prompt processing token-by-token (works for every family incl. SSM)
    t0 = time.time()
    logits = None
    for t in range(args.prompt_len):
        logits, cache = decode(params, cache, prompts[:, t], jnp.int32(t))
    prefill_s = time.time() - t0

    # batched greedy decode
    tok = jnp.argmax(logits[:, : cfg.vocab], axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.new - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits[:, : cfg.vocab], axis=-1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    decode_s = time.time() - t0
    toks = np.stack([np.asarray(t) for t in out], axis=1)
    print(f"arch={cfg.name} batch={args.batch}")
    print(f"prompt {args.prompt_len} toks: {prefill_s:.2f}s | "
          f"decode {args.new} toks: {decode_s:.2f}s "
          f"({args.batch * (args.new-1) / max(decode_s,1e-9):.1f} tok/s)")
    for b in range(min(2, args.batch)):
        print(f"  seq{b}: {toks[b].tolist()}")


if __name__ == "__main__":
    main()
