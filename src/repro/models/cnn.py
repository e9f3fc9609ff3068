"""papernet — ResNet-style mini CNN for the paper's own CIFAR-10 workload.

BatchNorm is replaced by per-position channel LayerNorm so the model is
deterministic under any data sharding (BN's cross-batch statistics would
couple workers through something other than the gradient sync the paper
studies).

The norm has a hand-written backward (``jax.custom_vjp``): it computes
the mean once, keeps only ``(x, mu, rstd, scale)`` for the backward and
recomputes the normalized input there. Autodiff of the plain formula
saved and re-read several full-size intermediates of every conv output,
and those HBM passes, not the convs' arithmetic, took about a quarter of
the fused training step's device time on a TPU v5e (ResNet-56, 8
workers of 128 images). The gradient is the exact one; only the
rounding order differs from autodiff's.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import Params, split_keys
from repro.models.sharding import ShardCtx, NULL_CTX


def _conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    fan_in = kh * kw * cin
    return (jax.random.normal(key, (kh, kw, cin, cout)) * (2.0 / fan_in) ** 0.5).astype(dtype)


def _conv(x, w, stride: int = 1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


_NORM_EPS = 1e-5


@jax.custom_vjp
def _chan_norm(x, scale, offset):
    """Normalize ``x`` over its last (channel) axis, then scale and shift."""
    return _chan_norm_fwd(x, scale, offset)[0]


def _chan_norm_fwd(x, scale, offset):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    rstd = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + _NORM_EPS)
    return xc * rstd * scale + offset, (x, mu, rstd, scale)


def _chan_norm_bwd(res, g):
    x, mu, rstd, scale = res
    xhat = (x - mu) * rstd
    gs = g * scale
    dx = rstd * (gs - jnp.mean(gs, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    lead = tuple(range(g.ndim - 1))
    return dx, jnp.sum(g * xhat, lead), jnp.sum(g, lead)


_chan_norm.defvjp(_chan_norm_fwd, _chan_norm_bwd)


def _norm_p(c):
    return {"scale": jnp.ones((c,), jnp.float32), "offset": jnp.zeros((c,), jnp.float32)}


def init(key, cfg: ModelConfig) -> Params:
    """3 stages x (n_layers//3) basic blocks; widths (w, 2w, 4w)."""
    w = cfg.d_model
    blocks_per_stage = max(1, cfg.n_layers // 3)
    ks = split_keys(key, 2 + 3 * blocks_per_stage * 3)
    ki = iter(ks)
    params: Params = {
        "stem": {"conv": _conv_init(next(ki), 3, 3, 3, w), **_norm_p(w)},
        "stages": [],
    }
    cin = w
    for s in range(3):
        cout = w * (2**s)
        stage = []
        for b in range(blocks_per_stage):
            stride = 2 if (s > 0 and b == 0) else 1
            blk = {
                "conv1": _conv_init(next(ki), 3, 3, cin, cout),
                "n1": _norm_p(cout),
                "conv2": _conv_init(next(ki), 3, 3, cout, cout),
                "n2": _norm_p(cout),
            }
            if stride != 1 or cin != cout:
                blk["proj"] = _conv_init(next(ki), 1, 1, cin, cout)
            stage.append(blk)
            cin = cout
        params["stages"].append(stage)
    params["fc"] = (jax.random.normal(next(ki), (cin, cfg.vocab)) * 0.01).astype(jnp.float32)
    params["fc_b"] = jnp.zeros((cfg.vocab,), jnp.float32)
    return params


def forward(cfg: ModelConfig, params: Params, images, *, ctx: ShardCtx = NULL_CTX):
    """images: (B, 32, 32, 3) float32 -> logits (B, classes)."""
    x = ctx.batch_only(images)
    st = params["stem"]
    x = jax.nn.relu(_chan_norm(_conv(x, st["conv"]), st["scale"], st["offset"]))
    for s, stage in enumerate(params["stages"]):
        for b, blk in enumerate(stage):
            stride = 2 if (s > 0 and b == 0) else 1
            h = jax.nn.relu(
                _chan_norm(_conv(x, blk["conv1"], stride), blk["n1"]["scale"], blk["n1"]["offset"])
            )
            h = _chan_norm(_conv(h, blk["conv2"]), blk["n2"]["scale"], blk["n2"]["offset"])
            skip = _conv(x, blk["proj"], stride) if "proj" in blk else x
            x = jax.nn.relu(h + skip)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc"] + params["fc_b"]


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, Any], *,
            ctx: ShardCtx = NULL_CTX, remat: bool = False):
    logits = forward(cfg, params, batch["images"], ctx=ctx).astype(jnp.float32)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def accuracy(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    logits = forward(cfg, params, batch["images"])
    return jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
