"""The plain reference of the benchmark's CIFAR ResNets, for the
correctness check.

Written from its description and importing nothing of the program: a
3x3 stem conv, then three stages of basic blocks of widths (w, 2w, 4w),
the first block of stages 2 and 3 with stride 2 and a 1x1 projection on
the skip path, each conv followed by a per-position layer norm over
channels (scale, offset; eps 1e-5) and ReLU, then global average pooling
and a linear classifier; the loss is the mean cross-entropy. With
``d_model`` 16 and ``n_layers`` 27 (9 blocks a stage) this is ResNet-56
of He et al. (arXiv:1512.03385, section 4.2), with the program's two
departures (the norm and the projection) written out the same way.

``init`` makes the weights from a key; the benchmark hands the same
weights to the program, so the parameter tree follows the layout the
program reads: ``stem``, ``stages`` (a list of stages, each a list of
blocks), ``fc``, ``fc_b``.

``loss`` takes the convs' and the classifier's ``precision``: "highest"
is the reference, float32 throughout; "bf16_3x" is the control, the
same arithmetic one precision lower, as JAX's "high" precision computes
a float32 product on a TPU: each operand split into a bfloat16 high part
and a bfloat16 remainder, and the three products other than remainder
by remainder summed in float32. Written out, it computes the same on a
CPU, whose products ignore the precision asked for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_EPS = 1e-5


def stage_plan(cfg: dict):
    """[(stride, cin, cout, has_proj)] per block, stage by stage."""
    w, per = cfg["d_model"], max(1, cfg["n_layers"] // 3)
    cin, out = w, []
    for s in range(3):
        cout = w * 2 ** s
        stage = []
        for b in range(per):
            stride = 2 if (s > 0 and b == 0) else 1
            stage.append((stride, cin, cout, stride != 1 or cin != cout))
            cin = cout
        out.append(stage)
    return out


def init(key, cfg: dict, dtype=jnp.float32):
    """He-normal convs, zero-offset unit-scale norms, a small classifier."""
    w = cfg["d_model"]
    plan = stage_plan(cfg)
    n_keys = 2 + sum(3 * len(st) for st in plan)
    keys = iter(jax.random.split(key, n_keys))

    def conv(k, kh, cin, cout):
        return (jax.random.normal(k, (kh, kh, cin, cout))
                * (2.0 / (kh * kh * cin)) ** 0.5).astype(dtype)

    def norm(c):
        return {"scale": jnp.ones((c,), dtype), "offset": jnp.zeros((c,), dtype)}

    params = {"stem": {"conv": conv(next(keys), 3, 3, w), **norm(w)},
              "stages": []}
    for stage in plan:
        blocks = []
        for stride, cin, cout, proj in stage:
            blk = {"conv1": conv(next(keys), 3, cin, cout), "n1": norm(cout),
                   "conv2": conv(next(keys), 3, cout, cout), "n2": norm(cout)}
            k_proj = next(keys)
            if proj:
                blk["proj"] = conv(k_proj, 1, cin, cout)
            blocks.append(blk)
        params["stages"].append(blocks)
    cin = plan[-1][-1][2]
    params["fc"] = (jax.random.normal(next(keys), (cin, cfg["vocab"]))
                    * 0.01).astype(dtype)
    params["fc_b"] = jnp.zeros((cfg["vocab"],), dtype)
    return params


def _split(x):
    """x as a bfloat16 high part and a bfloat16 remainder, in float32."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three(op, x, w):
    """op(x, w) of a product linear in each operand, from three bfloat16
    products, each exact in float32 (one bfloat16 pass multiplies two
    bfloat16 numbers exactly)."""
    (xh, xl), (wh, wl) = _split(x), _split(w)
    return op(xh, wh) + op(xh, wl) + op(xl, wh)


def _product(op, x, w, precision):
    """op(x, w, precision) at "highest", or at "bf16_3x": three bfloat16
    passes forward, and three again for each operand's gradient."""
    if precision != "bf16_3x":
        return op(x, w, precision)

    def exact(a, b):
        return op(a, b, "default")

    @jax.custom_vjp
    def f(x_, w_):
        return _three(exact, x_, w_)

    def fwd(x_, w_):
        return f(x_, w_), (x_, w_)

    def bwd(res, ct):
        x_, w_ = res

        def d_x(c, b):
            return jax.vjp(lambda a: exact(a, b), x_)[1](c)[0]

        def d_w(c, a):
            return jax.vjp(lambda b: exact(a, b), w_)[1](c)[0]
        return _three(d_x, ct, w_), _three(d_w, ct, x_)

    f.defvjp(fwd, bwd)
    return f(x, w)


def _conv(x, w, stride, precision):
    return _product(lambda a, b, p: jax.lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=p),
        x, w, precision)


def _norm(x, scale, offset):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + NORM_EPS) * scale + offset


def loss(params, images, labels, cfg: dict, precision="highest"):
    """Mean cross-entropy of the batch, computed in the params' dtype,
    the products at ``precision`` ("highest" or "bf16_3x")."""
    dt = params["fc"].dtype
    x = images.astype(dt)
    st = params["stem"]
    x = jax.nn.relu(_norm(_conv(x, st["conv"], 1, precision),
                          st["scale"], st["offset"]))
    for stage, blocks in zip(stage_plan(cfg), params["stages"]):
        for (stride, _, _, proj), blk in zip(stage, blocks):
            h = _conv(x, blk["conv1"], stride, precision)
            h = jax.nn.relu(_norm(h, blk["n1"]["scale"], blk["n1"]["offset"]))
            h = _conv(h, blk["conv2"], 1, precision)
            h = _norm(h, blk["n2"]["scale"], blk["n2"]["offset"])
            skip = _conv(x, blk["proj"], stride, precision) if proj else x
            x = jax.nn.relu(h + skip)
    x = jnp.mean(x, axis=(1, 2))
    logits = _product(lambda a, b, p: jnp.dot(a, b, precision=p), x,
                      params["fc"], precision) + params["fc_b"]
    logp = jax.nn.log_softmax(logits.astype(dt), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def flops_per_sample(cfg: dict) -> float:
    """Model FLOPs of one training sample: every conv and the classifier,
    forward (2 per multiply-add), the weight gradient (the same again) and
    the input gradient (the same again, except for the stem, whose input
    is the image). Norms, activations and the optimizer are not counted."""
    w = cfg["d_model"]
    hw = 32 * 32
    fwd = [2 * hw * 9 * 3 * w]            # stem
    for stage in stage_plan(cfg):
        for stride, cin, cout, proj in stage:
            hw //= stride * stride
            fwd.append(2 * hw * 9 * cin * cout)
            fwd.append(2 * hw * 9 * cout * cout)
            if proj:
                fwd.append(2 * hw * cin * cout)
    fwd.append(2 * stage_plan(cfg)[-1][-1][2] * cfg["vocab"])
    return float(2 * fwd[0] + 3 * sum(fwd[1:]))
