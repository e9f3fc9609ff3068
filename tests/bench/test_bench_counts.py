"""The benchmark's operation counts and the control's arithmetic against
hand counts."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import BENCH, load_module  # noqa: E402

RESNET56 = load_module(BENCH / "configs" / "resnet56.py")
REF = load_module(BENCH / "configs" / "resnet56_ref.py")


def test_resnet56_flops_per_sample_by_hand():
    # forward FLOPs, 2 per multiply-add, per 32x32x3 image
    stem = 2 * 32 * 32 * 9 * 3 * 16
    s0 = 18 * (2 * 32 * 32 * 9 * 16 * 16)          # 9 blocks x 2 convs
    s1 = (2 * 16 * 16 * 9 * 16 * 32 + 2 * 16 * 16 * 9 * 32 * 32
          + 2 * 16 * 16 * 16 * 32                  # the 1x1 projection
          + 16 * (2 * 16 * 16 * 9 * 32 * 32))
    s2 = (2 * 8 * 8 * 9 * 32 * 64 + 2 * 8 * 8 * 9 * 64 * 64
          + 2 * 8 * 8 * 32 * 64 + 16 * (2 * 8 * 8 * 9 * 64 * 64))
    fc = 2 * 64 * 10
    # the stem needs no input gradient: 2x; every other layer 3x
    want = 2 * stem + 3 * (s0 + s1 + s2 + fc)
    assert RESNET56.flops_per_sample() == want == 753_602_304


def test_resnet56_parameter_count():
    import jax

    shapes = jax.eval_shape(
        lambda k: REF.init(k, RESNET56.SIZES), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # He et al. give 0.85M; the two 1x1 projections add 16*32 + 32*64
    assert n == 855_578
    assert -(-n // 360) == 2377


def _dot(a, b, p):
    import jax.numpy as jnp

    return jnp.dot(a, b, precision=p)


def test_the_control_computes_three_bfloat16_products():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 32)), jnp.float32)
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    xh, xl = REF._split(x)
    wh, wl = REF._split(w)
    # the products of the parts, summed in float64, less the dropped one
    want = (np.asarray(xh, np.float64) + np.asarray(xl, np.float64)) @ \
        (np.asarray(wh, np.float64) + np.asarray(wl, np.float64)) - \
        np.asarray(xl, np.float64) @ np.asarray(wl, np.float64)
    got = np.asarray(REF._product(_dot, x, w, "bf16_3x"), np.float64)
    assert np.max(np.abs(got - want)) < 1e-4
    # a precision below float32's, above one bfloat16 pass
    err = np.max(np.abs(got - exact)) / np.max(np.abs(exact))
    one_pass = np.asarray(xh, np.float64) @ np.asarray(wh, np.float64)
    assert 1e-7 < err < np.max(np.abs(one_pass - exact)) / np.max(
        np.abs(exact)) / 10


def test_the_control_gradient_is_three_bfloat16_products_too():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    _, vjp = jax.vjp(lambda a, b: REF._product(_dot, a, b, "bf16_3x"), x, w)
    dx, dw = vjp(ct)
    exact_dx = np.asarray(ct, np.float64) @ np.asarray(w, np.float64).T
    exact_dw = np.asarray(x, np.float64).T @ np.asarray(ct, np.float64)
    for got, exact in ((dx, exact_dx), (dw, exact_dw)):
        rel = np.max(np.abs(np.asarray(got, np.float64) - exact)) / \
            np.max(np.abs(exact))
        assert 1e-7 < rel < 1e-4
    # at "highest" the gradient is float32's
    _, vjp = jax.vjp(lambda a, b: REF._product(_dot, a, b, "highest"), x, w)
    assert np.asarray(vjp(ct)[0]) == pytest.approx(exact_dx, rel=1e-5,
                                                   abs=1e-5)
