"""The CNN's per-position channel norm and its hand-written backward.

``models/cnn._chan_norm`` is a ``jax.custom_vjp``; these tests pin its
forward to the plain formula (``jnp.mean`` / ``jnp.var``) and its
closed-form backward to autodiff of that formula, alone, under
``jax.vmap`` and through a whole model's gradient.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import build, cnn


def _plain_norm(x, scale, offset, eps=1e-5):
    """The plain formula, left to autodiff: the reference for the custom backward."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + offset


def _rel(got, want):
    """Largest absolute gap over the largest absolute reference value."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _inputs(shape, seed=0):
    kx, ks, ko = jax.random.split(jax.random.PRNGKey(seed), 3)
    c = shape[-1]
    x = 3.0 * jax.random.normal(kx, shape) + 0.5
    scale = 1.0 + 0.3 * jax.random.normal(ks, (c,))
    offset = 0.2 * jax.random.normal(ko, (c,))
    return x, scale, offset


# (shape, vmapped): the last case maps the norm over a leading worker axis
CASES = [((2, 8, 8, 16), False), ((3, 4, 4, 64), False),
         ((4, 2, 8, 8, 32), True)]


def _fns(vmapped):
    new, old = cnn._chan_norm, _plain_norm
    if vmapped:
        new = jax.vmap(new, in_axes=(0, None, None))
        old = jax.vmap(old, in_axes=(0, None, None))
    return new, old


@pytest.mark.parametrize("shape,vmapped", CASES)
def test_forward_matches_plain_formula(shape, vmapped):
    x, scale, offset = _inputs(shape)
    new, old = _fns(vmapped)
    assert _rel(new(x, scale, offset), old(x, scale, offset)) < 1e-6


@pytest.mark.parametrize("shape,vmapped", CASES)
def test_vjp_matches_autodiff_of_plain_formula(shape, vmapped):
    x, scale, offset = _inputs(shape, seed=1)
    g = jax.random.normal(jax.random.PRNGKey(2), shape)
    new, old = _fns(vmapped)
    _, new_vjp = jax.vjp(new, x, scale, offset)
    _, old_vjp = jax.vjp(old, x, scale, offset)
    for name, got, want in zip(("x", "scale", "offset"), new_vjp(g),
                               old_vjp(g)):
        assert got.shape == want.shape, name
        assert _rel(got, want) < 1e-5, name


def test_model_grad_matches_plain_norm(monkeypatch):
    """papernet's reduced config: ``jax.grad`` of the loss with the new norm
    against the same loss with the plain norm swapped in."""
    api = build(get_reduced("papernet"))
    params = api.init(jax.random.PRNGKey(0))
    ki, kl = jax.random.split(jax.random.PRNGKey(1))
    batch = {"images": jax.random.normal(ki, (4, 32, 32, 3)),
             "labels": jax.random.randint(kl, (4,), 0, 10)}
    grad = jax.jit(jax.grad(api.loss_fn))
    new = grad(params, batch)
    monkeypatch.setattr(cnn, "_chan_norm", _plain_norm)
    old = jax.jit(jax.grad(api.loss_fn))(params, batch)
    leaves_new, tree_new = jax.tree.flatten(new)
    leaves_old, tree_old = jax.tree.flatten(old)
    assert tree_new == tree_old
    for got, want in zip(leaves_new, leaves_old):
        assert _rel(got, want) < 1e-5
