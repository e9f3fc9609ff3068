"""LTP gradient-sync semantics: shard_map v1 (packet-local), leafwise v2,
PSTrainer vmapped path — equivalences and compensation properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import LTPConfig
from repro.core import make_ltp_sync
from repro.core import ltp_sync as ls
from repro.core import packets as pk

N_DEV = jax.device_count()


from repro.launch.mesh import make_mesh


def _mesh(shape, axes):
    return make_mesh(shape, axes)


@pytest.fixture(scope="module")
def mesh1():
    return _mesh((1, 1), ("data", "model"))


def _grads():
    return {
        "w": jnp.arange(512, dtype=jnp.float32).reshape(32, 16) / 100,
        "b": jnp.linspace(-1, 1, 24),
    }


def test_full_delivery_is_identity(mesh1):
    grads = _grads()
    specs = {"w": P(), "b": P()}
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads)
    sync = make_ltp_sync(shapes, mesh1, LTPConfig(packet_floats=8), specs)
    out, _, stats = sync(grads, jnp.ones((1,)), jax.random.PRNGKey(0))
    np.testing.assert_allclose(out["w"], grads["w"], rtol=1e-6)
    np.testing.assert_allclose(out["b"], grads["b"], rtol=1e-6)
    assert float(stats["delivered_frac"]) == 1.0


def test_zero_delivery_keeps_critical_only(mesh1):
    grads = _grads()
    specs = {"w": P(), "b": P()}
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads)
    ltp = LTPConfig(packet_floats=8)
    sync = make_ltp_sync(shapes, mesh1, ltp, specs)
    out, _, _ = sync(grads, jnp.zeros((1,)), jax.random.PRNGKey(0))
    flat_in = pk.flatten(sync.plan, grads)
    flat_out = pk.flatten(sync.plan, out)
    crit = sync.plan.critical
    np.testing.assert_allclose(flat_out[crit], flat_in[crit], rtol=1e-6)
    assert np.all(np.asarray(flat_out)[~crit] == 0)


def test_error_feedback_conserves_gradient(mesh1):
    """sent + residual == grads (+ previous residual) exactly."""
    grads = _grads()
    specs = {"w": P(), "b": P()}
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads)
    ltp = LTPConfig(packet_floats=8, error_feedback=True)
    sync = make_ltp_sync(shapes, mesh1, ltp, specs)
    res0 = sync.init_residual()
    out, res1, _ = sync(grads, jnp.full((1,), 0.5), jax.random.PRNGKey(3), res0)
    flat_in = np.asarray(pk.flatten(sync.plan, grads))
    flat_out = np.asarray(pk.flatten(sync.plan, out))  # W=1 -> mean == sent
    np.testing.assert_allclose(flat_out + np.asarray(res1)[0, 0], flat_in,
                               rtol=1e-6, atol=1e-7)


# ----------------------------------------------------------------------------
# leafwise (v2) masking
# ----------------------------------------------------------------------------


def test_leafwise_masks_packet_structure():
    grads = {"w": jnp.ones((10, 7)), "b": jnp.ones((5,))}
    ltp = LTPConfig(packet_floats=8)
    masks, pkt_masks = ls.leafwise_packet_masks(
        grads, jax.random.PRNGKey(0), 0.5, ltp
    )
    flat = np.asarray(masks["w"]).ravel()
    # within a packet the mask is constant
    for p in range(len(flat) // 8):
        seg = flat[p * 8:(p + 1) * 8]
        assert np.all(seg == seg[0])
    # critical first/last packet always delivered
    assert flat[0] == 1.0 and flat[-1] == 1.0
    assert np.asarray(masks["b"]).all()  # 1 packet -> critical -> delivered


def test_leafwise_sync_full_delivery_identity():
    mesh = _mesh((1, 1), ("data", "model"))
    grads = _grads()
    ltp = LTPConfig(packet_floats=8)

    def inner(g, frac, key):
        return ls.masked_psum_leafwise(g, key, frac, ltp, ("data",), 1)

    out, realized = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), grads), P(), P()),
        out_specs=(jax.tree.map(lambda _: P(), grads), P()),
        axis_names={"data"}, check_vma=True,
    )(grads, jnp.ones((1,)), jax.random.PRNGKey(0))
    np.testing.assert_allclose(out["w"], grads["w"], rtol=1e-6)
    assert float(realized) == 1.0


# ----------------------------------------------------------------------------
# PSTrainer-path compensation statistics
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("comp,expect_unbiased", [("paper", False),
                                                  ("count", True)])
def test_compensation_bias(comp, expect_unbiased):
    """With identical grads across workers, count-compensation reproduces
    the true mean exactly on delivered packets; paper-mode shrinks toward 0
    by E[frac]."""
    w, n, p = 8, 200, 8
    grads = {"g": jnp.ones((n * p,))}
    plan = pk.make_plan(grads, packet_floats=p)
    flat = pk.flatten(plan, grads)
    flat_w = jnp.broadcast_to(flat, (w,) + flat.shape)
    keys = jax.random.split(jax.random.PRNGKey(1), w)
    frac = 0.6
    masks = jax.vmap(lambda k: pk.delivery_mask(plan, k, frac))(keys)
    sent = flat_w * masks[:, :, None]
    tot = jnp.sum(sent, axis=0)
    if comp == "count":
        cnt = jnp.maximum(jnp.sum(masks, axis=0), 1.0)
        mean = tot / cnt[:, None]
        # every packet delivered by >=1 worker gives exact mean 1.0
        got = np.asarray(mean)[np.asarray(jnp.sum(masks, 0)) > 0]
        np.testing.assert_allclose(got, 1.0, rtol=1e-6)
    else:
        mean = tot / w
        m = float(jnp.mean(mean))
        assert abs(m - frac) < 0.08   # shrunk toward E[frac]
