"""Trainer integration: plain vs LTP shard_map train steps agree at full
delivery; the ZeRO-packet variant matches the psum variant numerically."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import LTPConfig
from repro.configs import get_reduced
from repro.core import ltp_sync as ls
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.models.api import demo_inputs
from repro.optim import sgd_momentum
from repro.shapes import InputShape
from repro.train.trainer import (
    TrainState, init_state, make_ltp_train_step, make_plain_train_step,
)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("smollm_360m").replace(dtype="float32")
    api = build(cfg)
    opt = sgd_momentum()
    state = init_state(api, opt, jax.random.PRNGKey(0))
    batch = demo_inputs(cfg, InputShape("t", 64, 4, "train"),
                        jax.random.PRNGKey(1))
    return cfg, api, opt, state, batch


def test_ltp_full_delivery_matches_plain(setup):
    cfg, api, opt, state, batch = setup
    mesh = _mesh()
    lr = jnp.float32(0.1)
    plain = make_plain_train_step(api, opt)
    s_plain, m_plain = plain(state, batch, lr)

    ltp_cfg = LTPConfig(packet_floats=128)
    with jax.set_mesh(mesh):
        step = make_ltp_train_step(api, opt, mesh, ltp_cfg, ("data",),
                                   jax.tree.map(lambda _: P(), batch))
        s_ltp, m_ltp = step(state, batch, jnp.ones((1,)),
                            jax.random.PRNGKey(2), lr)
    np.testing.assert_allclose(float(m_ltp["loss"]), float(m_plain["loss"]),
                               rtol=1e-5)
    assert float(m_ltp["delivered_frac"]) == 1.0
    for a, b in zip(jax.tree.leaves(s_plain.params),
                    jax.tree.leaves(s_ltp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_ltp_zero_variant_matches_psum_variant(setup):
    cfg, api, opt, state, batch = setup
    mesh = _mesh()
    lr = jnp.float32(0.1)
    ltp_cfg = LTPConfig(packet_floats=128)
    batch_specs = jax.tree.map(lambda _: P(), batch)
    frac = jnp.full((1,), 0.7)
    key = jax.random.PRNGKey(3)

    with jax.set_mesh(mesh):
        step = make_ltp_train_step(api, opt, mesh, ltp_cfg, ("data",),
                                   batch_specs)
        s_psum, _ = step(state, batch, frac, key, lr)
        # zero-state variant
        m_sds = ls.zero_momentum_shapes(
            jax.eval_shape(lambda: state.params), ltp_cfg, 1)
        zstate = TrainState(
            params=state.params,
            opt_state={"m_pkts": [jnp.zeros(s.shape, s.dtype) for s in m_sds]},
            step=state.step,
        )
        s_zero, m_zero = step(zstate, batch, frac, key, lr)
    for a, b in zip(jax.tree.leaves(s_psum.params),
                    jax.tree.leaves(s_zero.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    assert 0.3 < float(m_zero["delivered_frac"]) <= 1.0
