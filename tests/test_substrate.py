"""Substrate tests: optimizers, data pipeline, checkpointing, compression,
HLO walker, PSTrainer integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LTPConfig, NetConfig, TrainConfig
from repro.configs import get_config
from repro.core import compression
from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.data import SyntheticCIFAR, SyntheticLM, batches
from repro.launch import hlo_analysis as ha
from repro.models import build
from repro.optim import adamw, lr_at, sgd_momentum
from repro.train import PSTrainer


def test_sgdm_matches_reference():
    params = {"w": jnp.array([1.0, 2.0])}
    grads = {"w": jnp.array([0.1, -0.2])}
    opt = sgd_momentum(momentum=0.9)
    st = opt.init(params)
    for _ in range(3):
        upd, st = opt.update(grads, st, params, jnp.float32(0.1))
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    # reference loop
    p = np.array([1.0, 2.0]); m = np.zeros(2); g = np.array([0.1, -0.2])
    for _ in range(3):
        m = 0.9 * m + g
        p -= 0.1 * m
    np.testing.assert_allclose(params["w"], p, rtol=1e-6)


def test_adamw_decreases_quadratic():
    opt = adamw()
    params = {"w": jnp.array([5.0, -3.0])}
    st = opt.init(params)
    for _ in range(200):
        g = {"w": 2 * params["w"]}
        upd, st = opt.update(g, st, params, jnp.float32(0.05))
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_lr_schedule():
    tc = TrainConfig(lr=0.1, lr_decay_every=10, lr_decay=0.8)
    assert float(lr_at(tc, 0, epoch_steps := 5)) == pytest.approx(0.1)
    assert float(lr_at(tc, 5 * 10, 5)) == pytest.approx(0.08)
    assert float(lr_at(tc, 5 * 20, 5)) == pytest.approx(0.064)


def test_synthetic_lm_floor():
    lm = SyntheticLM(vocab=64, seed=0)
    assert 0 < lm.entropy_floor < np.log(64)
    toks = lm.sample(4, 32, seed=1)
    assert toks.shape == (4, 33)
    assert toks.max() < 64


def test_synthetic_cifar_learnable():
    d = SyntheticCIFAR(seed=0)
    b = d.train_batch(64, 0)
    assert b["images"].shape == (64, 32, 32, 3)
    assert b["labels"].shape == (64,)
    # same class templates differ from others on average
    t = d.test_set(512)
    assert len(np.unique(t["labels"])) == 10


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": {"w": jnp.arange(6.0).reshape(2, 3)},
            "b": (jnp.ones(4, jnp.int32), jnp.zeros(())),
            }
    p = str(tmp_path / "ck.npz")
    save_checkpoint(p, tree, step=42)
    back, step = restore_checkpoint(p, tree)
    assert step == 42
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_compression_topk_randomk():
    key = jax.random.PRNGKey(0)
    grads = {"w": jax.random.normal(key, (1000,))}
    sp, res = compression.random_k(grads, 0.3, key)
    d = float(compression.measure_density(sp))
    assert abs(d - 0.3) < 0.06
    np.testing.assert_allclose(
        np.asarray(sp["w"] + res), np.asarray(grads["w"]), rtol=1e-6)
    sp2, res2 = compression.top_k(grads, 0.2)
    d2 = float(compression.measure_density(sp2))
    assert abs(d2 - 0.2) < 0.05
    kept = np.asarray(sp2["w"])
    dropped_max = np.abs(np.asarray(grads["w"])[kept == 0]).max()
    kept_min = np.abs(kept[kept != 0]).min()
    assert kept_min >= dropped_max - 1e-6   # top-k keeps the largest


def test_hlo_walker_scan_equals_unroll():
    W = jnp.ones((64, 64), jnp.float32)

    def f_scan(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ W, None), x, None, length=7)
        return y

    def f_unroll(x):
        for _ in range(7):
            x = x @ W
        return x

    x = jnp.ones((64, 64))
    costs = []
    for f in (f_scan, f_unroll):
        c = jax.jit(f).lower(x).compile()
        costs.append(ha.analyze(c.as_text()).flops)
    expected = 2 * 64**3 * 7
    np.testing.assert_allclose(costs, expected, rtol=1e-6)


def test_hlo_walker_collectives():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("d",))

    def f(x):
        return jax.lax.psum(x, "d")

    from jax.sharding import PartitionSpec as P
    g = jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P(),
                      check_vma=False)
    c = jax.jit(g).lower(jnp.ones((1, 256), jnp.float32)).compile()
    cost = ha.analyze(c.as_text())
    assert cost.collective_bytes >= 256 * 4 or cost.collective_bytes == 0
    # (1-device mesh may elide the collective; key assertion: no crash)


def test_pstrainer_short_run_decreases_loss():
    cfg = get_config("papernet").replace(d_model=8, n_layers=3)
    api = build(cfg)
    tc = TrainConfig(batch=64, lr=0.1, steps=25)
    tr = PSTrainer(api, sgd_momentum(), tc, LTPConfig(), NetConfig(10, 1, 0.001, 4096),
                   n_workers=4, protocol="ltp", compute_time=0.01, seed=0)
    data = SyntheticCIFAR(seed=1)
    hist = tr.run(batches(data, tc.batch, tc.steps))
    tail = np.mean([h["loss"] for h in hist[-5:]])
    head = np.mean([h["loss"] for h in hist[:5]])
    assert tail < head
    assert all(0.0 <= h["delivered"] <= 1.0 for h in hist)
    assert tr.sim_time > 0


def test_pstrainer_ltp_vs_baseline_same_seed_close():
    """With ~full delivery LTP matches the lossless baseline closely."""
    cfg = get_config("papernet").replace(d_model=8, n_layers=3)
    api = build(cfg)
    tc = TrainConfig(batch=64, lr=0.05, steps=8)
    data = SyntheticCIFAR(seed=1)
    runs = {}
    for proto, loss_rate in [("ltp", 0.0), ("cubic", 0.0)]:
        tr = PSTrainer(api, sgd_momentum(), tc, LTPConfig(), NetConfig(10, 1, loss_rate, 8192),
                       n_workers=4, protocol=proto, compute_time=0.01, seed=0)
        hist = tr.run(batches(data, tc.batch, tc.steps))
        runs[proto] = hist[-1]["loss"]
    assert abs(runs["ltp"] - runs["cubic"]) < 0.35
