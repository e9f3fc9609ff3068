"""Kernel-backed sync path: backend="pallas" (fused dropfill/packet_reduce
via the ops.py padding wrappers) vs backend="python" (jnp reference) —
agreement to float tolerance on real papernet gradients under lossy masks,
all compensation modes, non-lane-aligned payloads (DESIGN.md §7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import LTPConfig, NetConfig, TrainConfig
from repro.configs import get_config
from repro.core import ltp_sync as ls
from repro.core import make_ltp_sync
from repro.core import packets as pk
from repro.launch.mesh import make_mesh
from repro.models import build


@pytest.fixture(scope="module")
def papernet_grads():
    """Per-worker papernet gradients, packetized with a NON-lane-aligned
    payload (360 % 128 != 0 — exercises the ops.py padding)."""
    cfg = get_config("papernet").replace(d_model=8, n_layers=3)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    k = jax.random.PRNGKey(1)
    w = 4
    imgs = jax.random.normal(k, (w, 8, 32, 32, 3))
    labels = jax.random.randint(k, (w, 8), 0, 10)

    def one(img, lab):
        return jax.grad(
            lambda p: api.loss_fn(p, {"images": img, "labels": lab}))(params)

    grads_w = jax.vmap(one)(imgs, labels)
    plan = pk.make_plan(params, packet_floats=360)
    flat_w = jax.vmap(lambda g: pk.flatten(plan, g))(grads_w)   # (W, n, 360)
    return plan, flat_w, w


@pytest.mark.parametrize("comp", ["paper", "count", "expected"])
def test_reduce_packet_stream_backends_agree(papernet_grads, comp):
    plan, flat_w, w = papernet_grads
    rng = np.random.default_rng(3)
    masks = (rng.random((w, plan.n_packets)) < 0.6).astype(np.float32)
    masks[:, plan.critical] = 1.0
    ltp = LTPConfig(compensation=comp)
    frac = jnp.full((w,), 0.6)
    ref = ls.reduce_packet_stream(jnp.asarray(flat_w), jnp.asarray(masks),
                                  ltp, w, expected_frac=frac,
                                  backend="python")
    ker = ls.reduce_packet_stream(jnp.asarray(flat_w), jnp.asarray(masks),
                                  ltp, w, expected_frac=frac,
                                  backend="pallas")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("payload", [7, 100, 360, 384])
def test_apply_delivery_backends_agree_any_geometry(payload):
    """Padding wrappers: arbitrary (n_packets, payload), lane-aligned or
    not, must round-trip exactly through the kernel tiles."""
    rng = np.random.default_rng(0)
    n = 77
    pkts = jnp.asarray(rng.normal(size=(n, payload)).astype(np.float32))
    mask = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    scale = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    ref = ls.apply_delivery(pkts, mask, scale, backend="python")
    ker = ls.apply_delivery(pkts, mask, scale, backend="pallas")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("comp", ["paper", "count"])
def test_ltp_sync_shard_map_backends_agree(comp):
    """The shard_map-wrapped LTPSync path (bubble-fill + compensation gates
    through dropfill under "pallas") matches the reference."""
    mesh = make_mesh((1, 1), ("data", "model"))
    grads = {"w": jnp.arange(512, dtype=jnp.float32).reshape(32, 16) / 100,
             "b": jnp.linspace(-1, 1, 24)}
    specs = {"w": P(), "b": P()}
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          grads)
    outs = {}
    for be in ("python", "pallas"):
        ltp = LTPConfig(packet_floats=8, compensation=comp, sync_backend=be)
        sync = make_ltp_sync(shapes, mesh, ltp, specs)
        out, _, stats = sync(grads, jnp.full((1,), 0.5),
                             jax.random.PRNGKey(0))
        outs[be] = out
        assert 0.0 < float(stats["delivered_frac"]) <= 1.0
    for k in grads:
        np.testing.assert_allclose(np.asarray(outs["python"][k]),
                                   np.asarray(outs["pallas"][k]),
                                   rtol=1e-5, atol=1e-7)


def test_pstrainer_backends_agree_end_to_end():
    """Full PSTrainer steps on papernet: identical parameter trajectories
    under lossy masks for both backends (count compensation, residual
    error feedback exercises the dropfill path too)."""
    from repro.data.synthetic import SyntheticCIFAR, batches
    from repro.optim import sgd_momentum
    from repro.train.dp_sim import PSTrainer

    cfg = get_config("papernet").replace(d_model=8, n_layers=2)
    api = build(cfg)
    tc = TrainConfig(batch=32, lr=0.1, steps=3)
    data = SyntheticCIFAR(seed=1)
    params = {}
    for be in ("python", "pallas"):
        ltp = LTPConfig(sync_backend=be, compensation="count",
                        error_feedback=True, data_pct_threshold=0.6)
        tr = PSTrainer(api, sgd_momentum(), tc, ltp,
                       NetConfig(10, 1, 0.01, 4096), n_workers=4,
                       protocol="ltp", compute_time=0.01, seed=0)
        hist = tr.run(batches(data, tc.batch, tc.steps))
        assert all(0.0 < h["delivered"] <= 1.0 for h in hist)
        params[be] = tr.params
    for a, b in zip(jax.tree.leaves(params["python"]),
                    jax.tree.leaves(params["pallas"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mask_trace_feeds_sync():
    """DES delivery masks (net/scenarios) drive the fused reduction: the
    realized delivered fraction reported by the trainer equals the trace's
    mean (with criticals pinned)."""
    from repro.data.synthetic import SyntheticCIFAR, batches
    from repro.net.scenarios import train_iterations
    from repro.optim import sgd_momentum
    from repro.train.dp_sim import PSTrainer

    cfg = get_config("papernet").replace(d_model=8, n_layers=2)
    api = build(cfg)
    tc = TrainConfig(batch=32, lr=0.05, steps=2)
    net = NetConfig(10, 1, 0.002, 4096)
    ltp = LTPConfig(data_pct_threshold=0.6)
    out = train_iterations("ltp", net, 4, 3e5, iters=2, seed=7, ltp=ltp,
                           straggler_prob=0.5, straggler_scale=1.0,
                           coalesce=8)
    mt = out["delivery_masks"]
    assert mt is not None and mt.shape[:2] == (2, 4)
    tr = PSTrainer(api, sgd_momentum(), tc, ltp, net, n_workers=4,
                   protocol="ltp", compute_time=0.01, seed=0,
                   bst_trace=out["bst"], mask_trace=mt)
    hist = tr.run(batches(SyntheticCIFAR(seed=1), tc.batch, tc.steps))
    for h in hist:
        assert 0.0 < h["delivered"] <= 1.0


# ---------------------------------------------------------------------------
# sync_backend="auto" (DESIGN.md §9): never a regression, always valid
# ---------------------------------------------------------------------------


def test_resolve_backend_rules():
    """auto -> python in interpret mode and below the crossover; pallas
    only for compiled kernels on large streams. Explicit backends pass
    through untouched."""
    assert ls.resolve_backend("python", 10**9, False) == "python"
    assert ls.resolve_backend("pallas", 1, True) == "pallas"
    assert ls.resolve_backend("auto", 10**12, True) == "python"
    assert ls.resolve_backend("auto", ls.AUTO_CROSSOVER_ELEMS - 1,
                              False) == "python"
    assert ls.resolve_backend("auto", ls.AUTO_CROSSOVER_ELEMS,
                              False) == "pallas"


@pytest.mark.parametrize("comp", ["paper", "count", "expected"])
def test_reduce_packet_stream_auto_matches_python(papernet_grads, comp):
    """In interpret mode auto IS the python backend — bitwise."""
    plan, flat_w, w = papernet_grads
    rng = np.random.default_rng(9)
    masks = (rng.random((w, plan.n_packets)) < 0.7).astype(np.float32)
    ltp = LTPConfig(compensation=comp, sync_backend="auto")
    got = ls.reduce_packet_stream(jnp.asarray(flat_w), jnp.asarray(masks),
                                  ltp, w, expected_frac=0.7)
    ref = ls.reduce_packet_stream(jnp.asarray(flat_w), jnp.asarray(masks),
                                  ltp, w, expected_frac=0.7,
                                  backend="python")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_apply_delivery_auto_matches_python():
    rng = np.random.default_rng(4)
    pkts = jnp.asarray(rng.normal(size=(37, 250)).astype(np.float32))
    mask = jnp.asarray((rng.random(37) < 0.5).astype(np.float32))
    auto = ls.apply_delivery(pkts, mask, backend="auto")
    ref = ls.apply_delivery(pkts, mask, backend="python")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


def test_pstrainer_auto_backend_end_to_end():
    """PSTrainer under sync_backend='auto' matches the python trajectory
    exactly on CPU (interpret mode resolves auto -> python)."""
    from repro.data.synthetic import SyntheticCIFAR, batches
    from repro.optim import sgd_momentum
    from repro.train.dp_sim import PSTrainer

    cfg = get_config("papernet").replace(d_model=8, n_layers=2)
    api = build(cfg)
    tc = TrainConfig(batch=32, lr=0.1, steps=2)
    data = SyntheticCIFAR(seed=1)
    params = {}
    for be in ("python", "auto"):
        ltp = LTPConfig(sync_backend=be, compensation="count",
                        data_pct_threshold=0.6)
        tr = PSTrainer(api, sgd_momentum(), tc, ltp,
                       NetConfig(10, 1, 0.01, 4096), n_workers=4,
                       protocol="ltp", compute_time=0.01, seed=0)
        tr.run(batches(data, tc.batch, tc.steps))
        params[be] = tr.params
    for a, b in zip(jax.tree.leaves(params["python"]),
                    jax.tree.leaves(params["auto"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
