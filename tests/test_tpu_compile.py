"""Compile the Pallas kernels, and the steps that run them, for a TPU v5e
that is described but not attached (``v5e:2x2``).

Nothing here runs: each test lowers with shapes only and compiles with
the TPU's compiler, which refuses what the interpreter accepts (blocks
not aligned to the tiling, more VMEM than a kernel may use, a
``pallas_call`` inside a ``check_vma`` shard_map without a ``vma``).
Each test asserts the kernel reached the compiled program as a
``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers each import
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.config import LTPConfig
from repro.configs import get_config, get_reduced
from repro.core import packets as pk
from repro.kernels import common
from repro.kernels.dropfill import dropfill
from repro.kernels.packet_reduce import packet_reduce
from repro.kernels.randomk import randomk
from repro.launch.mesh import make_mesh
from repro.models import build, cnn
from repro.optim import sgd_momentum
from repro.runtime import step as stp
from repro.train.trainer import TrainState, make_ltp_train_step

PAYLOAD = 384          # packet_floats=360 padded to whole lanes


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable cannot be read back from the
    # persistent cache without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Route the program's own kernel calls (``interpret=None``) to the
    compiled kernels, as they resolve on a TPU."""
    monkeypatch.setattr(common, "interpret_mode",
                        lambda interpret=None: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args):
    return fn.lower(*args).compile().as_text()


def test_dropfill_compiles(one_chip):
    n = 8192
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    fn = jax.jit(functools.partial(dropfill, interpret=False))
    assert "tpu_custom_call" in _hlo(fn, f32((n, PAYLOAD)), f32((n,)),
                                     f32((n,)))


@pytest.mark.parametrize("comp", ["paper", "count"])
@pytest.mark.parametrize("w", [8, 64])
def test_packet_reduce_compiles(one_chip, w, comp):
    n = 1024
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    fn = jax.jit(functools.partial(packet_reduce, compensation=comp,
                                   interpret=False))
    assert "tpu_custom_call" in _hlo(fn, f32((w, n, PAYLOAD)), f32((w, n)))


def test_randomk_compiles(one_chip):
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    fn = jax.jit(functools.partial(randomk, interpret=False))
    assert "tpu_custom_call" in _hlo(fn, f32((2048, 2048)), f32((2048, 2048)),
                                     f32(()))


def test_papernet_fused_step_compiles(one_chip, compiled_kernels):
    """The BSP step of the paper's job at its published width: 8 workers,
    batch 128, the Pallas reduction."""
    w, batch = 8, 128
    cfg = get_config("papernet")
    api = build(cfg)
    opt = sgd_momentum()
    ltp = LTPConfig(sync_backend="pallas")
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    plan = pk.make_plan(params, ltp.packet_floats, ltp.critical_per_tensor)
    step = stp.build_fused_step(api, opt, ltp, plan, w, "ltp")

    def place(t):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), t)

    args = (
        place(params), place(jax.eval_shape(opt.init, params)), None,
        {"images": _sds((w, batch // w, 32, 32, 3), jnp.float32, one_chip),
         "labels": _sds((w, batch // w), jnp.int32, one_chip)},
        _sds((w, plan.n_packets), jnp.float32, one_chip),
        _sds((w,), jnp.float32, one_chip),
        _sds((), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in _hlo(step, *args)


def test_sharded_ltp_step_compiles(topo, compiled_kernels):
    """``make_ltp_train_step`` with the Pallas gate inside its
    ``check_vma=True`` shard_map, on a (data=4, model=1) mesh."""
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    cfg = get_reduced("smollm_360m").replace(dtype="float32")
    api = build(cfg)
    opt = sgd_momentum()
    ltp = LTPConfig(sync_backend="pallas")
    batch_specs = {"tokens": P("data"), "labels": P("data")}
    step = make_ltp_train_step(api, opt, mesh, ltp, ("data",), batch_specs)
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))

    def place(t):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, rep), t)

    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    state = TrainState(place(params),
                       place(jax.eval_shape(opt.init, params)),
                       _sds((), jnp.int32, rep))
    batch = {k: _sds((8, 64), jnp.int32, data) for k in batch_specs}
    with jax.set_mesh(mesh):
        text = _hlo(jax.jit(step), state, batch,
                    _sds((4,), jnp.float32, rep),
                    _sds((2,), jnp.uint32, rep), _sds((), jnp.float32, rep))
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


def _plain_norm(x, scale, offset, eps=1e-5):
    """The channel norm's plain formula, left to autodiff."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + offset


def test_chan_norm_backward_cuts_temporaries(one_chip):
    """A 16-channel basic block of the CNN (conv, norm, relu, conv, norm,
    skip, relu) at the benchmark's shapes, its parameter gradients mapped
    over W=8 workers at ``highest``: the norm's closed-form backward needs
    fewer temporaries and moves fewer bytes than autodiff of the plain
    formula."""
    w, batch, c = 8, 128, 16
    conv = _sds((w, 3, 3, c, c), jnp.float32, one_chip)
    vec = _sds((w, c), jnp.float32, one_chip)
    params = {"w1": conv, "s1": vec, "o1": vec,
              "w2": conv, "s2": vec, "o2": vec}
    x = _sds((w, batch, 32, 32, c), jnp.float32, one_chip)

    def compiled(norm):
        def loss(p, x):
            h = jax.nn.relu(norm(cnn._conv(x, p["w1"]), p["s1"], p["o1"]))
            h = norm(cnn._conv(h, p["w2"]), p["s2"], p["o2"])
            return jnp.mean(jax.nn.relu(h + x))

        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.vmap(jax.grad(loss))).lower(params, x).compile()

    new, old = compiled(cnn._chan_norm), compiled(_plain_norm)
    assert (new.memory_analysis().temp_size_in_bytes
            < old.memory_analysis().temp_size_in_bytes)
    assert (new.cost_analysis()["bytes accessed"]
            < old.cost_analysis()["bytes accessed"])
