"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpreted off
the TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("n,p", [(130, 360), (256, 384), (7, 33), (1000, 128),
                                 (1, 1), (513, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dropfill(n, p, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    pkts = jax.random.normal(k1, (n, p)).astype(dtype)
    mask = (jax.random.uniform(k2, (n,)) < 0.7).astype(jnp.float32)
    scale = jax.random.uniform(k3, (n,), minval=0.5, maxval=2.0)
    out = ops.ltp_dropfill(pkts, mask, scale)
    expect = ref.dropfill_ref(pkts, mask, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_dropfill_zero_fills_lost():
    pkts = jnp.ones((64, 360))
    mask = jnp.zeros((64,)).at[::2].set(1.0)
    out = np.asarray(ops.ltp_dropfill(pkts, mask))
    assert np.all(out[1::2] == 0) and np.all(out[::2] == 1)


@pytest.mark.parametrize("w,n,p", [(8, 130, 360), (4, 64, 384), (16, 33, 100),
                                   (2, 5, 7)])
@pytest.mark.parametrize("comp", ["paper", "count"])
def test_packet_reduce(w, n, p, comp):
    k1, k2 = jax.random.split(KEY)
    pkts = jax.random.normal(k1, (w, n, p), jnp.float32)
    mask = (jax.random.uniform(k2, (w, n)) < 0.8).astype(jnp.float32)
    out = ops.ltp_packet_reduce(pkts, mask, compensation=comp)
    expect = ref.packet_reduce_ref(pkts, mask, compensation=comp)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


def test_packet_reduce_full_delivery_is_mean():
    pkts = jnp.stack([jnp.full((16, 8), float(w)) for w in range(4)])
    mask = jnp.ones((4, 16))
    out = np.asarray(ops.ltp_packet_reduce(pkts, mask))
    np.testing.assert_allclose(out, 1.5)


def test_packet_reduce_count_unbiased_single_worker():
    pkts = jnp.stack([jnp.full((8, 4), 5.0), jnp.zeros((8, 4))])
    mask = jnp.stack([jnp.ones((8,)), jnp.zeros((8,))])
    out = np.asarray(ops.ltp_packet_reduce(pkts, mask, compensation="count"))
    np.testing.assert_allclose(out, 5.0)   # only deliverer counts


@pytest.mark.parametrize("shape", [(1000,), (37, 23), (4096,), (3, 5, 7)])
@pytest.mark.parametrize("k", [0.0, 0.3, 1.0])
def test_randomk(shape, k):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, shape, jnp.float32)
    u = jax.random.uniform(k2, shape)
    out = ops.randomk_sparsify(x, u, k)
    expect = ref.randomk_ref(x, u, k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_ops_donate_variants_match_and_cache_separately():
    """donate=True must be numerically identical to donate=False (on
    CPU donation is a no-op; on TPU it aliases the input buffer), and
    each (interpret, donate) variant gets its own cached jit so flags
    can't cross-contaminate compiled executables."""
    from repro.kernels import ops

    rng = np.random.default_rng(7)
    pkts = jnp.asarray(rng.normal(size=(33, 250)).astype(np.float32))
    mask = jnp.asarray((rng.random(33) < 0.6).astype(np.float32))
    ref = ops.ltp_dropfill(pkts, mask)
    # fresh buffer per donating call: a donated array may be consumed
    don = ops.ltp_dropfill(jnp.array(pkts), mask, donate=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(don))

    pkts_w = jnp.asarray(rng.normal(size=(3, 17, 250)).astype(np.float32))
    mask_w = jnp.asarray((rng.random((3, 17)) < 0.6).astype(np.float32))
    ref = ops.ltp_packet_reduce(pkts_w, mask_w, compensation="count")
    don = ops.ltp_packet_reduce(jnp.array(pkts_w), mask_w,
                                compensation="count", donate=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(don))

    assert ops._variant("dropfill", True, False) is \
        ops._variant("dropfill", True, False)
    assert ops._variant("dropfill", True, False) is not \
        ops._variant("dropfill", True, True)


def test_interpreter_choice_follows_platform_and_vma():
    """Off the TPU: the generic interpreter, or the TPU interpreter for a
    call whose output varies over manual mesh axes; an explicit
    ``interpret=False`` always compiles."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import common

    assert jax.default_backend() != "tpu"
    assert common.interpret_mode() is True
    assert common.pallas_interpret(None, frozenset()) is True
    assert isinstance(common.pallas_interpret(None, frozenset({"data"})),
                      pltpu.InterpretParams)
    assert common.pallas_interpret(False, frozenset({"data"})) is False
