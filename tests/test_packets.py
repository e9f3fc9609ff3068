"""Packetization invariants (unit + hypothesis property tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import packets as pk


def _tree(shapes):
    return {f"t{i}": jnp.arange(int(np.prod(s)), dtype=jnp.float32).reshape(s) + i
            for i, s in enumerate(shapes)}


def test_roundtrip_exact():
    tree = _tree([(7, 5), (13,), (2, 3, 4)])
    plan = pk.make_plan(tree, packet_floats=8)
    flat = pk.flatten(plan, tree)
    back = pk.unflatten(plan, flat)
    for k in tree:
        np.testing.assert_array_equal(tree[k], back[k])


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.tuples(st.integers(1, 9), st.integers(1, 9)), min_size=1, max_size=5),
    st.integers(2, 64),
)
def test_roundtrip_property(shapes, p):
    tree = _tree(shapes)
    plan = pk.make_plan(tree, packet_floats=p)
    back = pk.unflatten(plan, pk.flatten(plan, tree))
    for k in tree:
        np.testing.assert_array_equal(tree[k], back[k])


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.tuples(st.integers(1, 12), st.integers(1, 12)), min_size=1, max_size=4),
    st.integers(2, 50),
)
def test_padding_bubble_alignment(shapes, p):
    """No float straddles a packet boundary: zeroing any packet zeroes only
    whole float elements and leaves every other element bit-identical
    (paper §III-C, Fig 8)."""
    tree = _tree(shapes)
    plan = pk.make_plan(tree, packet_floats=p)
    flat = pk.flatten(plan, tree)
    kill = plan.n_packets // 2
    flat2 = flat.at[kill].set(0.0)
    back = pk.unflatten(plan, flat2)
    orig = pk.unflatten(plan, flat)
    changed = 0
    for k in tree:
        diff = np.asarray(back[k] != orig[k])
        eq_zero = np.asarray(back[k] == 0)
        assert np.all(~diff | eq_zero)   # every changed element became 0
        changed += diff.sum()
    assert changed <= plan.packet_floats


def test_critical_packets_cover_tensor_edges():
    tree = _tree([(17, 3), (5,), (101,)])
    plan = pk.make_plan(tree, packet_floats=16, critical_per_tensor=1)
    sizes = [51, 5, 101]
    offs = np.cumsum([0] + sizes)[:-1]
    for off, sz in zip(offs, sizes):
        assert plan.critical[off // 16]
        assert plan.critical[(off + sz - 1) // 16]


def test_delivery_mask_critical_always_on():
    tree = _tree([(64, 4)])
    plan = pk.make_plan(tree, packet_floats=8)
    m = pk.delivery_mask(plan, jax.random.PRNGKey(1), 0.0)
    assert np.all(np.asarray(m)[plan.critical] == 1.0)
    assert np.all(np.asarray(m)[~plan.critical] == 0.0)


@settings(max_examples=20, deadline=None)
@given(st.floats(0.1, 0.95), st.integers(0, 100))
def test_delivery_mask_rate(frac, seed):
    tree = _tree([(700, 4)])
    plan = pk.make_plan(tree, packet_floats=8, critical_per_tensor=1)
    m = np.asarray(pk.delivery_mask(plan, jax.random.PRNGKey(seed), frac))
    noncrit = m[~plan.critical]
    assert abs(noncrit.mean() - frac) < 0.12


def test_local_plan_shapes():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P
    sds = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
    plan = pk.local_plan(sds, {"w": P(None, None)}, mesh, packet_floats=8)
    assert plan.n_floats == 64 * 32
