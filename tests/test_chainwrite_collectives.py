"""Chainwrite collectives vs pure-numpy oracles, on 8 virtual devices.

Runs inside subprocesses (conftest.run_multidevice) so the rest of the
suite keeps seeing 1 device. Each snippet asserts internally.
"""

from __future__ import annotations

import pytest

# Multidevice oracle tests (subprocess per test): skipped under QUICK=1.
pytestmark = pytest.mark.slow


def test_chain_broadcast_subset_and_frames(run_multidevice):
    run_multidevice("""
    from repro.core import chainwrite as cw
    from repro.core import chainwrite_ref as ref

    mesh = jax.make_mesh((8,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
    xs = jnp.arange(8 * 6 * 2, dtype=jnp.float32).reshape(8, 6, 2)

    for order in [(2, 5, 1, 7), (0, 1), (3,), tuple(range(8))]:
        for frames in (1, 2, 3, 6):
            if 6 % frames:
                continue
            def f(x, order=order, frames=frames):
                return cw.chain_broadcast(x[0], 'x', order, num_frames=frames)[None]
            y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
            expect = ref.broadcast_ref(np.asarray(xs), order)
            np.testing.assert_allclose(np.asarray(y), expect, err_msg=f"{order} {frames}")

    # frame count must divide the leading dim
    try:
        def g(x):
            return cw.chain_broadcast(x[0], 'x', (0, 1), num_frames=4)[None]
        jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
        raise SystemExit("expected ValueError")
    except ValueError:
        pass
    print("broadcast OK")
    """)


def test_chain_ring_collectives_match_oracles(run_multidevice):
    run_multidevice("""
    import itertools, random
    from repro.core import chainwrite as cw
    from repro.core import chainwrite_ref as ref

    mesh = jax.make_mesh((8,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    orders = [tuple(range(8)), (0, 3, 1, 2, 7, 5, 6, 4), (7, 6, 5, 4, 3, 2, 1, 0)]
    random.seed(1)
    perm = list(range(8)); random.shuffle(perm)
    orders.append(tuple(perm))

    xs = jnp.asarray(rng.normal(size=(8, 4, 3)).astype(np.float32))
    for order in orders:
        # all_gather (stacked + tiled)
        def ag(x, order=order):
            return cw.chain_all_gather(x[0], 'x', order)[None]
        y = jax.jit(jax.shard_map(ag, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
        np.testing.assert_allclose(np.asarray(y), ref.all_gather_ref(np.asarray(xs)), rtol=1e-6)

        def agt(x, order=order):
            return cw.chain_all_gather(x[0], 'x', order, tiled=True)[None]
        y = jax.jit(jax.shard_map(agt, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
        np.testing.assert_allclose(
            np.asarray(y), ref.all_gather_ref(np.asarray(xs), tiled=True), rtol=1e-6)

        # all_reduce
        def ar(x, order=order):
            return cw.chain_all_reduce(x[0], 'x', order)[None]
        y = jax.jit(jax.shard_map(ar, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
        np.testing.assert_allclose(
            np.asarray(y), ref.all_reduce_ref(np.asarray(xs)), rtol=1e-5, atol=1e-5)

    # reduce_scatter + all_to_all need (L, L, ...) inputs
    xs2 = jnp.asarray(rng.normal(size=(8, 8, 5)).astype(np.float32))
    for order in orders:
        def rs(x, order=order):
            return cw.chain_reduce_scatter(x[0], 'x', order)[None]
        y = jax.jit(jax.shard_map(rs, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs2)
        np.testing.assert_allclose(
            np.asarray(y), ref.reduce_scatter_ref(np.asarray(xs2)), rtol=1e-5, atol=1e-5)

        def a2a(x, order=order):
            return cw.chain_all_to_all(x[0], 'x', order)[None]
        y = jax.jit(jax.shard_map(a2a, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs2)
        np.testing.assert_allclose(
            np.asarray(y), ref.all_to_all_ref(np.asarray(xs2)), rtol=1e-6)
    print("ring collectives OK")
    """, timeout=900)


def test_chain_all_reduce_non_divisible_payload(run_multidevice):
    """The pad/unpad path: payload leading dims NOT divisible by the
    ring size L must round-trip through the zero-padded reduce-scatter
    + all-gather and come back at the original shape."""
    run_multidevice("""
    from repro.core import chainwrite as cw
    from repro.core import chainwrite_ref as ref

    mesh = jax.make_mesh((8,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(2)
    for lead in (1, 5, 13, 23):   # all have lead % 8 != 0
        for order in [tuple(range(8)), (3, 1, 0, 2, 7, 5, 6, 4)]:
            xs = jnp.asarray(rng.normal(size=(8, lead, 3)).astype(np.float32))
            def f(x, order=order):
                return cw.chain_all_reduce(x[0], 'x', order)[None]
            y = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
            assert np.asarray(y).shape == xs.shape, (lead, np.asarray(y).shape)
            np.testing.assert_allclose(
                np.asarray(y), ref.all_reduce_ref(np.asarray(xs)),
                rtol=1e-5, atol=1e-5, err_msg=f"lead={lead} {order}")
            # bit-exact against the schedule-replaying oracle too
            np.testing.assert_array_equal(
                np.asarray(y),
                ref.multi_all_reduce_ref(np.asarray(xs), [order]),
                err_msg=f"lead={lead} {order}")
    print("pad path OK")
    """, timeout=900)


def test_chain_all_reduce_scrambled_ring_divisible_payload(run_multidevice):
    """A scrambled ring addresses ring members through a lookup inside
    the rolled step scan. As a gather, XLA:CPU failed to codegen it at
    some payload lengths (16 rows here) even with nothing to pad."""
    run_multidevice("""
    from repro.core import chainwrite as cw
    from repro.core import chainwrite_ref as ref

    mesh = jax.make_mesh((8,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
    order = (3, 1, 0, 2, 7, 5, 6, 4)
    xs = jnp.asarray(
        np.random.default_rng(3).normal(size=(8, 16, 3)).astype(np.float32))
    y = jax.jit(jax.shard_map(
        lambda x: cw.chain_all_reduce(x[0], 'x', order)[None],
        mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
    np.testing.assert_array_equal(
        np.asarray(y), ref.multi_all_reduce_ref(np.asarray(xs), [order]))
    """)


def test_order_must_be_full_permutation(run_multidevice):
    run_multidevice("""
    from repro.core import chainwrite as cw
    mesh = jax.make_mesh((8,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
    xs = jnp.zeros((8, 4))
    try:
        def f(x):
            return cw.chain_all_gather(x[0], 'x', (0, 1, 2))[None]
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
        raise SystemExit("expected ValueError")
    except ValueError:
        pass
    print("validation OK")
    """)


def test_xla_broadcast_baseline(run_multidevice):
    run_multidevice("""
    from repro.core import chainwrite as cw
    mesh = jax.make_mesh((8,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
    xs = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)
    def f(x):
        return cw.xla_broadcast(x[0], 'x', root=5)[None]
    y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
    expect = np.broadcast_to(np.asarray(xs[5]), (8, 3))
    np.testing.assert_allclose(np.asarray(y), expect)
    print("xla broadcast OK")
    """)


def test_compressed_all_reduce_and_error_feedback(run_multidevice):
    """int8 wire is now the IR dimension: ``chain_all_reduce(...,
    wire_dtype="int8")`` replaces the deleted hand-written
    ``compressed_chain_all_reduce``."""
    run_multidevice("""
    from repro.core import chainwrite as cw
    from repro.runtime.compression import ErrorFeedback, dequantize, quantize

    # quantize/dequantize roundtrip error bound: interior values round
    # within scale/2; the max-abs element clips 128 -> 127 (the
    # power-of-two divisor), so the bound is 1.5 steps
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    q, s = quantize(g)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize(q, s) - g))
    assert err.max() <= 1.5 * float(s)

    # ErrorFeedback compensates the residual on the next round
    ef = ErrorFeedback.init(g)
    (q1, s1), ef1 = ErrorFeedback.compress(g, ef)
    assert np.abs(np.asarray(ef1)).max() > 0  # residual captured
    (q2, s2), _ = ErrorFeedback.compress(g, ef1)
    two_round = np.asarray(dequantize(q1, s1) + dequantize(q2, s2))
    plain = np.asarray(dequantize(*quantize(g))) * 2
    # two EF rounds approximate 2g better than two independent rounds
    assert np.abs(two_round - 2 * np.asarray(g)).sum() <= \
        np.abs(plain - 2 * np.asarray(g)).sum() + 1e-6

    mesh = jax.make_mesh((8,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
    xs = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    def f(x):
        return cw.chain_all_reduce(x[0], 'x', wire_dtype='int8')[None]
    y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P('x'), out_specs=P('x')))(xs)
    exact = np.asarray(xs).sum(0)
    got = np.asarray(y)[0]
    # int8 wire: approximate, but well-correlated
    denom = np.abs(exact).max()
    assert np.abs(got - exact).max() / denom < 0.15
    print("compressed all-reduce OK")
    """)
