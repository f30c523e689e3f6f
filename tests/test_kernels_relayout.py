"""Pallas relayout (DSE) kernel: shape/dtype sweep vs pure-jnp oracle."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.relayout import ops

BLOCKS = [(16, 8), (8, 8), (64, 16), (16, 16)]  # the paper's layouts


def _rand(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    if jnp.issubdtype(dtype, jnp.integer):
        return (x * 10).astype(dtype)
    return x.astype(dtype)


@pytest.mark.parametrize("src", BLOCKS)
@pytest.mark.parametrize("dst", BLOCKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_relayout_sweep(src, dst, dtype):
    shape = (256, 192) if dtype != jnp.int8 else (128, 64)
    if any(shape[0] % b[0] or shape[1] % b[1] for b in (src, dst)):
        pytest.skip("blocks must divide shape")
    dense = _rand(shape, dtype)
    x = ops.dense_to_blocked(dense, src)
    got = ops.relayout(x, shape, src, dst)
    want = ops.relayout_ref(x, shape, src, dst)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # round-trip through dense
    np.testing.assert_array_equal(
        np.asarray(ops.blocked_to_dense(got, shape)), np.asarray(dense)
    )


def test_paper_layout_strings():
    """P1/P2 workloads: MNM16N8 -> MNM8N8; D1/D2: MNM16N8 -> MNM64N16."""
    assert ops.parse_layout("MNM16N8") == (16, 8)
    assert ops.parse_layout("MNM64N16") == (64, 16)
    with pytest.raises(ValueError):
        ops.parse_layout("N8M16")
    shape = (2048, 192)  # paper P1 QK^T single head shape
    dense = _rand(shape, jnp.bfloat16)
    x = ops.dense_to_blocked(dense, (16, 8))
    got = ops.relayout_str(x, shape, "MNM16N8", "MNM8N8")
    want = ops.relayout_ref(x, shape, (16, 8), (8, 8))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_identity_relayout():
    shape = (64, 64)
    x = ops.dense_to_blocked(_rand(shape, jnp.float32), (16, 8))
    got = ops.relayout(x, shape, (16, 8), (16, 8))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


def test_indivisible_raises():
    x = jnp.zeros((4, 4, 16, 8))
    with pytest.raises(ValueError):
        ops.relayout(x, (64, 32), (16, 8), (24, 8))


@settings(max_examples=25, deadline=None)
@given(
    mi=st.integers(1, 6),
    ni=st.integers(1, 6),
    si=st.sampled_from(BLOCKS),
    di=st.sampled_from(BLOCKS),
)
def test_relayout_property(mi, ni, si, di):
    """Random multiples of lcm(block) shapes: kernel == oracle."""
    import math

    lm = math.lcm(si[0], di[0])
    ln = math.lcm(si[1], di[1])
    shape = (lm * mi, ln * ni)
    dense = _rand(shape, jnp.float32, seed=mi * 7 + ni)
    x = ops.dense_to_blocked(dense, si)
    got = ops.relayout(x, shape, si, di)
    want = ops.relayout_ref(x, shape, si, di)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("plen", [16, 40])
def test_paged_kv_relayout_tiles_wide_rows(plen):
    """The paged-KV shape at h2o width, (1, F) rows -> (8, F) pages with
    F = 30720 bf16: the super-tile splits each row into lane tiles that
    divide F, and the result stays bit-exact."""
    from repro.kernels.relayout.kernel import _supertile

    F = 30720
    tm, tn, lane = _supertile((1, F), (8, F), (plen, F), 2)
    assert lane < F and F % lane == 0 and lane % 128 == 0 and tn == lane
    dense = _rand((plen, F), jnp.bfloat16, seed=plen)
    x = ops.dense_to_blocked(dense, (1, F))
    got = ops.relayout(x, (plen, F), (1, F), (8, F))
    want = ops.relayout_ref(x, (plen, F), (1, F), (8, F))
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint16), np.asarray(want).view(np.uint16))


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_default_interpret_follows_backend(monkeypatch, backend, interpret):
    from repro import kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    assert kernels.default_interpret() is interpret


def test_default_interpret_refuses_other_backends(monkeypatch):
    from repro import kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas lowering"):
        kernels.default_interpret()
