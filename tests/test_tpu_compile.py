"""Main-path kernels and collectives compiled for a described TPU v5e.

Nothing runs: the TPU compiler is installed, so each test compiles for a
``v5e:2x2`` topology that is described, not attached, and checks what
the compiler refuses (VMEM, tiling, HBM) and what it emits. Kernels
must lower to Mosaic (``tpu_custom_call``); Chainwrite collectives must
lower to collective-permutes. The topology is described inside a
fixture only: the TPU library admits one loading process, and the test
workers all import this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import chainwrite as cw
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.relayout.kernel import relayout_pallas

# h2o-danube-1.8b's dense KV row: 24 layers x (k, v) x 8 kv heads x 80
KV_WIDTH = 24 * 2 * 8 * 80


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # a described-topology compile is written to the persistent cache
    # but cannot be read back without a chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring(topo):
    return Mesh(np.array(topo.devices), ("x",),
                axis_types=(jax.sharding.AxisType.Auto,))


@pytest.mark.parametrize("plen", [16, 512, 4096])
def test_relayout_compiles_at_prefix_length(one_chip, plen):
    """The paged-KV relayout at h2o width: (1, F) rows -> (8, F) pages.
    512 tokens once ran out of VMEM; lanes are now tiled."""
    x = jax.ShapeDtypeStruct((plen, 1, 1, KV_WIDTH), jnp.bfloat16,
                             sharding=one_chip)
    hlo = relayout_pallas.lower(
        x, (plen, KV_WIDTH), (1, KV_WIDTH), (8, KV_WIDTH), interpret=False,
    ).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_at_h2o_width(one_chip):
    """H=32, Hkv=8, D=80, S=4096, sliding window 4096."""
    B, H, Hkv, S, D = 1, 32, 8, 4096, 80
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, Hkv, S, D), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, window=4096, interpret=False))
    hlo = fn.lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in hlo


def _compile_collective(ring, fn, n):
    body = jax.shard_map(lambda x: fn(x[0])[None], mesh=ring,
                         in_specs=P("x"), out_specs=P("x"))
    x = jax.ShapeDtypeStruct((4, n), jnp.float32,
                             sharding=NamedSharding(ring, P("x")))
    return jax.jit(body).lower(x).compile().as_text()


@pytest.mark.parametrize("name,fn,n", [
    ("multi_chain_all_reduce_k2",
     lambda x: cw.multi_chain_all_reduce(x, "x", ((0, 1), (2, 3))),
     1 << 20),
    # 2**20 + 3 elements: the pad path of a length the ring does not divide
    ("chain_all_reduce_non_divisible",
     lambda x: cw.chain_all_reduce(x, "x", (0, 1, 2, 3)),
     (1 << 20) + 3),
    # a scrambled ring takes the member-lookup addressing path
    ("chain_all_reduce_scrambled_non_divisible",
     lambda x: cw.chain_all_reduce(x, "x", (2, 0, 3, 1)),
     (1 << 20) + 3),
])
def test_chainwrite_collective_compiles_on_four_chips(ring, name, fn, n):
    hlo = _compile_collective(ring, fn, n)
    assert "collective-permute" in hlo, name
