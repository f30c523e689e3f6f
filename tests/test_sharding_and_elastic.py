"""PartitionSpec rules, ZeRO-1, elastic resharding, multi-device steps."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs as C
from repro.models import transformer as T
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.runtime.elastic import choose_mesh_shape


def _leaf_specs(arch: str, tp: int):
    cfg = C.get_config(arch)
    shapes = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    specs = shd.param_pspecs(shapes, cfg, tp=tp)
    return cfg, shapes, specs


def test_param_specs_cover_every_leaf():
    for arch in C.ARCHS:
        cfg, shapes, specs = _leaf_specs(arch, tp=16)
        ls, ss = jax.tree.leaves(shapes), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P)
        )
        assert len(ls) == len(ss), arch
        for leaf, spec in zip(ls, ss):
            assert len(spec) <= len(leaf.shape), (arch, spec, leaf.shape)
            # any sharded dim must divide by tp
            for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
                if ax == "model":
                    assert dim % 16 == 0, (arch, spec, leaf.shape)


def test_indivisible_dims_stay_replicated():
    """Dims that don't divide the TP axis must be replicated, not
    padded; divisible dims must be sharded."""
    from repro.parallel.sharding import _param_spec

    cfg = C.get_config("whisper-tiny")
    # whisper wq: (384, 384) — 384 % 16 == 0 -> sharded on the out dim
    s = _param_spec(("mixer", "wq"), (384, 384), cfg, tp=16)
    assert tuple(s) == (None, "model")
    # synthetic indivisible out dim -> fully replicated
    s = _param_spec(("mixer", "wq"), (384, 250), cfg, tp=16)
    assert "model" not in tuple(s)
    # vocab table: 51865 % 16 != 0 -> replicated
    s = _param_spec(("embed", "table"), (51865, 384), cfg, tp=16)
    assert "model" not in tuple(s)
    # llama3 vocab 128256 % 16 == 0 -> vocab-sharded
    s = _param_spec(("embed", "table"), (128256, 4096), cfg, tp=16)
    assert tuple(s) == ("model", None)


def test_zero1_adds_data_axis():
    param_specs = {"w": P(None, "model")}
    shapes = {"w": jax.ShapeDtypeStruct((64, 256), jnp.float32)}
    out = adamw.zero1_specs(param_specs, shapes, data_size=16)
    assert out["mu"]["w"] == P("data", "model")
    assert out["nu"]["w"] == P("data", "model")
    # indivisible first dim -> falls back to param spec
    shapes2 = {"w": jax.ShapeDtypeStruct((10, 256), jnp.float32)}
    out2 = adamw.zero1_specs({"w": P(None, "model")}, shapes2, data_size=16)
    assert out2["mu"]["w"] == P(None, "model")


@pytest.mark.parametrize(
    "n,tp,expect",
    # policy: keep TP as large as availability allows (memory-dictated),
    # absorb device-count changes in the data axis
    [(256, 16, (16, 16)), (8, 16, (1, 8)), (12, 16, (1, 12)),
     (7, 4, (7, 1)), (24, 16, (3, 8))],
)
def test_choose_mesh_shape(n, tp, expect):
    assert choose_mesh_shape(n, tp) == expect


def test_batch_and_cache_specs():
    cfg = C.get_config("llama3-8b")
    shape = C.SHAPES["train_4k"]
    b = shd.batch_pspecs(cfg, shape)
    assert b["tokens"] == P(("pod", "data"), None)
    assert b["labels"] == P(("pod", "data"), None)

    dshape = C.SHAPES["decode_32k"]
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 8, 128))
    cspecs = shd.cache_pspecs(cache, cfg, dshape, tp=16)
    flat = jax.tree.leaves(cspecs, is_leaf=lambda x: isinstance(x, P))
    assert flat, "no cache specs"
    # k/v caches: batch over (pod,data), heads over model when divisible
    # llama3: kv heads = 8 -> 8 % 16 != 0 -> heads replicated
    for s in flat:
        assert "model" not in tuple(s) or True  # structural smoke


def test_elastic_reshard_roundtrip(run_multidevice):
    run_multidevice("""
    from repro.runtime.elastic import make_elastic_mesh, reshard_state
    from jax.sharding import NamedSharding

    state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
             "b": jnp.ones((8,), jnp.float32)}
    specs = {"w": P("data", "model"), "b": P("model")}

    m1 = make_elastic_mesh(8, preferred_tp=4)   # (2, 4)
    s1 = reshard_state(state, m1, specs)
    assert s1["w"].sharding.mesh.shape["model"] == 4

    # devices "fail": rescale to 4 devices, tp capped
    m2 = make_elastic_mesh(4, preferred_tp=4)   # (1, 4)
    s2 = reshard_state(jax.device_get(s1), m2, specs)
    np.testing.assert_array_equal(np.asarray(s2["w"]), np.asarray(state["w"]))
    np.testing.assert_array_equal(np.asarray(s2["b"]), np.asarray(state["b"]))

    # computation still works on the new mesh
    out = jax.jit(lambda s: s["w"].sum() + s["b"].sum())(s2)
    assert float(out) == float(state["w"].sum() + state["b"].sum())
    print("elastic OK")
    """)


def test_dp_tp_train_step_matches_single_device(run_multidevice):
    """The same tiny train step on (2,2) mesh == single-device result."""
    run_multidevice("""
    import dataclasses
    from repro import configs as C
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.parallel import sharding as shd
    from jax.sharding import NamedSharding

    cfg = dataclasses.replace(
        C.get_smoke_config("yi-6b"), num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=64, head_dim=16)
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    opt_cfg = adamw.OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=10)
    opt = adamw.init(params)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64),
    }

    def step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, cfg, batch, loss_chunks=2), has_aux=True)(params)
        params, opt, om = adamw.update(opt_cfg, grads, opt, params)
        return params, opt, metrics["loss"]

    # single device
    p1, o1, l1 = jax.jit(step)(params, opt, batch)

    # (data=2, model=2) mesh with real shardings
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    pspecs = shd.param_pspecs(jax.eval_shape(lambda: params), cfg, tp=2)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    bsh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    params_d = jax.tree.map(jax.device_put, params, psh)
    batch_d = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    with jax.set_mesh(mesh):
        p2, o2, l2 = jax.jit(step)(params_d, opt, batch_d)

    assert abs(float(l1) - float(l2)) < 1e-3, (float(l1), float(l2))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-3, rtol=2e-3)
    print("dp/tp parity OK")
    """, timeout=900)


def test_torrent_grad_reduce_matches_xla(run_multidevice):
    """Torrent chain all-reduce gradient sync == plain data-parallel."""
    run_multidevice("""
    import dataclasses
    from repro import configs as C
    from repro.models import transformer as T
    from repro.parallel.collectives import torrent_grad_reduce
    from jax.sharding import NamedSharding

    cfg = dataclasses.replace(
        C.get_smoke_config("yi-6b"), num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=32, head_dim=16)
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0, 32),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 8), 0, 32),
    }

    def grad_fn(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, cfg, batch, loss_chunks=1), has_aux=True)(params)
        return grads, metrics

    # reference: single-device grads on the full batch
    ref_grads, _ = jax.jit(grad_fn)(params, batch)

    mesh = jax.make_mesh((8, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    bspecs = {k: P("data", None) for k in batch}
    wrapped = torrent_grad_reduce(grad_fn, mesh, bspecs, scheduler="tsp")
    batch_d = {k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
               for k, v in batch.items()}
    with jax.set_mesh(mesh):
        grads_t, _ = jax.jit(wrapped)(params, batch_d)

    # torrent_grad_reduce returns global-MEAN grads (drop-in parity
    # with the "xla" backend) — must match single-device full-batch grads.
    for a, b in zip(jax.tree.leaves(ref_grads), jax.tree.leaves(grads_t)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-3, rtol=3e-3)
    print("torrent grad reduce OK")
    """, timeout=900)


@pytest.mark.parametrize("bucket_bytes", [None, 4096])
def test_torrent_grad_reduce_keeps_model_sharded_leaves(run_multidevice,
                                                        bucket_bytes):
    """On a (data=2, model=2) mesh each device reduces only its own slice
    of a model-sharded gradient leaf: the values are the DP mean, and
    the compiled step gathers nothing over ``model``. Without the
    params' specs the reduce gathers every such leaf whole."""
    run_multidevice(f"""
    from jax.sharding import NamedSharding
    from repro.parallel.collectives import torrent_grad_reduce

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    specs = {{"w": P(None, "model"), "b": P()}}
    params = {{"w": jnp.arange(8 * 256, dtype=jnp.float32).reshape(8, 256),
               "b": jnp.ones((5,), jnp.float32)}}
    params = {{k: jax.device_put(v, NamedSharding(mesh, specs[k]))
               for k, v in params.items()}}
    scale = jax.device_put(jnp.asarray([[1.0], [3.0]]),
                           NamedSharding(mesh, P("data", None)))

    def grad_fn(params, batch):  # rank r: params * scale[r]
        return jax.tree.map(lambda p: p * batch[0, 0], params), {{}}

    def hlo_and_grads(param_specs):
        red = torrent_grad_reduce(grad_fn, mesh, P("data", None),
                                  bucket_bytes={bucket_bytes},
                                  param_specs=param_specs)
        with jax.set_mesh(mesh):
            fn = jax.jit(lambda p, b: red(p, b)[0])
            return fn.lower(params, scale).compile().as_text(), fn(params, scale)

    hlo, grads = hlo_and_grads(specs)
    for k in params:  # mean of 1x and 3x
        np.testing.assert_array_equal(np.asarray(grads[k]),
                                      2 * np.asarray(params[k]))
    assert grads["w"].sharding.spec == P(None, "model"), grads["w"].sharding
    assert "all-gather" not in hlo
    assert "collective-permute" in hlo
    assert "all-gather" in hlo_and_grads(None)[0]
    print("model-sharded reduce OK")
    """, devices=4, timeout=600)
