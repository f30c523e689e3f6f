"""The serving copy of the weights (``launch.steps.serving_params``):
which leaves it holds in bf16, that the serve programs compute the same
tokens, logits and caches from it as from the float32 tree, that they
then cast no parameter, and that a weight refresh installs the same
dtypes without a new compile."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr, Var

from repro import configs as C
from repro.launch.serve import ServeConfig, Server
from repro.launch.steps import make_serve_step, make_slot_prefill_step, serving_params
from repro.models import transformer as T

BATCH, PROMPT, MAX_SEQ = 2, 8, 24

# Leaves each family reads in float32 (by their key in the tree).
NORMS = {"scale"}
SSM = {"A_log", "D", "dt_bias", "conv_x_w", "conv_x_b", "conv_BC_w", "conv_BC_b"}
ROUTER = {"router"}


def _tied(arch):
    return dataclasses.replace(C.get_smoke_config(arch), tie_embeddings=True)


# (case, config, leaf keys read in float32 besides the head's table)
CASES = {
    "h2o": (C.get_smoke_config("h2o-danube-1.8b"), NORMS),
    "yi": (C.get_smoke_config("yi-6b"), NORMS),
    "deepseek-moe": (C.get_smoke_config("deepseek-moe-16b"), NORMS | ROUTER),
    "mamba2": (C.get_smoke_config("mamba2-2.7b"), NORMS | SSM),
    "deepseek-v2-lite": (C.get_smoke_config("deepseek-v2-lite-16b"), NORMS | ROUTER),
    "tied-h2o": (_tied("h2o-danube-1.8b"), NORMS),
}


def _key_names(path) -> list[str]:
    return [str(k.key) for k in path if isinstance(k, jax.tree_util.DictKey)]


def _read_in_f32(path, f32_keys, cfg) -> bool:
    names = _key_names(path)
    head = "embed" if cfg.tie_embeddings else "lm_head"
    return names[-1] in f32_keys or names[:2] == [head, "table"]


def _inputs(cfg, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, BATCH), jnp.int32)
    pos = jnp.asarray([PROMPT, PROMPT + 3], jnp.int32)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, PROMPT)), jnp.int32)
    return tokens, pos, prompt


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    cfg, f32_keys = CASES[request.param]
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    held = serving_params(cfg, params, batch=BATCH, prompt_len=PROMPT, max_seq=MAX_SEQ)
    return cfg, f32_keys, params, held


def test_float32_leaves_are_those_the_model_reads_in_float32(case):
    cfg, f32_keys, params, held = case
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree.leaves(held), strict=True):
        want = jnp.float32 if _read_in_f32(path, f32_keys, cfg) else jnp.bfloat16
        assert y.dtype == want, jax.tree_util.keystr(path)
        # rounded once, to the value each step's cast would give
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x.astype(want)))
    assert any(y.dtype == jnp.bfloat16 for y in jax.tree.leaves(held))


def test_held_params_give_the_same_tokens_logits_and_cache(case):
    cfg, _, params, held = case
    tokens, pos, prompt = _inputs(cfg)
    cache = T.init_cache(cfg, BATCH, MAX_SEQ)
    # fill the rows the decode reads through a prefill of each slot
    prefill = jax.jit(make_slot_prefill_step(cfg, MAX_SEQ))
    decode = jax.jit(lambda p, t, q, c: T.decode_step(p, cfg, t, q, c))
    serve = jax.jit(make_serve_step(cfg))
    outs = []
    for p in (params, held):
        first, one = prefill(p, prompt)
        live = jax.tree.map(lambda full, o: full.at[:, 0].set(o[:, 0].astype(full.dtype)),
                            cache, one)
        logits, _ = decode(p, tokens, pos, live)
        outs.append((first, one, logits, serve(p, tokens, pos, live)))
    _assert_same(outs[0], outs[1])


def _sub_jaxprs(params):
    for v in params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _param_derived_casts(jaxpr, marked) -> int:
    """f32 -> bf16 converts in ``jaxpr`` whose operand is a ``marked``
    var, or one made from it by indexing, reshaping or a call."""
    found = 0
    marked = set(marked)
    shape_only = {"squeeze", "reshape", "slice", "dynamic_slice", "gather",
                  "transpose", "split"}
    for eqn in jaxpr.eqns:
        ins = [i for i, v in enumerate(eqn.invars) if isinstance(v, Var) and v in marked]
        if not ins:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            found += src == jnp.float32 and eqn.params["new_dtype"] == jnp.bfloat16
        elif name in shape_only:
            marked.update(eqn.outvars)
        for sub in _sub_jaxprs(eqn.params):
            # every call-like op here passes its operands through in order,
            # after any leading operands it adds itself
            skip = len(eqn.invars) - len(sub.invars)
            found += _param_derived_casts(sub, [sub.invars[i - skip] for i in ins if i >= skip])
    return found


def _serve_step_casts(cfg, params) -> int:
    tokens, pos, _ = _inputs(cfg)
    cache = T.init_cache(cfg, BATCH, MAX_SEQ)
    closed = jax.make_jaxpr(make_serve_step(cfg))(params, tokens, pos, cache)
    n = len(jax.tree.leaves(params))
    return _param_derived_casts(closed.jaxpr, closed.jaxpr.invars[:n])


def test_serve_step_casts_no_parameter(case):
    cfg, _, params, held = case
    assert _serve_step_casts(cfg, params) > 0  # the float32 tree is cast in the step
    # a tied table is read in float32 by the head, so its lookup still casts
    assert _serve_step_casts(cfg, held) == int(cfg.tie_embeddings)


def test_weight_refresh_installs_serving_dtypes_without_a_new_compile():
    rng = np.random.default_rng(3)
    sc = ServeConfig(arch="yi-6b", smoke=True, batch=2, prompt_len=24, max_seq=48,
                     replicas=3, page_size=8)
    server = Server(sc)
    dtypes = jax.tree.map(lambda x: x.dtype, server.params)
    assert jnp.bfloat16 in jax.tree.leaves(dtypes) and jnp.float32 in jax.tree.leaves(dtypes)
    prefix = rng.integers(0, 256, size=16).astype(np.int32)
    server.register_prefix(prefix)

    def serve_one():
        suffix = rng.integers(0, 256, size=4).astype(np.int32)
        req = server.submit(np.concatenate([prefix, suffix]), 3)
        server.run([req])
        return req

    serve_one()
    sizes = (server.serve_step._cache_size(), server.slot_prefill._cache_size())

    fresh = T.model_init(jax.random.PRNGKey(9), server.cfg)  # a float32 tree
    rec = server.broadcast_weights(new_params=fresh)
    assert rec["prefix_invalidated"] == 1
    assert server.prefix_cache.entries == []
    assert jax.tree.map(lambda x: x.dtype, server.params) == dtypes
    assert rec["bytes"] == sum(int(x.nbytes) for x in jax.tree.leaves(server.params))
    for x, y in zip(jax.tree.leaves(fresh), jax.tree.leaves(server.params)):
        np.testing.assert_array_equal(np.asarray(x.astype(y.dtype)), np.asarray(y))

    server.register_prefix(prefix)
    assert serve_one().prefix_hit
    assert (server.serve_step._cache_size(), server.slot_prefill._cache_size()) == sizes
