"""Everything the benchmark knows about the program under test.

The program builds its model from a registered architecture name and
initializes its own weights. The benchmark instead hands it the
configuration from the cell's file and the weights it made from the
seed: while a server or trainer is built, ``repro.configs.get_config``
answers with the cell's configuration and ``transformer.model_init``
with the benchmark's weights, mapped into the program's layout.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from bench.reference import dense_gqa


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file. Every
    field the file leaves out keeps the program's default, and every
    field other than those in ``reduced`` must equal the program's own
    registered configuration of ``program_arch``."""
    from repro import configs as C
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name=config["name"], **config["model"])
    registered = C.get_config(config["program_arch"])
    for f in dataclasses.fields(ModelConfig):
        if f.name == "name" or f.name in config["reduced"]:
            continue
        if getattr(cfg, f.name) != getattr(registered, f.name):
            raise ValueError(
                f"{config['name']}: {f.name}={getattr(cfg, f.name)!r} differs from "
                f"{config['program_arch']}'s {getattr(registered, f.name)!r} and is "
                "not listed in reduced"
            )
    return cfg


def to_program_params(w: dict, cfg) -> dict:
    """The reference layout's weights in the program's parameter tree
    (one scanned group of identical dense layers)."""
    groups = cfg.layer_groups()
    if len(groups) != 1 or len(groups[0][0]) != 1:
        raise ValueError(f"{cfg.name}: expected one group of identical layers")
    ly = w["layers"]
    layer = {
        "norm1": {"scale": ly["norm1"]},
        "mixer": {k: ly[k] for k in ("wq", "wk", "wv", "wo")},
        "norm2": {"scale": ly["norm2"]},
        "ffn": {k: ly[k] for k in ("gate", "up", "down")},
    }
    return {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "groups": [[layer]],
        "lm_head": {"table": w["head"]},
    }


def check_layout(cfg, model: dict) -> None:
    """Raise unless the program's own parameter tree has the structure,
    shapes and dtypes that :func:`to_program_params` produces."""
    from repro.models import transformer as T

    theirs = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    ours = jax.eval_shape(
        lambda: to_program_params(dense_gqa._init(jax.random.PRNGKey(0), model), cfg)
    )
    if jax.tree.structure(theirs) != jax.tree.structure(ours):
        raise ValueError("the program's parameter tree changed layout")
    for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(ours)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"parameter {a.shape} {a.dtype} != {b.shape} {b.dtype}")


@contextlib.contextmanager
def as_program(config: dict, init):
    """While open, the program builds ``config['program_arch']`` with
    the cell's configuration, and its ``model_init`` returns
    ``init(cfg)``: weights in the program's layout."""
    from repro import configs as C
    from repro.models import transformer as T

    cfg = model_config(config)
    check_layout(cfg, config["model"])
    arch = config["program_arch"]
    get_config, model_init = C.get_config, T.model_init

    def patched_get_config(name):
        return cfg if name == arch else get_config(name)

    def patched_init(key, c):
        return init(c) if c is cfg else model_init(key, c)

    C.get_config, T.model_init = patched_get_config, patched_init
    try:
        yield cfg
    finally:
        C.get_config, T.model_init = get_config, model_init


def seeded_init(config: dict, cfg, sharding=None):
    """A jitted ``key -> weights in the program's layout``; every seed
    runs the same compiled program."""
    return jax.jit(lambda key: to_program_params(dense_gqa._init(key, config["model"]), cfg),
                   out_shardings=sharding)


def zero_params(config: dict, cfg) -> dict:
    """Zeros in the program's layout: the same program for every seed,
    for a constructor whose own initializer runs under ``jit``."""
    shapes = jax.eval_shape(lambda: to_program_params(
        dense_gqa._init(jax.random.PRNGKey(0), config["model"]), cfg))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def build_server(config: dict, serve: dict, seed: int):
    """A ``launch.serve.Server`` for the cell, with weights from the seed."""
    from repro.launch.serve import ServeConfig, Server

    cfg = model_config(config)
    made = seeded_init(config, cfg)(dense_gqa.seed_key(seed))
    with as_program(config, lambda c: made):
        sc = ServeConfig(
            arch=config["program_arch"], smoke=False, batch=serve["slots"],
            prompt_len=serve["prompt_window"], max_seq=serve["max_seq"],
            replicas=serve.get("replicas", 1), page_size=serve.get("page_size", 8),
            seed=0,
        )
        server = Server(sc)
    jax.block_until_ready((server.params, server.cache))
    return server


def free(*objs) -> None:
    """Drop device buffers held by ``objs`` now."""
    for o in objs:
        for leaf in jax.tree.leaves(o):
            if isinstance(leaf, jax.Array):
                leaf.delete()
