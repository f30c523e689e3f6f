"""Everything the benchmark knows about the program under test.

The program builds its model from a registered architecture name and
initializes its own weights. The benchmark instead hands it the
configuration from the cell's file and the weights it made from the
seed: while a server or trainer is built, ``repro.configs.get_config``
answers with the cell's configuration and ``transformer.model_init``
with the benchmark's weights, mapped into the program's layout by the
reference module that the configuration file names.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import re

import jax
import jax.numpy as jnp


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


def reference(config: dict):
    """The reference module that the configuration file names: a path
    under ``bench/reference/`` (the contract is that package's
    docstring)."""
    path = config.get("reference")
    m = re.fullmatch(r"bench/reference/([A-Za-z][A-Za-z0-9_]*)\.py", str(path))
    name = m and f"bench.reference.{m.group(1)}"
    if m is None or importlib.util.find_spec(name) is None:
        raise ValueError(f"{config['name']}: reference {path!r} is not a module under bench/reference/")
    return importlib.import_module(name)


def check_layout(cfg, config: dict) -> None:
    """Raise unless the program's own parameter tree has the structure,
    shapes and dtypes that the reference module's mapping produces."""
    from repro.models import transformer as T

    ref = reference(config)
    theirs = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    ours = jax.eval_shape(
        lambda: ref.to_program_params(ref.init(jax.random.PRNGKey(0), config["model"]), cfg))
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
    check_layout(cfg, config)
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
    ref = reference(config)
    return jax.jit(lambda key: ref.to_program_params(ref.init(key, config["model"]), cfg),
                   out_shardings=sharding)


def zero_params(config: dict, cfg) -> dict:
    """Zeros in the program's layout: the same program for every seed,
    for a constructor whose own initializer runs under ``jit``."""
    ref = reference(config)
    shapes = jax.eval_shape(lambda: ref.to_program_params(
        ref.init(jax.random.PRNGKey(0), config["model"]), cfg))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def build_server(config: dict, serve: dict, seed: int):
    """A ``launch.serve.Server`` for the cell, with weights from the seed."""
    from repro.launch.serve import ServeConfig, Server

    cfg = model_config(config)
    made = seeded_init(config, cfg)(reference(config).seed_key(seed))
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
