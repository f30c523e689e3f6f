"""Program spans and named scopes: the ``repro.`` host spans of the serve
loop and the train step, with their counts and nesting, as a profiler
session records them; the device scopes in the compiled programs'
``op_name`` metadata; and a span with no session active."""

from __future__ import annotations

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import ServeConfig, Server
from repro.launch.steps import make_serve_step, make_slot_prefill_step
from repro.models import transformer as T
from repro.runtime.tracing import span

from bench.lib.program_trace import in_scope, scope_path
from repro import configs as C

PREFIX_LEN = 8


def _record(log_dir, fn):
    """Run ``fn`` in a profiler session; return the ``repro.`` host spans
    as (name, start_ns, end_ns, counts) sorted by start."""
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    counts = {k: int(v) for k, v in e.stats}
                    out.append((e.name[len("repro."):], e.start_ns, e.end_ns, counts))
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _parent(spans, child):
    """The innermost other span that encloses ``child``."""
    holders = [s for s in spans if s is not child and _inside(child, s)]
    return min(holders, key=lambda s: s[2] - s[1])[0] if holders else None


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A smoke server with one registered prefix, driven through one
    prefix hit and one miss inside a profiler session."""
    server = Server(ServeConfig(arch="yi-6b", smoke=True, batch=2, prompt_len=16,
                                max_seq=48, replicas=1, page_size=PREFIX_LEN))
    vocab = server.cfg.vocab_size
    prefix = np.arange(PREFIX_LEN, dtype=np.int32) % vocab
    server.register_prefix(prefix)
    hit_prompt = np.concatenate([prefix, np.array([3, 1, 4, 1], np.int32)])
    miss_prompt = (np.arange(12, dtype=np.int32) + 5) % vocab
    reqs = {}

    def drive():
        reqs["hit"] = server.submit(hit_prompt, 3)
        reqs["miss"] = server.submit(miss_prompt, 3)
        while not all(r.done for r in reqs.values()):
            server._admit()
            if server._active():
                server.step()

    spans = _record(tmp_path_factory.mktemp("serve_trace"), drive)
    return spans, reqs


def test_serve_spans_counts_and_nesting(served):
    spans, reqs = served
    assert {s[0] for s in spans} == {"serve.admit_request", "serve.seed_rows",
                                     "serve.suffix_decode", "serve.decode_step",
                                     "serve.step_sync"}

    # admitted in queue order: the hit, then the miss
    admits = [s for s in spans if s[0] == "serve.admit_request"]
    hit, miss = reqs["hit"], reqs["miss"]
    assert len(admits) == 2
    for s, req, cached in ((admits[0], hit, PREFIX_LEN), (admits[1], miss, 0)):
        c = s[3]
        assert set(c) == {"prompt_tokens", "cached_tokens", "queued_us"}
        assert c["prompt_tokens"] == req.prompt.size
        assert c["cached_tokens"] == cached
        assert c["queued_us"] >= 0

    for s in spans:
        if s[0] in ("serve.seed_rows", "serve.suffix_decode"):
            assert _parent(spans, s) == "serve.admit_request" and _inside(s, admits[0]), s
            assert s[3] == {}
        if s[0] == "serve.step_sync":
            assert _parent(spans, s) == "serve.decode_step"
        if s[0] == "serve.decode_step":
            assert s[3] == {}
    assert [s[0] for s in spans if _inside(s, admits[1]) and s is not admits[1]] == []


def test_hold_weights_span_counts_the_held_bytes(tmp_path):
    built = {}

    def build():
        built["server"] = Server(ServeConfig(arch="yi-6b", smoke=True, batch=2, prompt_len=16,
                                             max_seq=48, replicas=1))

    spans = _record(tmp_path, build)
    assert [s[0] for s in spans] == ["serve.hold_weights"]
    held = jax.tree.leaves(built["server"].params)
    assert spans[0][3] == {
        "bf16_bytes": sum(int(x.nbytes) for x in held if x.dtype == jnp.bfloat16),
        "f32_bytes": sum(int(x.nbytes) for x in held if x.dtype == jnp.float32),
    }
    assert spans[0][3]["bf16_bytes"] > 0 and spans[0][3]["f32_bytes"] > 0


def test_train_spans_nest_and_count_tokens(tmp_path):
    from repro.launch.train import TrainConfig, Trainer

    tc = TrainConfig(arch="yi-6b", smoke=True, steps=2, global_batch=2, seq_len=16,
                     ckpt_dir=None, loss_chunks=1, log_every=100)
    trainer = Trainer(tc)
    spans = _record(tmp_path, lambda: trainer.run(2))
    steps = [s for s in spans if s[0] == "train.step"]
    assert [s[3] for s in steps] == [{}, {}]
    for name in ("train.batch", "train.sync"):
        children = [s for s in spans if s[0] == name]
        assert len(children) == 2
        assert all(_parent(spans, c) == "train.step" for c in children)
    # the straggler monitor times the train.step span, not a second interval
    assert len(trainer.monitor.durations) == 2
    for d, s in zip(trainer.monitor.durations, steps):
        assert d <= (s[2] - s[1]) * 1e-9


def test_span_without_session_records_nothing(tmp_path):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with span("test.before", n=1) as sp:
        sp.set_metadata(m=2)

    def during():
        with span("test.during", n=3):
            pass

    spans = _record(tmp_path, during)
    with span("test.after"):
        pass
    assert [(s[0], s[3]) for s in spans] == [("test.during", {"n": 3})]


def _scope_paths(compiled_text: str) -> list[tuple[str, ...]]:
    return [scope_path(n) for n in set(re.findall(r'op_name="([^"]*)"', compiled_text))]


def _has_scope(paths, scope: str) -> bool:
    return any(in_scope(p, scope) for p in paths)


def test_serve_programs_carry_model_scopes():
    cfg = C.get_smoke_config("yi-6b")
    params = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, 32))
    step = jax.jit(make_serve_step(cfg)).lower(
        params, jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), cache).compile().as_text()
    prefill = jax.jit(make_slot_prefill_step(cfg, 32)).lower(
        params, jax.ShapeDtypeStruct((1, 8), jnp.int32)).compile().as_text()
    for text in (step, prefill):
        paths = _scope_paths(text)
        for scope in ("attn", "mlp", "lm_head"):
            assert _has_scope(paths, scope), scope


def test_torrent_train_step_carries_reduce_scopes(run_multidevice):
    text = run_multidevice("""
    import dataclasses
    from repro import configs as C
    from repro.launch.steps import _named, make_train_step
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.parallel import sharding as shd

    cfg = dataclasses.replace(
        C.get_smoke_config("yi-6b"), num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=2, d_ff=64, vocab_size=32, head_dim=16)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda: adamw.init(params))
    pspecs = shd.param_pspecs(params, cfg, tp=2)
    ospecs = shd.opt_pspecs(pspecs, params, 2)
    bspec = {"tokens": P("data", None), "labels": P("data", None)}
    step = make_train_step(cfg, adamw.OptConfig(), collectives="torrent",
                           bucket_bytes=4096, mesh=mesh, batch_specs=bspec,
                           loss_chunks=1)
    batch = {k: jax.ShapeDtypeStruct((4, 8), jnp.int32) for k in bspec}
    with jax.set_mesh(mesh):
        text = jax.jit(
            step,
            in_shardings=(_named(mesh, pspecs), _named(mesh, ospecs), _named(mesh, bspec)),
            out_shardings=(_named(mesh, pspecs), _named(mesh, ospecs), None),
        ).lower(params, opt, batch).compile().as_text()
    print(text)
    """, devices=4, timeout=900)
    paths = _scope_paths(text)
    for scope in ("grad_reduce/chain", "optimizer", "attn", "mlp", "lm_head"):
        assert _has_scope(paths, scope), scope
    # the chain's permutes are the step's only collective-permutes
    permutes = re.findall(r"collective-permute\(.*op_name=\"([^\"]*)\"", text)
    assert permutes and all(in_scope(scope_path(n), "grad_reduce/chain") for n in permutes)
