"""Each loop rehearsed at smoke size on the CPU through the harness's own
run (everything but the look for a chip), sound and with the timed path
broken underneath: a broken run must come out not correct."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import run as R
from bench.lib import peaks, program
from bench.tests import smoke

SEED = 2**31 + 123_457
SERVE = [
    ("serve.h2o-danube-1.8b.prefix-chat", "h2o-danube-1.8b", "prefix-chat"),
    ("serve.yi-6b-pp4.unshared-docs", "yi-6b-pp4", "unshared-docs"),
]


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no published peak: borrow the v5e's so the per-layer
    readers run (their numbers mean nothing here)."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def serve(cell, cfg, mix, trace=False):
    return R.run_cell(cell, SEED, 1.5, trace, check_chip=False,
                      config=smoke.config(cfg), mix=smoke.mix(mix))


@pytest.mark.parametrize("cell,cfg,mix", SERVE)
def test_serve_cell_is_correct_and_reports_its_metrics(cell, cfg, mix):
    out = serve(cell, cfg, mix)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    spec = smoke.load("BENCHMARK.json")
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"] if R.applies(m, cell)}
    assert {"setup_s", "itl_p99_ms"} <= set(out["metrics"])
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_traced_serve_run_reports_per_layer_metrics(cpu_peaks):
    cell, cfg, mix = SERVE[0]
    out = serve(cell, cfg, mix, trace=True)
    assert out["correct"]
    # the CPU has no TPU device plane: only the host-clock metric remains
    assert set(out["metrics"]) == {"serve.admit_ms", "serve.step_mfu"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell,cfg,mix", SERVE)
def test_a_token_altered_where_it_is_produced_is_not_correct(cell, cfg, mix, monkeypatch):
    build = program.build_server

    def broken(*a, **k):
        server = build(*a, **k)
        step = server.serve_step

        def altered(*args):
            toks, cache = step(*args)
            return (toks + 1) % server.cfg.vocab_size, cache

        server.serve_step = altered
        return server

    monkeypatch.setattr(program, "build_server", broken)
    out = serve(cell, cfg, mix)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


TRAIN = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {src!r}]
import jax
import numpy as np
from jax.sharding import PartitionSpec as P
from bench import run as R
from bench.loops import train_loop
from bench.tests import smoke
fault = {fault!r}
if fault == "unchanged":
    from repro.optim import adamw
    real = adamw.update
    def update(cfg, grads, state, params):
        _, new_state, metrics = real(cfg, grads, state, params)
        return params, state, metrics
    adamw.update = update
elif fault == "half_batch":
    build = train_loop.build
    def half(ctx):
        trainer = build(ctx)
        src = trainer.source
        class Half:
            def batch(self, step, **kw):
                b = src.batch(step, **kw)
                n = b["tokens"].shape[0] // 2
                return {{k: np.concatenate([v[:n], v[:n]]) for k, v in b.items()}}
        trainer.source = Half()
        return trainer
    train_loop.build = half
elif fault == "no_exchange":
    from repro.launch import steps
    def no_exchange(grad_fn, mesh, batch_specs, **kw):
        def f(params, batch):
            return jax.shard_map(grad_fn, mesh=mesh, in_specs=(P(), batch_specs),
                                 out_specs=(P(), P()), axis_names={{"data"}},
                                 check_vma=False)(params, batch)
        return f
    steps.torrent_grad_reduce = no_exchange
out = R.run_cell({cell!r}, {seed}, 1.5, False, check_chip=False,
                 config=smoke.config("h2o-danube-1.8b"), mix=smoke.mix("dp2-tp2"))
print(json.dumps(out))
"""


def train(fault: str, tmp_path) -> dict:
    root = smoke.checkout_with_train(tmp_path)
    code = TRAIN.format(root=root, src=os.path.join(smoke.ROOT, "src"), fault=fault, seed=SEED,
                        cell=smoke.TRAIN_CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_train_cell_is_correct_and_reports_its_metrics(tmp_path):
    out = train("none", tmp_path)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert out["attempted"] > 0 and out["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_a_broken_train_step_is_not_correct(fault, tmp_path):
    out = train(fault, tmp_path)
    assert not out["correct"], out["checks"]
