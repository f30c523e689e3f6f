"""Training through ``launch.train.Trainer``, steps back to back.

Set-up builds the trainer with the cell's configuration, the weights made
from the seed and a data source drawn from the seed, and drives it
through its first three steps with the window's own call
(``Trainer.run``). The window then calls ``Trainer.run(1)`` until its
time is up; every completed step's tokens count.

Correctness: after the window the plain reference (float32, ``highest``)
takes the same three steps from the same weights and rows, sharded over
the same chips, and three numbers are compared: the losses; the norm of
each leaf's first gradient as the optimizer got it (from its first
moment after one step); and the norm of each leaf's change over the
three steps. A norm's gap is measured against the reference's norm of
that leaf or of the median leaf, whichever is larger, and the worst leaf
counts.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.lib import program, traffic
from bench.lib.record import Span

CHECK_STEPS = 3
TRACE_S = 8.0


def build(ctx):
    """The trainer, with the weights made from the seed in its own
    parameter layout and a data source drawn from the seed."""
    from repro.launch.train import TrainConfig, Trainer

    tr = ctx.mix["train"]
    with program.as_program(ctx.config, lambda c: program.zero_params(ctx.config, c)) as cfg:
        trainer = Trainer(TrainConfig(
            arch=ctx.config["program_arch"], smoke=False, steps=tr["schedule_steps"],
            global_batch=tr["global_batch"], seq_len=tr["seq_len"],
            collectives=tr["collectives"], bucket_bytes=tr.get("bucket_bytes"),
            tp=tr["tp"], ckpt_dir=None, seed=0, log_every=1 << 30,
        ))
    zeros = trainer.state["params"]
    trainer.state["params"] = initial_params(ctx, trainer, cfg)
    program.free(zeros)
    trainer.source = batches(ctx)
    return trainer


def initial_params(ctx, trainer, cfg):
    ref = program.reference(ctx.config)
    return program.seeded_init(ctx.config, cfg, trainer.param_sh)(ref.seed_key(ctx.seed))


def batches(ctx) -> traffic.TrainBatches:
    tr = ctx.mix["train"]
    return traffic.TrainBatches(ctx.config["model"]["vocab_size"], tr["seq_len"],
                                tr["global_batch"], tr["data"]["branch"], ctx.seed)


def opt_dict(trainer) -> dict:
    o = trainer.opt_cfg
    return {**dataclasses.asdict(o), "z_loss": 1e-4}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def program_readings(trainer, ctx) -> dict:
    """Three steps through ``Trainer.run``: losses, first-gradient norms
    from the first moment, and change norms after the third step."""
    cfg = program.model_config(ctx.config)
    first = trainer.run(1)
    t = time.perf_counter()
    b1 = trainer.opt_cfg.b1
    g1 = jax.tree.map(lambda n: float(n) / (1 - b1), _norms(trainer.state["opt"]["mu"]))
    ctx.exclude(time.perf_counter() - t)
    rest = trainer.run(CHECK_STEPS - 1)
    t = time.perf_counter()
    p0 = initial_params(ctx, trainer, cfg)
    change = jax.tree.map(float, _diff_norms(trainer.state["params"], p0))
    program.free(p0)
    ctx.exclude(time.perf_counter() - t)
    return {"loss": first["losses"] + rest["losses"], "grad": g1, "change": change}


def reference_readings(ctx, opt: dict, quant: str | None = None, rows: slice | None = None) -> dict:
    """The plain reference's three steps on every chip of the cell, each
    leaf sharded along its last dimension."""
    model, ref = ctx.config["model"], program.reference(ctx.config)
    n = ctx.cell["chips"]
    mesh = Mesh(np.array(jax.devices()[:n]), ("x",))

    def spec(shape):
        for ax in range(len(shape) - 1, -1, -1):
            if shape[ax] % n == 0:
                return NamedSharding(mesh, P(*([None] * ax + ["x"])))
        return NamedSharding(mesh, P())

    shard = jax.tree.map(spec, ref.shapes(model), is_leaf=lambda s: isinstance(s, tuple))
    w0 = jax.jit(lambda key: ref.init(key, model), out_shardings=shard)(ref.seed_key(ctx.seed))
    zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w), out_shardings=shard)
    w, mu, nu = w0, zeros(w0), zeros(w0)
    data = batches(ctx)
    fn = jax.jit(lambda w, mu, nu, t, y, step: ref.train_step(
        w, mu, nu, t, y, step, model=model, opt=opt, quant=quant),
        out_shardings=(shard, shard, shard, None, None), donate_argnums=(1, 2))
    losses, g1 = [], None
    for step in range(1, CHECK_STEPS + 1):
        b = data.batch(step - 1)
        toks, labels = b["tokens"], b["labels"]
        if rows is not None:
            toks, labels = toks[rows], labels[rows]
        w, mu, nu, loss, gn = fn(w, mu, nu, jnp.asarray(toks), jnp.asarray(labels), jnp.int32(step))
        losses.append(float(loss))
        if step == 1:
            g1 = jax.tree.map(float, gn)
    change = jax.tree.map(float, _diff_norms(w, w0))
    program.free(w, mu, nu, w0)
    cfg = program.model_config(ctx.config)
    return {"loss": losses, "grad": ref.to_program_params(g1, cfg),
            "change": ref.to_program_params(change, cfg)}


def norm_gap(got, ref) -> float:
    """Worst leaf: |‖got‖ - ‖ref‖| over max(‖ref‖, median leaf ‖ref‖).
    Leaves whose reference norm is under a thousandth of the median
    leaf's (nought to rounding) are left out."""
    g, r = np.array(jax.tree.leaves(got)), np.array(jax.tree.leaves(ref))
    med = float(np.median(r))
    keep = r >= 1e-3 * med
    return float(np.max(np.abs(g[keep] - r[keep]) / np.maximum(r[keep], med)))


def compare(got: dict, ref: dict) -> dict:
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])),
        "grad_gap": norm_gap(got["grad"], ref["grad"]),
        "change_gap": norm_gap(got["change"], ref["change"]),
    }


def run(ctx) -> dict:
    model, tr = ctx.config["model"], ctx.mix["train"]
    trainer = build(ctx)
    got = program_readings(trainer, ctx)
    tokens_per_step = tr["global_batch"] * tr["seq_len"]
    step_flops = program.reference(ctx.config).train_flops_per_token(model, tr["seq_len"]) * tokens_per_step
    spans: list[Span] = []
    ctx.window_opens()
    tracer = ctx.tracer(max(0.0, ctx.seconds - TRACE_S), ctx.seconds) if ctx.trace else None
    t0 = time.perf_counter()
    steps = 0
    while True:
        now = time.perf_counter() - t0
        if tracer is not None:
            tracer.tick(now)
        if now >= ctx.seconds:
            break
        s0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.train_step"):
            trainer.run(1)
        spans.append(Span("train_step", s0, time.perf_counter(), 1, step_flops))
        steps += 1
    elapsed = time.perf_counter() - t0
    if tracer is not None:
        tracer.close()
    ctx.window_closed()
    ctx.read_memory()
    opt = opt_dict(trainer)
    program.free(trainer.state)
    del trainer
    gc.collect()
    t = time.perf_counter()
    ref = reference_readings(ctx, opt)
    checks = compare(got, ref)
    ctx.note(f"reference: {time.perf_counter() - t:.1f} s; losses program {got['loss']} "
             f"reference {ref['loss']}")
    return {
        "attempted": steps,
        "failed": 0,
        "end_to_end": {"train_tokens_per_s": steps * tokens_per_step / elapsed},
        "checks": checks,
        "spans": spans,
        "facts": {"steps": steps, "tokens_per_step": tokens_per_step,
                  "model_flops_per_step": step_flops},
        "counts": {},
    }
