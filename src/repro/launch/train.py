"""End-to-end fault-tolerant training driver.

Composes every substrate layer: data pipeline (Markov source), model
(any assigned arch), AdamW + ZeRO-1 specs, mesh + shardings, Torrent or
XLA collectives, async checkpointing with restart-on-failure, straggler
monitoring, and optional elastic rescale between runs.

CLI (see examples/train_lm.py for the library-level API):

    PYTHONPATH=src python -m repro.launch.train \
        --arch yi-6b --smoke --steps 200 --batch 8 --seq 128 \
        --collectives torrent --ckpt-dir /tmp/run0
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as C
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import MarkovSource, Prefetcher
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import _named, _sanitize, make_train_step
from repro.models import transformer as T
from repro.optim import adamw
from repro.parallel import ef_residual_init, ef_residual_specs
from repro.parallel import sharding as shd
from repro.runtime.failure import FaultInjector, resilient_loop
from repro.runtime.monitor import StepMonitor
from repro.runtime.tracing import span

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    arch: str = "yi-6b"
    smoke: bool = True
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    peak_lr: float = 1e-3
    warmup_steps: int = 20
    collectives: str = "xla"  # "xla" | "torrent"
    compress_grads: bool = False
    bucket_bytes: int | None = None  # bucketed backward-overlapped reduce
    topology: str | None = None  # tiered link-graph spec (torrent auto-K)
    remat: str = "dots"
    loss_chunks: int = 4
    microbatches: int = 1  # gradient accumulation (HBM-fit lever)
    ckpt_dir: str | None = "/tmp/repro_ckpt"  # None: no checkpoints
    ckpt_every: int = 50
    keep_last_k: int = 3
    tp: int = 1
    seed: int = 0
    log_every: int = 10
    fail_at: tuple[int, ...] = ()  # fault-injection (tests/demos)


class Trainer:
    """Owns mesh, sharded state, step function and the resilient loop."""

    def __init__(self, tc: TrainConfig):
        self.tc = tc
        self.cfg = (
            C.get_smoke_config(tc.arch) if tc.smoke else C.get_config(tc.arch)
        )
        self.mesh = make_host_mesh(model=tc.tp)
        self.opt_cfg = adamw.OptConfig(
            peak_lr=tc.peak_lr,
            warmup_steps=tc.warmup_steps,
            decay_steps=max(tc.steps, tc.warmup_steps + 1),
        )
        self.source = MarkovSource(
            vocab=self.cfg.vocab_size,
            seq_len=tc.seq_len,
            global_batch=tc.global_batch,
            seed=tc.seed + 1,
        )
        self.monitor = StepMonitor()
        self.step = 0  # steps taken so far, over every call of run()
        self._build()

    # -- state / step ----------------------------------------------------
    def _build(self):
        tc, cfg, mesh = self.tc, self.cfg, self.mesh
        params_shape = jax.eval_shape(
            lambda: T.model_init(jax.random.PRNGKey(tc.seed), cfg)
        )
        pspecs = shd.param_pspecs(params_shape, cfg, tp=mesh.shape["model"])
        ospecs = shd.opt_pspecs(pspecs, params_shape, mesh.shape["data"])
        self.param_sh = _named(mesh, pspecs)
        self.opt_sh = _named(mesh, ospecs)
        self.batch_spec = P("data", None)
        self.batch_sh = NamedSharding(mesh, _sanitize(self.batch_spec, mesh))

        with jax.set_mesh(mesh):
            params = jax.jit(
                lambda: T.model_init(jax.random.PRNGKey(tc.seed), cfg),
                out_shardings=self.param_sh,
            )()
            opt = jax.jit(
                lambda: adamw.init(params), out_shardings=self.opt_sh
            )()
        self.state = {"params": params, "opt": opt}
        if tc.compress_grads:
            # Error-feedback residual rides in the training state so it
            # survives checkpoint/restart like the optimizer moments do.
            dp_size = int(np.prod(
                [mesh.shape[a] for a in mesh.axis_names if a != "model"]
            ))
            self.ef_sh = _named(mesh, ef_residual_specs(mesh, params_shape))
            with jax.set_mesh(mesh):
                self.state["ef"] = jax.jit(
                    lambda: ef_residual_init(params_shape, dp_size),
                    out_shardings=self.ef_sh,
                )()

        bspecs = {"tokens": self.batch_spec, "labels": self.batch_spec}
        step = make_train_step(
            cfg,
            self.opt_cfg,
            remat=tc.remat,
            collectives=tc.collectives,
            compress_grads=tc.compress_grads,
            error_feedback=tc.compress_grads,
            bucket_bytes=tc.bucket_bytes,
            topology=tc.topology,
            mesh=mesh,
            batch_specs={
                k: _sanitize(v, mesh) for k, v in bspecs.items()
            },
            loss_chunks=tc.loss_chunks,
            microbatches=tc.microbatches,
        )
        batch_sh = {"tokens": self.batch_sh, "labels": self.batch_sh}
        if tc.compress_grads:
            self.step_fn = jax.jit(
                step,
                in_shardings=(
                    self.param_sh, self.opt_sh, self.ef_sh, batch_sh
                ),
                out_shardings=(
                    self.param_sh, self.opt_sh, self.ef_sh, None
                ),
                donate_argnums=(0, 1, 2),
            )
        else:
            self.step_fn = jax.jit(
                step,
                in_shardings=(self.param_sh, self.opt_sh, batch_sh),
                out_shardings=(self.param_sh, self.opt_sh, None),
                donate_argnums=(0, 1),
            )

    def _device_batch(self, step: int) -> dict:
        host = self.source.batch(step)
        return {
            k: jax.device_put(v, self.batch_sh) for k, v in host.items()
        }

    # -- driver ------------------------------------------------------------
    def run(self, steps: int | None = None) -> dict[str, Any]:
        """Take ``steps`` more steps (default ``tc.steps``), going on
        from where the previous call ended."""
        tc = self.tc
        start = self.step
        end = start + (tc.steps if steps is None else steps)
        ckpt = (
            CheckpointManager(tc.ckpt_dir, keep_last_k=tc.keep_last_k)
            if tc.ckpt_dir is not None else None
        )
        injector = FaultInjector(tc.fail_at)
        losses: list[float] = []
        grad_norms: list[float] = []

        def one_step(state, i):
            injector.maybe_fail(i)
            # the straggler monitor times exactly the train.step span
            with span("train.step"):
                self.monitor.start_step()
                with span("train.batch"):
                    batch = self._device_batch(i)
                with jax.set_mesh(self.mesh):
                    if "ef" in state:
                        params, opt, ef, metrics = self.step_fn(
                            state["params"], state["opt"], state["ef"], batch
                        )
                    else:
                        params, opt, metrics = self.step_fn(
                            state["params"], state["opt"], batch
                        )
                        ef = None
                with span("train.sync"):
                    loss = float(metrics["loss"])
                    grad_norm = float(metrics["grad_norm"])
                ev = self.monitor.end_step(i)
            if ev is not None:
                log.warning(
                    "straggler step %d: %.3fs (median %.3fs)",
                    ev.step, ev.duration_s, ev.median_s,
                )
            if i % tc.log_every == 0:
                log.info("step %5d loss %.4f lr %.2e", i, loss,
                         float(metrics["lr"]))
            losses.append(loss)
            grad_norms.append(grad_norm)
            new_state = {"params": params, "opt": opt}
            if ef is not None:
                new_state["ef"] = ef
            return new_state, {"loss": loss}

        t0 = time.time()
        state, result = resilient_loop(
            state=self.state,
            step_fn=one_step,
            num_steps=end,
            ckpt=ckpt,
            ckpt_every=tc.ckpt_every,
            start_step=start,
        )
        wall = time.time() - t0
        if ckpt is not None:
            ckpt.close()
        self.state = state
        self.step = result.final_step
        return {
            "final_step": result.final_step,
            "restarts": result.restarts,
            "losses": losses,
            "grad_norms": grad_norms,  # global norm of the reduced grads
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "wall_s": wall,
            "straggler_events": len(self.monitor.events),
            "tokens_per_s": (
                (end - start) * tc.global_batch * tc.seq_len / wall
                if wall else 0
            ),
        }


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="yi-6b", choices=C.ARCHS)
    p.add_argument("--smoke", action="store_true", default=False)
    p.add_argument("--full", dest="smoke", action="store_false")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--collectives", choices=("xla", "torrent"), default="xla")
    p.add_argument("--compress-grads", action="store_true", default=False,
                   help="int8 wire for the DP gradient all-reduce with "
                        "error-feedback residuals (requires --collectives "
                        "torrent)")
    p.add_argument("--bucket-mb", type=float, default=None,
                   help="bucket size (MiB) for the bucketed, backward-"
                        "overlapped DP grad reduce (requires --collectives "
                        "torrent)")
    p.add_argument("--topology", default=None,
                   help="tiered link-graph spec for auto-K ring planning, "
                        "e.g. 'pods=2:interpod_bw=0.25' (requires "
                        "--collectives torrent)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--remat", default="dots")
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--fail-at", default="",
                   help="comma-separated steps for fault injection demo")
    args = p.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()
    tc = TrainConfig(
        arch=args.arch, smoke=args.smoke, steps=args.steps,
        global_batch=args.batch, seq_len=args.seq, peak_lr=args.lr,
        collectives=args.collectives, compress_grads=args.compress_grads,
        bucket_bytes=(
            int(args.bucket_mb * (1 << 20)) if args.bucket_mb else None
        ),
        topology=args.topology,
        tp=args.tp, remat=args.remat,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        fail_at=tuple(int(s) for s in args.fail_at.split(",") if s),
    )
    out = Trainer(tc).run()
    log.info(
        "done: %d steps (%d restarts)  loss %.4f -> %.4f  %.1f tok/s",
        out["final_step"], out["restarts"], out["first_loss"],
        out["last_loss"], out["tokens_per_s"],
    )
    return out


if __name__ == "__main__":
    main()
