"""Readings from which a cell's correctness limits are set, for many
seeds in one process, each judged by the harness's own comparison
against the cell's limits in ``bench/limits/``.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Serving cells: for each seed, a run of the cell's own loop at the
cell's load for ``--seconds``, then the program's numbers and the
control's: the same numbers with the logit gap of the token that the
reference computed from float8 operands puts first, at the same
positions, in place of the served token's.

Training cells: for each seed, the program's first three steps and the
reference's, and the same comparison for the float8 reference (the
control) and for the reference that leaves half the batch out (with two
data-parallel ranks the same readings as a reduce that leaves out the
exchange, read on the first chip).

One JSON line per seed: for the program and for each control or fault,
``correct`` as the harness decides it and each number beside its limit.
The limits lie between the largest program reading and the smallest
control or fault reading; a control or fault must come out not correct.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

from bench import run as R  # noqa: E402


def serve_rows(ctx) -> dict:
    from bench.loops import serve_open_loop as S

    ctx.control = "fp8"
    out = S.run(ctx)
    gap = out["control_gap"]
    control = dict(out["checks"], logit_gap=math.inf if gap is None else gap)
    return {"program": verdict(out["checks"], ctx), "control_fp8": verdict(control, ctx)}


def train_rows(ctx) -> dict:
    from bench.loops import train_loop as T
    from bench.lib import program

    trainer = T.build(ctx)
    got = T.program_readings(trainer, ctx)
    opt = T.opt_dict(trainer)
    program.free(trainer.state)
    del trainer
    gc.collect()
    ref = T.reference_readings(ctx, opt)
    low = T.reference_readings(ctx, opt, quant="fp8")
    half = T.reference_readings(ctx, opt, rows=slice(0, ctx.mix["train"]["global_batch"] // 2))
    return {"program": verdict(T.compare(got, ref), ctx),
            "control_fp8": verdict(T.compare(low, ref), ctx),
            "fault_half_batch": verdict(T.compare(half, ref), ctx)}


def verdict(values: dict, ctx) -> dict:
    correct, checks = R.judge(values, ctx.limits)
    return {"correct": correct, "checks": checks}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    args = p.parse_args(argv)
    R._paths()
    R.cache_on()
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = R.Context(args.workload, seed, args.seconds, False)
        R.require_chip(ctx.cell["chips"])
        rows = serve_rows(ctx) if ctx.mix["loop"] == "serve_open_loop" else train_rows(ctx)
        print(json.dumps({"seed": seed, **rows}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
