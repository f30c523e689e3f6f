"""The lower-precision control at smoke size, judged by the harness's own
comparison: the float8 reference, put in the program's place, comes out
not correct where the program comes out correct (on the chip, at the
cells' sizes and against the cells' own limits: PERF.md)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from bench import control
from bench import run as R
from bench.tests import smoke


def test_serve_control_reads_far_above_the_program():
    R._paths()
    ctx = R.Context("serve.h2o-danube-1.8b.prefix-chat", 2**32 + 5, 1.0, False,
                    smoke.config("h2o-danube-1.8b"), smoke.mix("prefix-chat"))
    # smoke widths give small logits: a limit for their size (the program
    # reads 0 here, the control above 0.02)
    ctx.limits = dict(ctx.limits, logit_gap=0.01)
    rows = control.serve_rows(ctx)
    sound, low = rows["program"], rows["control_fp8"]
    assert sound["correct"] and not low["correct"], rows
    assert low["checks"]["logit_gap"]["value"] > 3 * sound["checks"]["logit_gap"]["value"]
    assert low["checks"]["unfinished"] == sound["checks"]["unfinished"]


def test_train_control_and_half_batch_read_far_above_the_program(tmp_path):
    root = smoke.checkout_with_train(tmp_path)
    code = (
        "import os, sys, json\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        f"sys.path[:0] = [{root!r}, {os.path.join(smoke.ROOT, 'src')!r}]\n"
        "from bench import control, run as R\n"
        "from bench.tests import smoke\n"
        "R._paths()\n"
        f"ctx = R.Context({smoke.TRAIN_CELL!r}, 2**32 + 7, 1.0, False,\n"
        "                smoke.config('h2o-danube-1.8b'), smoke.mix('dp2-tp2'))\n"
        "print(json.dumps(control.train_rows(ctx)))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    sound = rows["program"]
    assert sound["correct"], rows
    for broken in (rows["control_fp8"], rows["fault_half_batch"]):
        assert not broken["correct"], rows
