"""``chip_smoke.py`` rehearsed on the CPU at smoke sizes.

The script's phases run here on the reduced configs, through the same
functions the chip run calls; its entry point must refuse to run
without a TPU and print no result line.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run_script(cwd: str, *args: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _has_ok_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return bool(json.loads(lines[-1]).get("ok"))
    except (json.JSONDecodeError, AttributeError):
        return False


@pytest.mark.parametrize("args", [(), ("--four-chips",)])
def test_refuses_without_a_tpu(args):
    proc = _run_script(REPO, *args)
    assert proc.returncode != 0
    assert not _has_ok_line(proc.stdout)
    assert "no TPU" in proc.stderr


def test_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path))
    assert proc.returncode != 0
    assert not _has_ok_line(proc.stdout)


def test_serve_phase_at_smoke_size(monkeypatch, capsys):
    """Miss run, registered prefix, mixed traffic; the checks inside the
    phase raise on any mismatch. The smoke config's 8-token sliding
    window would make the KV cache a ring, which the prefix multicast
    does not take, so the window is widened here."""
    from repro.configs import h2o_danube_1_8b as h2o

    monkeypatch.setattr(h2o, "SMOKE_CONFIG", dataclasses.replace(
        h2o.SMOKE_CONFIG, sliding_window=None))
    chip_smoke.serve_phase(smoke=True, prefix_len=16, suffix_len=8, max_new=6)
    out = capsys.readouterr().out
    assert "check passed: every request finished" in out
    assert "smoke reading serve.generated_tokens: 48" in out


def test_hit_miss_gap_fails_misplaced_kv(monkeypatch):
    """A prefix hit's teacher-forced logits stay within the bound of the
    miss path's; KV rows a page late, or a page holding the first page's
    rows, exceed it."""
    from repro.configs import h2o_danube_1_8b as h2o
    from repro.launch.serve import ServeConfig, Server

    monkeypatch.setattr(h2o, "SMOKE_CONFIG", dataclasses.replace(
        h2o.SMOKE_CONFIG, sliding_window=None))
    server = Server(ServeConfig(arch=chip_smoke.ARCH, smoke=True,
                                prompt_len=40, max_seq=48, replicas=1))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, server.cfg.vocab_size, 40).astype(np.int32)
    entry = server.register_prefix(prompt[:32])
    req = server.submit(prompt, 6)
    server.run([req])
    gap = chip_smoke.hit_miss_gap(server, prompt, entry.dense, req.out)
    assert gap <= chip_smoke.HIT_MISS_GAP
    faults = chip_smoke.misplaced_kv(entry.dense, server.sc.page_size)
    for name in ("kv_one_page_late", "last_page_wrong"):
        bad = chip_smoke.hit_miss_gap(server, prompt, faults[name], req.out)
        assert bad > chip_smoke.HIT_MISS_GAP, (name, bad)


def test_four_chip_phases_on_virtual_devices(run_multidevice):
    """Collectives against XLA and the oracle, then torrent vs xla
    train steps, on 4 virtual CPU devices at smoke sizes."""
    out = run_multidevice(f"""
    import sys
    sys.path.insert(0, {REPO!r})
    import chip_smoke as cs
    cs.collectives_phase(sizes=(64 * 1024, 64 * 1024 + 12),
                         oracle_bytes=4096 + 12)
    cs.train_phase(smoke=True, steps=3, global_batch=4, seq_len=32,
                   bucket_bytes=64 * 1024)
    """, devices=4, timeout=900)
    assert out.count("check passed:") == 5
    assert "reduced gradients agree leaf by leaf" in out


@pytest.mark.parametrize("fault", ["no_dp_mean", "one_rank_only"])
def test_train_check_fails_a_faulty_reduce(run_multidevice, fault):
    """A chain all-reduce that skips the mean over the DP ranks, or one
    that keeps a single rank's gradients, fails the gradient check (the
    losses of 3 steps move too little to show either)."""
    out = run_multidevice(f"""
    import sys
    sys.path.insert(0, {REPO!r})
    import chip_smoke as cs
    from repro.core import chainwrite as cw

    right = cw.chain_all_reduce

    def faulty(x, axis, *args, **kw):
        if {fault!r} == "no_dp_mean":
            return right(x, axis, *args, **kw) * jax.lax.axis_size(axis)
        return jax.lax.all_gather(x, axis)[0] * jax.lax.axis_size(axis)

    cw.chain_all_reduce = faulty
    try:
        cs.train_phase(smoke=True, steps=3, global_batch=4, seq_len=32,
                       bucket_bytes=64 * 1024)
    except AssertionError as e:
        assert "reduced gradients part" in str(e), e
        print("caught")
    """, devices=4, timeout=900)
    assert "caught" in out
