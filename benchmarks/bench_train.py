"""Bucketed, backward-overlapped gradient reduction benchmark on the
DeepSeek configs: modeled overlap timeline vs the executed step.

Three claims, each asserted (BENCH=1 ci.sh runs this):

* **EXACT wire bytes** — the bucketed ``torrent_grad_reduce`` path's
  HLO collective bytes (trip-count-aware parse, 8 virtual devices) must
  equal ``roofline.modeled_train_overlap``'s ``total_wire_bytes`` to
  the byte: the model prices the very same per-bucket
  ``plan_all_reduce`` programs (chunk-aligned padded payloads, the same
  ``resolve_ring_chains`` auto-K) the executor runs. Checked for both
  DeepSeek archs at the f32 wire and for the int8 wire.
* **Modeled overlap wins** — on the FULL (non-smoke) DeepSeek configs
  at production ring size, the overlapped step time
  (``overlap_timeline``: bucket i's reduction starts at
  max(backward-ready_i, NoC-free)) is strictly below the serial step
  time (all comm after backward), with efficiency = hidden/total comm
  in (0, 1].
* **HLO overlap evidence** — the bucketed train step's HLO shows the
  dispatch interleaving: collective -> compute -> collective patterns
  (and any async start/done pairs XLA emits) counted by
  ``hlo_breakdown.overlap_stats``; the bucketed step must interleave
  at least as much as it has buckets.

``main()`` writes ``BENCH_train.json`` at the repo root — measured
step wall time (serial vs bucketed, CPU-portable only as a smoke
number), the modeled timelines, bucket count/bytes, and the HLO
async/interleaving counts — so training perf is tracked across PRs
like the collectives and serving lanes. Run standalone:

    PYTHONPATH=src python -m benchmarks.bench_train
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ARCHS = ("deepseek-v2-lite-16b", "deepseek-moe-16b")
STEP_ARCH = "deepseek-v2-lite-16b"  # full-step timing twin
L = 8  # virtual devices (smoke execution ring)
BB_SMOKE = 1 << 18  # 256 KiB buckets over the ~1 MB smoke grad tree
TOKENS_SMOKE = 32  # per-device tokens of the smoke step (8*32/8)
FULL_RING = 16  # production "data" axis (launch.mesh single pod)
BB_FULL = 128 << 20
TOKENS_FULL = 65536  # per-device tokens/step at seq 4k

_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile, time
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import configs as C
from repro.launch import hlo_cost
from repro.launch.hlo_breakdown import overlap_stats
from repro.launch.mesh import make_host_mesh
from repro.launch.train import TrainConfig, Trainer
from repro.models import transformer as T
from repro.parallel.collectives import torrent_grad_reduce

ARCHS = ("deepseek-v2-lite-16b", "deepseek-moe-16b")
STEP_ARCH = "deepseek-v2-lite-16b"
BB = 1 << 18
ITERS = 3

out = {"reduce": {}, "step": {}}
mesh = make_host_mesh(model=1)
batch_specs = {"d": P("data", None)}
dummy = {"d": jnp.zeros((8, 1), jnp.float32)}


def reduce_case(arch, wire):
    # The bucketed DP reduction in isolation: its HLO holds ONLY the
    # chain ppermutes, so the trip-count-aware collective-byte parse is
    # the exact wire of the bucketed path (metrics dict empty -> no
    # psum; params replicated -> no resharding collectives).
    cfg = C.get_smoke_config(arch)
    shapes = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    red = torrent_grad_reduce(
        lambda p, b: (p, {}), mesh, batch_specs,
        num_chains="auto", wire_dtype=wire, bucket_bytes=BB,
    )
    jitted = jax.jit(lambda p, b: red(p, b)[0])
    ones = jax.tree.map(lambda s: jnp.ones(s.shape, s.dtype), shapes)
    with jax.set_mesh(mesh):
        text = jitted.lower(ones, dummy).compile().as_text()
        if wire is None:
            # exact wire: 8 local all-ones grads -> sum 8 / dp 8 == 1.0
            got = jitted(ones, dummy)
            for leaf in jax.tree.leaves(got):
                np.testing.assert_array_equal(np.asarray(leaf), 1.0)
    cost = hlo_cost.analyze(text)
    return {
        "hlo_bytes": int(cost.coll_bytes),
        "coll": {k: int(v) for k, v in cost.coll.items() if v},
    }


for arch in ARCHS:
    out["reduce"][arch] = reduce_case(arch, None)
out["reduce"][STEP_ARCH + "__int8"] = reduce_case(STEP_ARCH, "int8")


def step_case(bb):
    tc = TrainConfig(
        arch=STEP_ARCH, smoke=True, steps=1, global_batch=8, seq_len=32,
        collectives="torrent", bucket_bytes=bb, loss_chunks=2,
        ckpt_dir=tempfile.mkdtemp(),
    )
    tr = Trainer(tc)
    batch = tr._device_batch(0)
    with jax.set_mesh(tr.mesh):
        compiled = tr.step_fn.lower(
            tr.state["params"], tr.state["opt"], batch
        ).compile()
        text = compiled.as_text()
        p, o, m = compiled(tr.state["params"], tr.state["opt"], batch)
        jax.block_until_ready(m)
        t0 = time.perf_counter()
        for _ in range(ITERS):
            p, o, m = compiled(p, o, batch)
        jax.block_until_ready(m)
        us = (time.perf_counter() - t0) / ITERS * 1e6
    cost = hlo_cost.analyze(text)
    return {
        "us": us,
        "loss": float(m["loss"]),
        "coll": {k: int(v) for k, v in cost.coll.items() if v},
        "overlap_stats": overlap_stats(text),
    }


out["step"]["serial"] = step_case(None)
out["step"]["bucketed"] = step_case(BB)
print(json.dumps(out))
"""


def _modeled_smoke(arch: str, wire: str | None) -> dict:
    """The modeled twin of the subprocess's reduce_case — same leaves,
    same ring, same bucket size, same auto-K resolution."""
    import jax

    from repro import configs as C
    from repro.launch.roofline import modeled_train_overlap
    from repro.models import transformer as T

    cfg = C.get_smoke_config(arch)
    leaves = jax.tree.leaves(
        jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    )
    return modeled_train_overlap(
        leaves, L, TOKENS_SMOKE, bucket_bytes=BB_SMOKE,
        num_chains="auto", wire_dtype=wire,
    )


def _modeled_full(arch: str) -> dict:
    """Production-scale modeled timeline: FULL config leaves on the
    16-ring, where backward compute is long enough that overlapping
    the bucket reductions visibly shortens the modeled step."""
    import jax

    from repro import configs as C
    from repro.launch.roofline import modeled_train_overlap
    from repro.models import transformer as T

    cfg = C.get_config(arch)
    leaves = jax.tree.leaves(
        jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    )
    m = modeled_train_overlap(
        leaves, FULL_RING, TOKENS_FULL, bucket_bytes=BB_FULL,
        num_chains="auto",
    )
    # keep the JSON tractable: summarize the (many) bucket records
    buckets = m.pop("buckets")
    m["num_buckets"] = len(buckets)
    m["bucket_bytes"] = BB_FULL
    m["tokens_per_device"] = TOKENS_FULL
    m["ring"] = FULL_RING
    m.pop("timeline", None)
    return m


def main() -> list[tuple[str, float, str]]:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    env["JAX_PLATFORMS"] = "cpu"  # virtual CPU devices; never the chip
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET], capture_output=True, text=True,
        env=env, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    sub = json.loads(proc.stdout.strip().splitlines()[-1])

    rows: list[tuple[str, float, str]] = []
    metrics: dict = {"reduce": {}, "step": sub["step"], "modeled_full": {}}

    # -- EXACT: modeled wire bytes == the bucketed path's HLO bytes ----
    for key, wire in [(a, None) for a in ARCHS] + [
        (STEP_ARCH + "__int8", "int8")
    ]:
        arch = key.split("__")[0]
        m = _modeled_smoke(arch, wire)
        hlo = sub["reduce"][key]
        assert m["total_wire_bytes"] == hlo["hlo_bytes"], (key, m, hlo)
        metrics["reduce"][key] = {
            "hlo_bytes": hlo["hlo_bytes"],
            "modeled_bytes": m["total_wire_bytes"],
            "num_buckets": len(m["buckets"]),
            "buckets": m["buckets"],
        }
        rows.append((
            f"train.reduce_exact.{key}", 0.0,
            f"wire_bytes={hlo['hlo_bytes']} buckets={len(m['buckets'])}",
        ))

    # -- modeled overlap beats modeled serial on the full configs ------
    for arch in ARCHS:
        m = _modeled_full(arch)
        assert m["overlap_cc"] < m["serial_cc"], (arch, m)
        assert 0.0 < m["efficiency"] <= 1.0, (arch, m)
        assert m["num_buckets"] > 1, (arch, m)
        metrics["modeled_full"][arch] = m
        rows.append((
            f"train.modeled_overlap.{arch}", float(m["overlap_cc"]),
            f"serial_cc={m['serial_cc']} eff={m['efficiency']:.3f} "
            f"buckets={m['num_buckets']}",
        ))

    # -- HLO overlap evidence in the executed bucketed step ------------
    ov = sub["step"]["bucketed"]["overlap_stats"]
    n_buckets = len(metrics["reduce"][STEP_ARCH]["buckets"])
    assert ov["collectives"] > 0, ov
    assert ov["interleavings"] >= n_buckets, (ov, n_buckets)
    for kind in ("serial", "bucketed"):
        s = sub["step"][kind]
        rows.append((
            f"train.step_{kind}", s["us"],
            f"interleavings={s['overlap_stats']['interleavings']} "
            f"async_pairs={s['overlap_stats']['async_done']}",
        ))
    # both steps train: finite loss from the same data pipeline
    import math

    assert math.isfinite(sub["step"]["serial"]["loss"])
    assert math.isfinite(sub["step"]["bucketed"]["loss"])

    with open(os.path.join(repo, "BENCH_train.json"), "w") as f:
        json.dump(metrics, f, indent=2, sort_keys=True)
        f.write("\n")
    rows.append((
        "train.subprocess_s", (time.perf_counter() - t0) * 1e6,
        "8 virtual devices",
    ))
    return rows


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.2f},{derived}")
