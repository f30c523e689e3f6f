"""Chainwrite vs XLA-native collectives on the TPU-analogue mesh:
wall-clock on 8 virtual CPU devices (subprocess) + HLO wire bytes.

This is the JAX-side counterpart of Fig. 5: the "network-layer
multicast" baseline is XLA's built-in all-reduce/all-gather; "Torrent"
is the scheduled ppermute chain. On CPU the wall-clock ratio is not
meaningful for TPU — the *collective wire bytes* (trip-count-aware HLO
parse) are the portable metric and must match the ChainProgram IR's
``program_wire_bytes`` prediction for every collective × K:

* all-reduce — ``rotation`` must match the (S+K-2)-payload/device
  prediction and ``rs_ag`` (fused per-ring reduce-scatter/all-gather +
  cross-ring shard rotation) must match (2·(S-1)+(K-1))/S·payload and
  land strictly below its rotation twin;
* reduce-scatter / all-gather / all-to-all — the K-ring schedules must
  match the single ring's bytes exactly (the planner redistributes
  hops, not bytes);
* int8-wire all-reduce (``ar_int8_k{1,2,4}``) — the same rs_ag
  schedules with ``wire_dtype="int8"``: int8 frames plus one f32 scale
  per hop (~4x fewer payload bytes), matched exactly by the IR's
  int8-aware ``Step.bytes`` model;
* multi-chain broadcast (K=2) is timed against the single chain.

Besides the CSV rows, ``main()`` writes ``BENCH_collectives.json`` at
the repo root — per-benchmark ``{us, hlo_bytes, modeled_bytes,
modeled_latency_cc}`` from the very same IR the executors run — so the
perf trajectory is tracked across PRs. Model-only ``recovery_k{K}_f{N}``
entries (no HLO twin) record the ``plan_recovery`` program's wire bytes
and ``chain_recovery_latency`` completion for K ∈ {2, 4} partitions
with one and two concurrent failures, asserted self-consistent against
the failure-free model.

Model-only ``plan_L{64,256,1024}`` entries track the symbolic-addressing
scaling pin: cold plan+validate wall time and pickled program bytes for
the K=8 all-to-all at each ring length, plus ``plan_hlo_const_bytes`` —
the executor's compiled-HLO literal-constant footprint measured at L=8
and L=16 virtual devices and hard-asserted EQUAL (addresses are
computed in-kernel from the device index, so the constant footprint is
ring-length-independent).
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import time

L = 8
N = 1 << 18  # 256k f32 per device = 1 MiB
RINGS = {
    1: ((0, 1, 2, 3, 4, 5, 6, 7),),
    2: ((0, 1, 2, 3), (4, 5, 6, 7)),
    4: ((0, 1), (2, 3), (4, 5), (6, 7)),
}
BCAST_CHAINS = ((1, 2, 3), (4, 5, 6, 7))

_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import chainwrite as cw
from repro.launch import hlo_cost

L = 8
mesh = jax.make_mesh((L,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
N = 1 << 18  # 256k f32 per device = 1 MiB

def time_fn(f, *args):
    f(*args)  # compile+warm
    t0 = time.perf_counter()
    for _ in range(5):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / 5 * 1e6

x = jnp.ones((L, N), jnp.float32)

def chain_ar(x):
    return cw.chain_all_reduce(x[0], "x")[None]

def xla_ar(x):
    return jax.lax.psum(x[0], "x")[None]

RINGS = {2: [(0,1,2,3), (4,5,6,7)], 4: [(0,1), (2,3), (4,5), (6,7)]}

def multi_ar(k, algo):
    def fn(x):
        return cw.multi_chain_all_reduce(x[0], "x", RINGS[k], algo=algo)[None]
    return fn

def multi_rs(k):
    def fn(x):
        orders = RINGS[k] if k > 1 else None
        v = x[0].reshape(L, N // L)
        out = (cw.multi_chain_reduce_scatter(v, "x", orders) if k > 1
               else cw.chain_reduce_scatter(v, "x"))
        return jnp.tile(out, L)[None]
    return fn

def multi_ag(k):
    def fn(x):
        v = x[0, : N // L]
        out = (cw.multi_chain_all_gather(v, "x", RINGS[k], tiled=True) if k > 1
               else cw.chain_all_gather(v, "x", tiled=True))
        return out[None]
    return fn

def multi_a2a(k):
    def fn(x):
        v = x[0].reshape(L, N // L)
        out = (cw.multi_chain_all_to_all(v, "x", RINGS[k]) if k > 1
               else cw.chain_all_to_all(v, "x"))
        return out.reshape(N)[None]
    return fn

def int8_ar(k):
    def fn(x):
        out = (cw.multi_chain_all_reduce(
                   x[0], "x", RINGS[k], algo="rs_ag", wire_dtype="int8")
               if k > 1
               else cw.chain_all_reduce(x[0], "x", wire_dtype="int8"))
        return out[None]
    return fn

results = {}
cases = [
    ("chain_all_reduce", chain_ar),
    ("multi_chain_all_reduce_k2_rotation", multi_ar(2, "rotation")),
    ("multi_chain_all_reduce_k2_rs_ag", multi_ar(2, "rs_ag")),
    ("multi_chain_all_reduce_k4_rotation", multi_ar(4, "rotation")),
    ("multi_chain_all_reduce_k4_rs_ag", multi_ar(4, "rs_ag")),
    ("xla_all_reduce", xla_ar),
]
for k in (1, 2, 4):
    cases += [
        (f"multi_chain_reduce_scatter_k{k}", multi_rs(k)),
        (f"multi_chain_all_gather_k{k}", multi_ag(k)),
        (f"multi_chain_all_to_all_k{k}", multi_a2a(k)),
    ]
for k in (1, 2, 4):
    # name deliberately avoids the "all_reduce" substring: the int8 wire
    # is lossy, so the exact sums-to-L check below must not apply.
    cases.append((f"ar_int8_k{k}", int8_ar(k)))
for name, fn in cases:
    sm = jax.shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    jitted = jax.jit(sm)
    us = time_fn(jitted, x)
    cost = hlo_cost.analyze(jitted.lower(x).compile().as_text())
    results[name] = (us, cost.coll_bytes)
    if "all_reduce" in name:  # correctness: every element sums to L
        np.testing.assert_allclose(
            np.asarray(jitted(x))[0], np.full((N,), L, np.float32))
    elif name.startswith("ar_int8"):
        # lossy wire: per-hop requantization bounds the error relative
        # to the tensor max, not element-wise
        got = np.asarray(jitted(x))[0]
        err = float(np.max(np.abs(got - L)) / L)
        assert err < 0.08, (name, err)

payload = N * 4
ring_pred = 2 * (L - 1) / L * payload
chain_bytes = results["chain_all_reduce"][1]
assert 0.9 * ring_pred <= chain_bytes <= 1.35 * ring_pred, (chain_bytes, ring_pred)
# Rotation trades wire bytes for chain length: (S-1)+(K-1) full-payload
# sends/device. RS+AG keeps the short rings but moves 1/S shards:
# (2*(S-1)+(K-1))/S payloads/device — strictly below its rotation twin.
for K in (2, 4):
    S = L // K
    rot_pred = (S + K - 2) * payload
    rot_bytes = results[f"multi_chain_all_reduce_k{K}_rotation"][1]
    assert 0.9 * rot_pred <= rot_bytes <= 1.35 * rot_pred, (K, rot_bytes, rot_pred)
    rsag_pred = (2 * (S - 1) + (K - 1)) / S * payload
    rsag_bytes = results[f"multi_chain_all_reduce_k{K}_rs_ag"][1]
    assert 0.9 * rsag_pred <= rsag_bytes <= 1.35 * rsag_pred, (K, rsag_bytes, rsag_pred)
    assert rsag_bytes < rot_bytes, (K, rsag_bytes, rot_bytes)

# int8 wire: each rs_ag step ships its f32 shard as int8 plus one f32
# scale, so per-device bytes = steps * (shard_elems + 4) exactly —
# ~4x below the f32 twin (which ships steps * shard_elems * 4).
SHARDS = {1: N // 8, 2: N // 4, 4: N // 2}
for K in (1, 2, 4):
    S = L // K
    steps = 2 * (S - 1) + (K - 1)
    pred = steps * (SHARDS[K] + 4)
    got = results[f"ar_int8_k{K}"][1]
    assert got == pred, (K, got, pred)
    f32_twin = results["chain_all_reduce" if K == 1
                       else f"multi_chain_all_reduce_k{K}_rs_ag"][1]
    assert got < f32_twin / 3.5, (K, got, f32_twin)

# The K-ring reduce-scatter / all-gather / all-to-all redistribute hops,
# not bytes: every K must land on the single ring's byte count.
ring_bytes = {
    "multi_chain_reduce_scatter": (L - 1) / L * payload,
    "multi_chain_all_gather": (L - 1) / L * payload,
    "multi_chain_all_to_all": (L - 1) * payload,
}
for stem, pred in ring_bytes.items():
    for k in (1, 2, 4):
        got = results[f"{stem}_k{k}"][1]
        assert 0.9 * pred <= got <= 1.35 * pred, (stem, k, got, pred)

# P2MP broadcast: single chain vs 2 partitioned chains. The K=2 split
# buys LATENCY (10 -> 7 pipeline slots: the longest chain halves), not
# bytes — the head's per-slot fan-out costs a second ppermute, so HLO
# wire bytes RISE from 10 to 7x2 frame-payloads (both recorded in
# BENCH_collectives.json and matched exactly by pipelined_wire_bytes).
def chain_bc(x):
    return cw.chain_broadcast(x[0], "x", tuple(range(8)), num_frames=4)[None]

def multi_bc(x):
    return cw.multi_chain_broadcast(
        x[0], "x", 0, [(1, 2, 3), (4, 5, 6, 7)], num_frames=4)[None]

for name, fn in [("chain_broadcast", chain_bc), ("multi_chain_broadcast_k2", multi_bc)]:
    sm = jax.shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    jitted = jax.jit(sm)
    us = time_fn(jitted, x)
    cost = hlo_cost.analyze(jitted.lower(x).compile().as_text())
    results[name] = (us, cost.coll_bytes)
    np.testing.assert_allclose(np.asarray(jitted(x)), np.ones((L, N), np.float32))

for name, (us, cb) in results.items():
    print(f"{name},{us:.1f},{cb:.0f}")
"""


def _modeled(name: str) -> dict:
    """Modeled bytes/latency for a benchmark entry from the very same
    ChainProgram the subprocess executed (host-side: no jax needed)."""
    from repro.core import program as prg
    from repro.core.simulator import program_latency
    from repro.core.topology import MeshTopology

    topo = MeshTopology(L, 1)  # the snake-ring analogue topology
    payload = N * 4
    prog = None
    size = payload
    if name in ("chain_broadcast", "multi_chain_broadcast_k2"):
        chains = (
            (tuple(range(1, L)),) if name == "chain_broadcast" else BCAST_CHAINS
        )
        prog = prg.plan_broadcast(L, 0, chains)
        return {
            # the bench runs the frame-pipelined path (num_frames=4)
            "modeled_bytes": prg.pipelined_wire_bytes(prog, payload, 4),
            "modeled_latency_cc": program_latency(topo, 0, prog, payload),
        }
    if name.startswith("ar_int8_k"):
        k = int(name[len("ar_int8_k"):])
        prog = prg.plan_all_reduce(L, RINGS[k], "rs_ag", wire_dtype="int8")
    elif name.startswith("multi_chain_all_reduce") or name == "chain_all_reduce":
        if name == "chain_all_reduce":
            k, algo = 1, "rs_ag"
        else:
            parts = name.split("_k")[1].split("_", 1)
            k, algo = int(parts[0]), parts[1]
        prog = prg.plan_all_reduce(L, RINGS[k], "rs_ag" if k == 1 else algo)
    elif name.startswith("multi_chain_reduce_scatter"):
        prog = prg.plan_reduce_scatter(L, RINGS[int(name[-1])])
    elif name.startswith("multi_chain_all_gather"):
        prog = prg.plan_all_gather(L, RINGS[int(name[-1])])
        size = payload // L  # per-device input is one shard
    elif name.startswith("multi_chain_all_to_all"):
        prog = prg.plan_all_to_all(L, RINGS[int(name[-1])])
    if prog is None:
        return {}
    return {
        "modeled_bytes": prog.wire_bytes(size),
        "modeled_latency_cc": program_latency(topo, 0, prog, size),
    }


# Executor HLO constant footprint at a given virtual-device count: the
# K=2 all-to-all (the heaviest table user) with a fixed 32-element
# chunk, compiled and parsed for literal ``constant`` bytes.
_CONST_SNIPPET = r"""
import os, sys
L = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={L}"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import chainwrite as cw
from repro.launch import hlo_cost

mesh = jax.make_mesh((L,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
rings = (tuple(range(L // 2)), tuple(range(L // 2, L)))
C = 32  # elems per chunk, fixed across L

def a2a(x):
    v = x[0].reshape(L, C)
    return cw.multi_chain_all_to_all(v, "x", rings).reshape(L * C)[None]

x = jnp.ones((L, L * C), jnp.float32)
jitted = jax.jit(jax.shard_map(a2a, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
print(hlo_cost.constant_bytes(jitted.lower(x).compile().as_text()))
"""


def _plan_scaling_metrics(env: dict) -> dict[str, dict]:
    """Model-only symbolic-addressing scaling entries: cold
    plan+validate wall time and program pickle size for the K=8
    all-to-all at L ∈ {64, 256, 1024} (host-side, no jax), plus the
    executor's HLO constant bytes at L ∈ {8, 16} virtual devices —
    hard-asserted ring-length-independent."""
    from repro.core import program as prg

    out: dict[str, dict] = {}
    for ring_len in (64, 256, 1024):
        K = 8
        S = ring_len // K
        rings = tuple(
            tuple(range(i * S, (i + 1) * S)) for i in range(K)
        )
        prg.clear_planner_caches()
        t0 = time.perf_counter()
        prog = prg.plan_all_to_all(ring_len, rings)
        plan_s = time.perf_counter() - t0
        out[f"plan_L{ring_len}"] = {
            "plan_validate_s": plan_s,
            "program_bytes": len(pickle.dumps(prog)),
            "steps": len(prog.steps),
        }
        # "seconds, not minutes" is the acceptance bar; 30s is generous
        assert plan_s < 30.0, (ring_len, plan_s)
    const: dict[str, int] = {}
    for dev in (8, 16):
        proc = subprocess.run(
            [sys.executable, "-c", _CONST_SNIPPET, str(dev)],
            capture_output=True, text=True, env=env, timeout=600,
        )
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-2000:])
        const[f"L{dev}"] = int(proc.stdout.strip())
    # THE pin: symbolic addressing keeps the executor's embedded-table
    # footprint independent of ring length (a dense-table regression
    # would scale these O(L^2) per step).
    assert const["L8"] == const["L16"], const
    out["plan_hlo_const_bytes"] = const
    return out


def _recovery_metrics() -> dict[str, dict]:
    """Modeled recovery cost (no HLO twin — recovery never executes as
    one SPMD collective): for K ∈ {2, 4} partitions of 16 destinations
    on the 8x8 NoC, one and two concurrent mid-chain failures, the
    ``plan_recovery`` program's wire bytes and the
    ``chain_recovery_latency`` completion — asserted self-consistent
    against the failure-free model (BENCH=1 ci.sh runs this)."""
    from repro.core import program as prg
    from repro.core.scheduling import partition_schedule
    from repro.core.simulator import (
        DEFAULT_PARAMS,
        chain_recovery_latency,
        multi_chain_latency,
    )
    from repro.core.topology import MeshTopology

    topo = MeshTopology(8, 8)
    payload = N * 4
    out: dict[str, dict] = {}
    for k in (2, 4):
        chains = partition_schedule(topo, list(range(1, 17)), 0, num_chains=k)
        base = multi_chain_latency(topo, 0, chains, payload)
        mid = [c[len(c) // 2] for c in chains]  # one mid-chain member each
        for nf, failed in (("f1", {mid[0]}), ("f2", {mid[0], mid[1]})):
            program = prg.plan_recovery(topo, 0, chains, frozenset(failed))
            lat = chain_recovery_latency(topo, 0, chains, frozenset(failed), payload)
            entry = {
                "modeled_bytes": program.wire_bytes(payload),
                "modeled_latency_cc": lat,
                "failures": len(failed),
                "num_chains": k,
            }
            out[f"recovery_k{k}_{nf}"] = entry
        # the modeled invariants the JSON record is trusted for:
        f1, f2 = out[f"recovery_k{k}_f1"], out[f"recovery_k{k}_f2"]
        assert f1["modeled_bytes"] > 0, f1
        assert f2["modeled_bytes"] >= f1["modeled_bytes"], (f1, f2)
        for e in (f1, f2):
            # recovery = detection timeout + a real re-send on top of
            # the failure-free completion
            assert e["modeled_latency_cc"] > base + DEFAULT_PARAMS.fail_timeout_cc, (
                e, base)
    return out


def _tiered_metrics() -> dict[str, dict]:
    """Model-only tier-aware planning entries (no HLO twin — the SPMD
    executor is topology-agnostic): on two 2-tier pod topologies, the
    K-swept all-reduce chosen on the weighted link graph vs the
    tier-blind twin (chosen on the uniform mesh of the same shape,
    then priced on the tiered graph), plus the pod-partitioned
    broadcast chains. Self-consistency: the tier-aware plan is never
    slower than the tier-blind one on ANY entry, STRICTLY faster on
    the 4-pod auto all-reduce entry (where one sub-ring per pod — the
    hierarchical schedule — emerges, K=4), and every broadcast chain
    crosses the inter-pod boundary at most once (exactly once for
    every remote-pod chain). BENCH=1 ci.sh runs this."""
    from repro.core.program import tier_crossing_stats
    from repro.core.scheduling import (
        partition_schedule,
        partition_tier_crossings,
    )
    from repro.core.simulator import (
        all_reduce_latency,
        choose_num_chains,
        multi_chain_latency,
        plan_ring_collective,
    )
    from repro.core.topology import MeshTopology, parse_topology_spec

    payload = N * 4
    out: dict[str, dict] = {}
    # the same spec grammar dryrun --topology / train --topology take
    topos = {
        "p4": parse_topology_spec("pods=4x(4x4):interpod_bw=0.25"),
        "p2": parse_topology_spec(
            "pods=2x(4x4):interpod_bw=0.5:interpod_lat=2"),
    }
    for tag, topo in topos.items():
        uniform = MeshTopology(topo.nx, topo.ny, topo.torus)
        dests = list(range(1, topo.num_nodes))
        for mk in (2, 4):
            aware = choose_num_chains(
                topo, 0, dests, payload, max_chains=mk,
                collective="all_reduce", algo="rs_ag", detail=True,
            )
            blind = choose_num_chains(
                uniform, 0, dests, payload, max_chains=mk,
                collective="all_reduce", algo="rs_ag", detail=True,
            )
            blind_cc = all_reduce_latency(
                topo, 0, blind["rings"], payload, algo="rs_ag")
            program = plan_ring_collective(
                "all_reduce", topo.num_nodes, aware["rings"])
            stats = tier_crossing_stats(program, topo)
            out[f"tiered_{tag}_ar_k{mk}"] = {
                "topology": topo.spec(),
                "max_chains": mk,
                "num_chains": aware["num_chains"],
                "modeled_latency_cc": int(aware["latency_cc"]),
                "blind_num_chains": blind["num_chains"],
                "blind_latency_cc": int(blind_cc),
                "modeled_bytes": program.wire_bytes(payload),
                "interpod_crossings": stats["total"],
                "crossing_steps": stats["crossing_steps"],
            }
        # pod-partitioned broadcast: one chain per pod, remote chains
        # entering their pod once and staying there
        k = topo.num_pods
        chains = partition_schedule(topo, dests, 0, num_chains=k)
        blind_chains = partition_schedule(uniform, dests, 0, num_chains=k)
        out[f"tiered_{tag}_bcast_k{k}"] = {
            "topology": topo.spec(),
            "num_chains": k,
            "modeled_latency_cc": int(
                multi_chain_latency(topo, 0, chains, payload)),
            "blind_latency_cc": int(
                multi_chain_latency(topo, 0, blind_chains, payload)),
            "chain_tier_crossings": partition_tier_crossings(
                topo, chains, 0),
        }
    for name, e in out.items():
        assert e["modeled_latency_cc"] <= e["blind_latency_cc"], (name, e)
    # THE hierarchical pin: on the 4-pod topology the weighted planner
    # picks one sub-ring per pod and beats the tier-blind plan
    # STRICTLY on the same links.
    p4 = out["tiered_p4_ar_k4"]
    assert p4["num_chains"] == 4, p4
    assert p4["modeled_latency_cc"] < p4["blind_latency_cc"], p4
    for name, k in (("tiered_p4_bcast_k4", 4), ("tiered_p2_bcast_k2", 2)):
        cr = out[name]["chain_tier_crossings"]
        assert sorted(cr) == [0] + [1] * (k - 1), (name, cr)
    return out


def main() -> list[tuple[str, float, str]]:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # virtual CPU devices; never the chip
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET], capture_output=True, text=True,
        env=env, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    rows = []
    metrics: dict[str, dict] = {}
    for line in proc.stdout.strip().splitlines():
        name, us, cb = line.split(",")
        rows.append((f"collectives.{name}", float(us), f"wire_bytes={cb}"))
        metrics[name] = {
            "us": float(us), "hlo_bytes": float(cb), **_modeled(name),
        }
    for name, m in metrics.items():
        # The IR's byte model must match the HLO parse EXACTLY — this
        # also keeps the module-level L/N/RINGS constants honest
        # against their copies inside the subprocess snippet.
        assert m.get("modeled_bytes", m["hlo_bytes"]) == m["hlo_bytes"], (
            name, m)
    # Model-only entries (no HLO twin): the recovery program's cost.
    recovery = _recovery_metrics()
    metrics.update(recovery)
    for name, m in recovery.items():
        rows.append((
            f"collectives.{name}", float(m["modeled_latency_cc"]),
            f"modeled_bytes={m['modeled_bytes']}",
        ))
    # Model-only entries: tier-aware planning on 2-tier pod topologies
    # vs the tier-blind twin priced on the same links.
    tiered = _tiered_metrics()
    metrics.update(tiered)
    for name, m in tiered.items():
        rows.append((
            f"collectives.{name}", float(m["modeled_latency_cc"]),
            f"blind={m['blind_latency_cc']} k={m['num_chains']}",
        ))
    # Model-only entries: symbolic-addressing plan scaling + the HLO
    # constant-footprint independence pin.
    scaling = _plan_scaling_metrics(env)
    metrics.update(scaling)
    for name, m in scaling.items():
        if name.startswith("plan_L"):
            rows.append((
                f"collectives.{name}", m["plan_validate_s"] * 1e6,
                f"program_bytes={m['program_bytes']}",
            ))
    rows.append((
        "collectives.plan_hlo_const_bytes", float(scaling["plan_hlo_const_bytes"]["L8"]),
        "asserted equal at L=8 and L=16",
    ))
    with open(os.path.join(repo, "BENCH_collectives.json"), "w") as f:
        json.dump(metrics, f, indent=2, sort_keys=True)
        f.write("\n")
    rows.append((
        "collectives.subprocess_s",
        (time.perf_counter() - t0) * 1e6, "8 virtual devices",
    ))
    return rows


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.2f},{derived}")
