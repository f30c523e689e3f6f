"""Continuous-batching serving with Torrent P2MP weight AND KV multicast.

The serving runtime is where the paper's *dynamic* four-phase protocol
survives compilation (DESIGN.md §2): requests arrive asynchronously, and
host-side P2MP movement is driven as Torrent chain tasks with real
predicted-cycle accounting. Two payloads ride the replica plan:

* **Weight refresh** — ``broadcast_weights`` streams the *entire*
  flattened parameter tree (chunked, byte-exact; the logged byte count
  is asserted against the params' true nbytes) down the persistent
  ``parallel.collectives.MultiChainPlan``'s sub-chains.
* **KV-block multicast** — ``register_prefix`` prefilles a shared
  prompt prefix (system prompt / few-shot preamble) ONCE, flattens the
  per-position KV rows to a dense bf16 matrix
  (:mod:`repro.launch.paged_kv`), broadcasts the bytes to every replica
  as a ``core.program.plan_broadcast`` ChainProgram (priced by
  ``simulator.program_latency`` / ``program_wire_bytes``; delivered
  byte-exactly by ``MultiChainTask``), and each receiving replica runs
  the :mod:`repro.kernels.relayout` kernel to materialize its paged
  ``(page, F)`` block layout — pinned bit-exactly against the numpy
  oracle. Requests whose prompt starts with a registered prefix are
  admitted by *seeding* the cached rows instead of re-prefilling them.

The decode loop is slot-based continuous batching with **per-slot
positions**: every slot advances at its own absolute position
(``(B,)``-vector ``pos`` through ``models.transformer.decode_step``),
admission prefilles ONLY the admitted slot
(``launch.steps.make_slot_prefill_step`` + ``write_cache_slot``), and a
slot finishes only when *it* runs out of room — an admission or another
slot's exhaustion never perturbs an in-flight request's tokens.

Elastic serving: ``Server.scale_down`` handles replica loss by
*re-forming* the live plan around the lost members
(``runtime.elastic.scale_down_plan`` → ``MultiChainPlan.reform``)
instead of rebuilding it.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --requests 16 --max-new 32

``--full`` serves the published config at full width instead.
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

from repro import configs as C
from repro.core.chaintask import MultiChainTask
from repro.core.program import plan_broadcast, program_wire_bytes
from repro.core.simulator import program_latency, unicast_latency
from repro.core.topology import MeshTopology
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.paged_kv import (
    PrefixCache,
    PrefixEntry,
    dense_from_bytes,
    extract_dense_kv,
    paged_ref,
    seed_cache_row,
    to_paged,
)
from repro.launch.steps import (
    make_serve_step,
    make_slot_prefill_step,
    serving_params,
    write_cache_slot,
)
from repro.models import transformer as T
from repro.parallel.collectives import MultiChainPlan
from repro.runtime.elastic import scale_down_plan
from repro.runtime.tracing import span

log = logging.getLogger("repro.serve")


@dataclasses.dataclass(eq=False)
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    arrival: int = 0  # decode tick the request becomes visible
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    prefix_hit: bool = False  # admitted by seeding a registered prefix
    t_admit: int | None = None  # decode tick admitted to a slot
    t_done: int | None = None  # decode tick the last token was emitted
    t_submit: float = dataclasses.field(default_factory=time.perf_counter)  # host clock


@dataclasses.dataclass
class ServeConfig:
    arch: str = "yi-6b"
    smoke: bool = True
    batch: int = 4  # decode slots
    prompt_len: int = 16  # admission window: longest accepted prompt
    max_seq: int = 128
    eos: int = -1  # -1: run to max_new
    replicas: int = 4  # model replicas for weight/KV multicast
    page_size: int = 8  # KV page height (positions per paged block)
    prefix_cache_bytes: int | None = None  # None = unbounded, else LRU
    seed: int = 0


class Server:
    """Slot-based continuous batching with greedy decode, per-slot
    positions, and a multicast-fed prefix cache.

    ``params`` is the serving copy of the weights: every leaf that the
    serve programs only read as bf16 is held in bf16, converted once
    when the weights are installed (``launch.steps.serving_params``);
    the rest stay float32. No float32 copy of a converted leaf is kept."""

    def __init__(self, sc: ServeConfig):
        self.sc = sc
        self.cfg = C.get_smoke_config(sc.arch) if sc.smoke else C.get_config(sc.arch)
        # the cache first: its stacking transient then never meets the
        # float32 weights and their bf16 copy at once
        self.cache = T.init_cache(self.cfg, sc.batch, sc.max_seq)
        key = jax.random.PRNGKey(sc.seed)
        self.params = self._hold(T.model_init(key, self.cfg))
        self.slot_prefill = jax.jit(make_slot_prefill_step(self.cfg, sc.max_seq))
        self.serve_step = jax.jit(make_serve_step(self.cfg))
        self.queue: list[Request] = []
        self.slots: list[Request | None] = [None] * sc.batch
        self.clock = 0  # decode ticks (the traffic harness's time base)
        self.steps = 0
        # P2MP bookkeeping (paper Fig. 4 host orchestration): ONE
        # persistent multi-chain plan for the replica set — elastic
        # scale-down re-forms it (endpoint-side) instead of rebuilding.
        self.replicas = sc.replicas
        self.topo = MeshTopology(max(2, sc.replicas), 1)
        self.plan = MultiChainPlan(
            self.topo, 0, list(range(1, sc.replicas)), scheduler="tsp"
        )
        self.multicast_log: list[dict] = []
        self.last_delivery: dict[int, np.ndarray] = {}
        self.prefix_cache = PrefixCache(capacity_bytes=sc.prefix_cache_bytes)
        self.kv_multicast_log: list[dict] = []

    def _hold(self, params):
        """Install ``params`` in the dtypes the serve programs read."""
        sc = self.sc
        with span("serve.hold_weights") as sp:
            held = serving_params(self.cfg, params, batch=sc.batch,
                                  prompt_len=sc.prompt_len, max_seq=sc.max_seq)
            nbytes = {"bf16_bytes": 0, "f32_bytes": 0}
            for x in jax.tree.leaves(held):
                nbytes["bf16_bytes" if x.dtype == jnp.bfloat16 else "f32_bytes"] += int(x.nbytes)
            sp.set_metadata(**nbytes)
        return held

    # -- the paper's host-side P2MP: weight refresh to replicas ----------
    def broadcast_weights(self, chunk_bytes: int = 1 << 20,
                          new_params=None) -> dict:
        """Multicast the FULL parameter tree to every surviving replica
        down the persistent plan's sub-chains, ``chunk_bytes`` at a
        time. The logged ``bytes`` is asserted against the params' true
        nbytes — the record describes a real weight refresh. With no
        surviving destinations (``replicas=1``) nothing moves and the
        record says so: a distinct no-op with 0 chunks / 0 delivered
        bytes, never a phantom full-payload claim.

        ``new_params`` replaces the served weights before streaming and
        version-invalidates the prefix cache (cached KV was prefilled
        under the old weights). They are installed as the serving copy,
        in the dtypes ``__init__`` holds (bf16 where the serve programs
        only read bf16), so what streams is what the programs read and
        the steps need no new compile. Re-broadcasting unchanged weights —
        e.g. the refresh at ``run()`` start — keeps entries valid.
        ``prefix_invalidated`` in the record counts what was dropped."""
        t0 = time.perf_counter()
        invalidated = 0
        if new_params is not None:
            self.params = self._hold(new_params)
            invalidated = self.prefix_cache.on_weights_update()
        dests = self.plan.survivors
        if not dests:
            rec = {
                "bytes": 0, "delivered_bytes": 0, "chunks": 0,
                "replicas": 1, "cycles": 0, "speedup_vs_unicast": 1.0,
                "noop": True, "prefix_invalidated": invalidated,
                "wall_s": time.perf_counter() - t0,
            }
            self.last_delivery = {}
            self.multicast_log.append(rec)
            return rec
        flat, _ = jax.tree_util.tree_flatten(self.params)
        true_nbytes = sum(int(x.nbytes) for x in flat)  # no host copy
        # A full-width model's weights are GBs: the byte stream and each
        # replica's copy are filled in place, one leaf or chunk at a
        # time, and the previous delivery is dropped first.
        self.last_delivery = {}
        # dtype-agnostic byte stream: the wire moves bytes, not floats
        payload = np.empty(true_nbytes, np.uint8)
        off = 0
        for x in flat:
            leaf = np.ascontiguousarray(x).reshape(-1).view(np.uint8)
            payload[off : off + leaf.size] = leaf
            off += leaf.size
        cycles = unicast = chunks = 0
        delivered = {d: np.empty(payload.size, np.uint8) for d in dests}
        step = max(1, int(chunk_bytes))
        for off in range(0, payload.size, step):
            chunk = payload[off : off + step]
            task = MultiChainTask(
                self.topo, 0, dests, chunk,
                chains=[list(c) for c in self.plan.chains],
            )
            bufs = task.run()
            for d, buf in bufs.items():
                if buf.size != chunk.size:
                    raise AssertionError(
                        f"replica {d} got {buf.size} B of a {chunk.size} B chunk"
                    )
                delivered[d][off : off + chunk.size] = buf
            cycles += task.cycle_ledger["total"]
            unicast += task.unicast_cycles()
            chunks += 1
        self.last_delivery = delivered
        rec = {
            "bytes": int(payload.nbytes),
            "delivered_bytes": sum(
                int(b.nbytes) for b in self.last_delivery.values()
            ),
            "chunks": chunks,
            "replicas": len(dests) + 1,
            "cycles": cycles,
            "speedup_vs_unicast": unicast / cycles if cycles else 1.0,
            "prefix_invalidated": invalidated,
            "wall_s": time.perf_counter() - t0,  # host clock
        }
        if rec["bytes"] != true_nbytes:
            raise AssertionError(
                f"weight refresh logged {rec['bytes']} B but params hold "
                f"{true_nbytes} B"
            )
        self.multicast_log.append(rec)
        return rec

    # -- KV-block multicast: prefill a shared prefix once, chain it out --
    def register_prefix(self, tokens: np.ndarray) -> PrefixEntry:
        """Prefill a shared prompt prefix on the head replica, broadcast
        its KV rows to every survivor as a ``plan_broadcast``
        ChainProgram, and relayout them into paged blocks on receipt.

        Delivery is byte-exact and the modeled wire bytes
        (``program_wire_bytes``) are asserted against the bytes the
        chain task actually delivered; each replica's paged blocks are
        pinned bit-exactly against the ``relayout_ref`` numpy oracle."""
        sc = self.sc
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        plen = int(tokens.size)
        if plen == 0 or plen % sc.page_size:
            raise ValueError(
                f"prefix length {plen} must be a positive multiple of "
                f"page_size={sc.page_size}"
            )
        if plen >= sc.max_seq:
            raise ValueError(f"prefix length {plen} >= max_seq {sc.max_seq}")
        # scratch B=1 prefill — the live slots are never touched
        _, one_cache = self.slot_prefill(self.params, jnp.asarray(tokens)[None])
        dense = extract_dense_kv(one_cache, 0, plen, sc.max_seq)
        paged = to_paged(dense, sc.page_size)
        oracle = paged_ref(dense, sc.page_size)
        np.testing.assert_array_equal(
            paged.view(np.uint8), oracle.view(np.uint8)
        )  # relayout kernel pinned against its numpy oracle
        entry = PrefixEntry(
            tokens=tokens, page=sc.page_size, dense=dense, paged=paged
        )
        entry.broadcast = self._broadcast_kv(entry)
        self.prefix_cache.add(entry)
        self.kv_multicast_log.append(entry.broadcast)
        return entry

    def _broadcast_kv(self, entry: PrefixEntry) -> dict:
        """Chain the dense KV rows to the surviving replicas and paged-
        relayout them on each receiver."""
        t0 = time.perf_counter()
        dests = self.plan.survivors
        payload = np.ascontiguousarray(entry.dense).reshape(-1).view(np.uint8)
        nbytes = int(payload.nbytes)
        if not dests:
            entry.replica_paged = {0: entry.paged}
            return {
                "prefix_len": entry.plen, "bytes": nbytes,
                "delivered_bytes": 0, "wire_bytes": 0, "replicas": 1,
                "cycles": 0, "modeled_cycles": 0,
                "speedup_vs_unicast": 1.0, "noop": True,
                "wall_s": time.perf_counter() - t0,
            }
        chains = tuple(tuple(c) for c in self.plan.chains)
        program = plan_broadcast(self.topo.num_nodes, 0, chains)
        modeled_wire = program_wire_bytes(program, nbytes)
        modeled_cc = int(program_latency(self.topo, 0, program, nbytes))
        uni_cc = int(unicast_latency(self.topo, 0, dests, nbytes))
        task = MultiChainTask(
            self.topo, 0, dests, payload, chains=[list(c) for c in chains]
        )
        bufs = task.run()
        delivered = 0
        replica_paged = {0: entry.paged}
        F = entry.dense.shape[1]
        for d, buf in bufs.items():
            rdense = dense_from_bytes(buf, entry.plen, F)
            np.testing.assert_array_equal(
                rdense.view(np.uint8), entry.dense.view(np.uint8)
            )  # byte-exact delivery vs the prefilling replica
            rpaged = to_paged(rdense, entry.page)
            np.testing.assert_array_equal(
                rpaged.view(np.uint8),
                paged_ref(entry.dense, entry.page).view(np.uint8),
            )  # receiver-side relayout pinned vs the numpy oracle
            replica_paged[d] = rpaged
            delivered += int(buf.nbytes)
        # Two byte books, each checked against its own invariant: the
        # task must deliver the FULL payload to every destination, and
        # the planned program's wire bytes are the fused-ppermute HLO
        # attribution — (steps + K - 1) payloads, which equals the
        # delivered bytes exactly when the plan is a single chain.
        if delivered != len(dests) * nbytes:
            raise AssertionError(
                f"KV broadcast delivered {delivered} B, expected "
                f"{len(dests)} x {nbytes} B"
            )
        if modeled_wire != (len(program.steps) + len(chains) - 1) * nbytes:
            raise AssertionError(
                f"planned program prices {modeled_wire} B, expected "
                f"{len(program.steps) + len(chains) - 1} x {nbytes} B"
            )
        entry.replica_paged = replica_paged
        return {
            "prefix_len": entry.plen,
            "bytes": nbytes,
            "delivered_bytes": delivered,
            "wire_bytes": modeled_wire,
            "replicas": len(dests) + 1,
            "cycles": int(task.cycle_ledger["total"]),
            "modeled_cycles": modeled_cc,
            "unicast_cycles": uni_cc,
            "speedup_vs_unicast": (
                uni_cc / modeled_cc if modeled_cc else 1.0
            ),
            "wall_s": time.perf_counter() - t0,  # host clock
        }

    # -- elastic scale-down: re-form the live plan, never rebuild it -----
    def scale_down(self, replicas: int) -> tuple[int, ...]:
        """Shrink the replica set to ``replicas`` (keeping replica 0,
        the plan head). The lost members are spliced out of the live
        ``MultiChainPlan`` as a concurrent failure set — surviving
        sub-chains keep their schedules verbatim and the next
        :meth:`broadcast_weights` still delivers full weights to every
        survivor. Returns the lost replica ids."""
        lost = scale_down_plan(self.plan, self.replicas, replicas)
        if lost:
            log.info("scale-down: lost replicas %s, plan re-formed", list(lost))
        self.replicas = int(replicas)
        return lost

    # -- request lifecycle -------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int, arrival: int = 0) -> Request:
        """Queue a request. Prompts longer than the admission window are
        rejected HERE — never silently truncated into a different
        prompt — as are empty ones."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size > self.sc.prompt_len:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the admission window "
                f"prompt_len={self.sc.prompt_len}; refusing to truncate"
            )
        req = Request(
            rid=len(self.queue), prompt=prompt, max_new=max_new,
            arrival=int(arrival),
        )
        self.queue.append(req)
        return req

    def _admit(self):
        """Fill free slots with arrived requests, prefilling ONLY the
        admitted slot — in-flight rows are never rebuilt."""
        waiting = [
            r for r in self.queue
            if not r.done and r.t_admit is None and r.arrival <= self.clock
        ]
        for i, slot in enumerate(self.slots):
            if (slot is None or slot.done) and waiting:
                r = waiting.pop(0)
                self.slots[i] = r
                r.t_admit = self.clock
                self._prefill_slot(i)

    def _prefill_slot(self, i: int):
        """Fill slot ``i``'s cache row and emit its first token.

        Prefix-cache hit: seed the registered prefix's multicast KV rows
        straight into the row (bit-identical to prefilling them) and run
        only the prompt's suffix through single-row decode. Miss: exact-
        length full prefill of this row alone."""
        r = self.slots[i]
        prompt = r.prompt
        plen = int(prompt.size)
        with span("serve.admit_request") as sp:
            entry = self.prefix_cache.lookup(prompt) if self.prefix_cache.entries else None
            # keep at least one token to feed through decode so the
            # slot's first output falls out of the last suffix step
            seed = 0 if entry is None else min(entry.plen, plen - 1)
            sp.set_metadata(
                prompt_tokens=plen, cached_tokens=seed,
                queued_us=int((time.perf_counter() - r.t_submit) * 1e6),
            )
            if entry is not None:
                r.prefix_hit = True
                if seed:
                    with span("serve.seed_rows"):
                        self.cache = seed_cache_row(self.cache, i, entry.dense, seed)
                with span("serve.suffix_decode"):
                    one_cache = jax.tree.map(lambda t: t[:, i : i + 1], self.cache)
                    for p in range(seed, plen):
                        tok, one_cache = self.serve_step(
                            self.params,
                            jnp.asarray([int(prompt[p])], jnp.int32),
                            jnp.int32(p),
                            one_cache,
                        )
            else:
                tok, one_cache = self.slot_prefill(
                    self.params, jnp.asarray(prompt)[None]
                )
            self.cache = write_cache_slot(self.cache, one_cache, i)
            first = int(np.asarray(tok)[0])
            r.out.append(first)
            self._maybe_finish(r)

    def _maybe_finish(self, r: Request):
        t = r.out[-1]
        if (
            len(r.out) >= r.max_new
            or t == self.sc.eos
            or len(r.prompt) + len(r.out) >= self.sc.max_seq
        ):
            r.done = True
            r.t_done = self.clock

    def _active(self) -> list[int]:
        return [
            i for i, r in enumerate(self.slots)
            if r is not None and not r.done and r.out
        ]

    def step(self):
        """One decode step: every active slot advances at its OWN
        absolute position (inactive rows are parked at position 0 and
        their tokens discarded — their rows are rewritten on the next
        admission)."""
        active = self._active()
        if not active:
            return
        B = self.sc.batch
        with span("serve.decode_step"):
            cur = np.zeros(B, np.int32)
            pos = np.zeros(B, np.int32)
            for i in active:
                r = self.slots[i]
                cur[i] = r.out[-1]
                pos[i] = len(r.prompt) + len(r.out) - 1
            toks, self.cache = self.serve_step(
                self.params, jnp.asarray(cur), jnp.asarray(pos), self.cache
            )
            self.clock += 1
            self.steps += 1
            with span("serve.step_sync"):
                nxt = np.asarray(toks)
            for i in active:
                r = self.slots[i]
                r.out.append(int(nxt[i]))
                self._maybe_finish(r)

    def run(self, requests: list[Request]) -> dict[str, Any]:
        t0 = time.time()
        self.broadcast_weights()  # weight multicast to the replica set
        while any(not r.done for r in requests):
            self._admit()
            if not self._active():
                future = [
                    r.arrival for r in self.queue
                    if not r.done and r.t_admit is None
                ]
                if not future:
                    break
                # idle until the next arrival
                self.clock = max(self.clock + 1, min(future))
                continue
            self.step()
        wall = time.time() - t0
        served = [r for r in requests if r.done]
        lat = [r.t_done - r.arrival for r in served if r.t_done is not None]
        toks = sum(len(r.out) for r in requests)
        return {
            "requests": len(requests),
            "served": len(served),
            "generated_tokens": toks,
            "decode_steps": self.steps,
            "wall_s": wall,
            "tokens_per_s": toks / wall if wall else 0.0,
            "prefix_hit_rate": self.prefix_cache.hit_rate,
            "prefix_entries": len(self.prefix_cache.entries),
            "prefix_bytes": self.prefix_cache.total_bytes,
            "prefix_evictions": self.prefix_cache.evictions,
            "prefix_invalidations": self.prefix_cache.invalidations,
            "latency_ticks_p50": float(np.percentile(lat, 50)) if lat else 0.0,
            "latency_ticks_p99": float(np.percentile(lat, 99)) if lat else 0.0,
            "weight_multicast": self.multicast_log[-1] if self.multicast_log else None,
            "kv_multicast": self.kv_multicast_log[-1] if self.kv_multicast_log else None,
        }


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="yi-6b", choices=C.ARCHS)
    p.add_argument("--smoke", action="store_true", default=True,
                   help="reduced-width config (the default)")
    p.add_argument("--full", dest="smoke", action="store_false",
                   help="the published config at full width")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    args = p.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()
    sc = ServeConfig(
        arch=args.arch, smoke=args.smoke, batch=args.batch,
        prompt_len=args.prompt_len,
        max_seq=args.prompt_len + args.max_new + 2,
    )
    server = Server(sc)
    rng = np.random.default_rng(0)
    reqs = [
        server.submit(
            rng.integers(0, server.cfg.vocab_size, size=sc.prompt_len),
            args.max_new,
        )
        for _ in range(args.requests)
    ]
    out = server.run(reqs)
    log.info(
        "served %d requests, %d tokens in %.2fs (%.1f tok/s); "
        "weight multicast %.1fx vs unicast",
        out["requests"], out["generated_tokens"], out["wall_s"],
        out["tokens_per_s"],
        (out["weight_multicast"] or {}).get("speedup_vs_unicast", 0.0),
    )
    return out


if __name__ == "__main__":
    main()
