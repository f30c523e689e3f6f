"""Open-loop serving through ``launch.serve.Server`` on the wall clock.

Requests arrive on the mix's schedule whatever the server does; each is
timed from when it was due. The window drives the server's own loop
body: ``submit`` for every request that is due, then ``_admit`` and one
decode ``step``. After the window no more requests arrive and the
server finishes the ones it has, for at most ``DRAIN_S`` seconds.

Correctness: a sample of finished requests, drawn from the seed with the
longest among them, is run through the plain reference over its prompt
and served tokens; the widest gap by which a served token's reference
logit lies below the reference's best is compared with the cell's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time

import jax
import numpy as np

from bench.lib import program, traffic
from bench.lib.record import Span

DRAIN_S = 60.0  # past the window's close, for requests that were due in it
TRACE_S = 8.0  # the traced part of a ``--trace 1`` window: its last seconds


@dataclasses.dataclass(eq=False)
class Tracked:
    plan: traffic.Planned
    req: object  # launch.serve.Request
    due: float  # host clock
    late: float  # seconds the submission lagged its due time
    times: list[float] = dataclasses.field(default_factory=list)  # per token


def warm_plan(plan: list[traffic.Planned], prefixes: list[np.ndarray], slots: int,
              vocab: int, seed: int) -> list[tuple[np.ndarray, int]]:
    """Requests that take every shape the window will: a first wave that
    puts one request in each slot (a prefix hit where the mix has
    prefixes), then one request of each prompt length that misses."""
    rng = traffic.seed_rng(seed, "warm")
    hits = sorted({p.prefix for p in plan if p.prefix >= 0})
    miss_lengths = sorted({p.prompt.size for p in plan if p.prefix < 0})
    out = []

    def fresh(n):
        return rng.integers(0, vocab, n).astype(np.int32)

    for i in range(slots):
        if hits:
            out.append((np.concatenate([prefixes[hits[i % len(hits)]], fresh(4)]), 2))
        else:
            out.append((fresh(miss_lengths[0]), 2))
    out += [(fresh(n), 2) for n in miss_lengths]
    return out


def drive(server, reqs) -> None:
    """Run ``reqs`` through the server until every one has finished."""
    while any(not r.done for r in reqs):
        server._admit()
        if server._active():
            server.step()


def _new_tokens(items: list[Tracked], t: float) -> None:
    for it in items:
        n = len(it.req.out) - len(it.times)
        if n > 0:
            it.times.extend([t] * n)


def window(server, plan, ref, model: dict, seconds: float, spans: list[Span],
           tracer=None) -> tuple[list[Tracked], float]:
    """Drive the open loop; returns the requests and the window's start
    on the host clock. ``ref`` is the configuration's reference module,
    which counts each span's work."""
    per_weight = sum(int(x.nbytes) for x in jax.tree.leaves(server.params)) / ref.param_count(model)
    per_kv_value = jax.tree.leaves(server.cache)[0].dtype.itemsize
    t0 = time.perf_counter()
    tracked: list[Tracked] = []
    live: list[Tracked] = []
    i = 0
    while True:
        now = time.perf_counter() - t0
        if tracer is not None:
            tracer.tick(now)
        while i < len(plan) and plan[i].due_s <= now:
            p = plan[i]
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.submit"):
                req = server.submit(p.prompt, p.max_new, arrival=server.clock)
            it = Tracked(p, req, t0 + p.due_s, s0 - (t0 + p.due_s))
            tracked.append(it)
            live.append(it)
            spans.append(Span("submit", s0, time.perf_counter(), 1, 0))
            i += 1
        if i == len(plan) and not live:
            break
        if now >= seconds + DRAIN_S:
            break
        waiting = [it for it in live if it.req.t_admit is None]
        s0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.admit"):
            server._admit()
        s1 = time.perf_counter()
        admitted = [it for it in waiting if it.req.t_admit is not None]
        if admitted:
            work = 0
            for it in admitted:
                plen = it.req.prompt.size
                start = min(_prefix_len(server, it), plen - 1) if it.req.prefix_hit else 0
                work += ref.forward_flops(model, start, plen)
            spans.append(Span("admit", s0, s1, len(admitted), work))
            _new_tokens(admitted, s1)
        if server._active():
            active = [it for it in live if it.req.out and not it.req.done]
            pos = [it.req.prompt.size + len(it.req.out) - 1 for it in active]
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.decode_step"):
                server.step()
            s1 = time.perf_counter()
            spans.append(Span("decode_step", s0, s1, len(active), ref.decode_step_flops(model, pos),
                              ref.decode_step_bytes(model, pos, per_weight, per_kv_value)))
            _new_tokens(active, s1)
        elif i < len(plan):
            wait = plan[i].due_s - (time.perf_counter() - t0)
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(min(wait, 0.01))
        live = [it for it in live if not it.req.done]
    if tracer is not None:
        tracer.close()
    return tracked, t0


def _prefix_len(server, it: Tracked) -> int:
    """Tokens of the request's prompt that came from the prefix cache."""
    for e in server.prefix_cache.entries:
        if e.plen <= it.req.prompt.size and np.array_equal(it.req.prompt[: e.plen], e.tokens):
            return e.plen
    return 0


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (an unfinished request is ``inf``)."""
    if not values:
        return float("nan")
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def latencies(tracked: list[Tracked], t0: float, seconds: float) -> dict:
    close = t0 + seconds
    ttft = [(it.times[0] - it.due) if it.times else math.inf for it in tracked]
    itl = [b - a for it in tracked for a, b in zip(it.times, it.times[1:]) if b <= close]
    return {"ttft_s": ttft, "itl_s": itl}


def sample(tracked: list[Tracked], seed: int, tokens: int, requests: int) -> list[Tracked]:
    """The finished request with the most served tokens, then others in
    an order drawn from the seed until there are at least ``requests``
    requests and ``tokens`` served tokens."""
    done = [it for it in tracked if it.req.done and it.req.out]
    if not done:
        return []
    longest = max(done, key=lambda it: len(it.req.out))
    rest = [it for it in done if it is not longest]
    order = traffic.seed_rng(seed, "check").permutation(len(rest))
    out, n = [longest], len(longest.req.out)
    for k in order:
        if n >= tokens and len(out) >= requests:
            break
        out.append(rest[k])
        n += len(rest[k].req.out)
    return out


def served_gaps(ref, model: dict, seed: int, seqs, pad_to: int, read: int,
                quant: str | None = None) -> tuple[np.ndarray, list[np.ndarray]]:
    """For each (prompt, served tokens): the reference module's logit
    gap of every served token. With ``quant``, also the gap of the token
    that the lower-precision reference puts first at each position."""
    w = ref.init_weights(model, seed)
    served, control = [], []
    for prompt, out in seqs:
        toks = np.zeros(pad_to, np.int32)
        full = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        toks[: full.size] = full
        start = prompt.size - 1
        logits = np.asarray(ref.logits_at(w, model, toks, start, read))[: len(out)]
        best = logits.max(-1)
        served.append(best - logits[np.arange(len(out)), out])
        if quant:
            low = np.asarray(ref.logits_at(w, model, toks, start, read, quant))[: len(out)]
            control.append(best - logits[np.arange(len(out)), low.argmax(-1)])
    return np.concatenate(served), control


def run(ctx) -> dict:
    model, mix = ctx.config["model"], ctx.mix
    serve = mix["serve"]
    ref = program.reference(ctx.config)
    ctx.phase("started")
    server = program.build_server(ctx.config, serve, ctx.seed)
    ctx.phase("weights made")
    prefixes, plan = traffic.schedule(mix, model["vocab_size"], ctx.seed, ctx.seconds)
    for p in prefixes:
        server.register_prefix(p)
    ctx.phase("prefixes registered")
    warm = [server.submit(p, n) for p, n in warm_plan(
        plan, prefixes, serve["slots"], model["vocab_size"], ctx.seed)]
    drive(server, warm)
    gc.collect()
    ctx.phase("shapes warmed")
    facts = {
        "param_bytes": sum(int(x.nbytes) for x in jax.tree.leaves(server.params)),
        "cache_bytes": sum(int(x.nbytes) for x in jax.tree.leaves(server.cache)),
    }
    hits0, misses0 = server.prefix_cache.hits, server.prefix_cache.misses
    spans: list[Span] = []
    ctx.window_opens()
    tracer = ctx.tracer(max(0.0, ctx.seconds - TRACE_S), ctx.seconds) if ctx.trace else None
    tracked, t0 = window(server, plan, ref, model, ctx.seconds, spans, tracer)
    ctx.window_closed()
    lat = latencies(tracked, t0, ctx.seconds)
    failed = sum(not it.req.done for it in tracked)
    facts.update(
        prefix_hits=server.prefix_cache.hits - hits0,
        prefix_misses=server.prefix_cache.misses - misses0,
        lateness_s=max((it.late for it in tracked), default=0.0),
    )
    ctx.read_memory()
    # correctness, after the program's state is freed
    chosen = sample(tracked, ctx.seed, mix["check"]["sample_tokens"], mix["check"]["sample_requests"])
    seqs = [(it.req.prompt, np.asarray(it.req.out, np.int32)) for it in chosen]
    bad_tokens = sum(int(((s < 0) | (s >= model["vocab_size"])).sum()) for _, s in seqs)
    program.free(server.params, server.cache)
    del server
    gc.collect()
    t = time.perf_counter()
    gaps, control = served_gaps(ref, model, ctx.seed, seqs, serve["max_seq"] + mix["output"]["max"],
                                mix["output"]["max"], ctx.control) if seqs and not bad_tokens else (np.array([np.inf]), [])
    ctx.note(f"reference over {len(seqs)} requests, {gaps.size} served tokens: "
             f"{time.perf_counter() - t:.1f} s")
    return {
        "attempted": len(tracked),
        "failed": failed,
        "end_to_end": {
            "ttft_p90_ms": percentile(lat["ttft_s"], 90) * 1e3,
            "itl_p99_ms": percentile(lat["itl_s"], 99) * 1e3,
        },
        "checks": {
            "logit_gap": float(gaps.max()) if gaps.size else math.inf,
            "unfinished": float(failed),
            "bad_tokens": float(bad_tokens),
        },
        "spans": spans,
        "facts": facts,
        "control_gap": float(np.concatenate(control).max()) if control else None,
        "counts": {"requests": len(tracked), "itl_samples": len(lat["itl_s"]),
                   "checked_tokens": int(gaps.size)},
    }
