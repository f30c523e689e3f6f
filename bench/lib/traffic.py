"""One generator for every traffic mix, driven by the mix's data file.

A mix's schedule is fixed: request sizes and inter-arrival gaps are
stratified quantiles of the stated distributions, put in an order drawn
once from the mix's ``schedule_seed``. The run's seed draws the token
contents (and the benchmark draws the weights from it). With some tens of
requests in a window, the order of long and short requests sets the
tail of the latency; drawing it from the run's seed made runs of
different seeds differ far more than runs of one seed (PERF.md).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""

    due_s: float  # seconds after the window opens
    prompt: np.ndarray  # (S,) int32
    max_new: int
    prefix: int  # index into the mix's prefixes, -1 for none


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed (any integer seed)."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def quantiles(n: int) -> np.ndarray:
    """Midpoint probabilities of ``n`` equal strata."""
    return (np.arange(n) + 0.5) / n


def sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified sizes of a length distribution, in ascending order.

    ``dist``: ``{"kind": "lognormal", "median", "sigma", "min", "max",
    "multiple"}`` or ``{"kind": "fixed", "value"}``. Sizes are clipped to
    ``[min, max]`` and then rounded up to ``multiple``."""
    if dist["kind"] == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if dist["kind"] != "lognormal":
        raise ValueError(f"unknown size distribution {dist['kind']!r}")
    z = np.array([_NORMAL.inv_cdf(q) for q in quantiles(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    x = np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    m = int(dist.get("multiple", 1))
    return -(-x // m) * m


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) whose sum is ``n / rate``."""
    rate = float(arrivals["rate_per_s"])
    if arrivals["kind"] == "poisson":
        g = -np.log1p(-quantiles(n)) / rate
    elif arrivals["kind"] == "uniform":
        g = np.full(n, 1.0 / rate)
    else:
        raise ValueError(f"unknown arrival process {arrivals['kind']!r}")
    return g * (n / rate) / g.sum()


def apportion(weights: list[float], n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` items by ``weights``."""
    w = np.asarray(weights, float)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts


def popularity(spec: dict, k: int) -> list[float]:
    if spec["kind"] == "zipf":
        return [1.0 / (r + 1) ** spec["s"] for r in range(k)]
    if spec["kind"] == "uniform":
        return [1.0] * k
    raise ValueError(f"unknown popularity {spec['kind']!r}")


def prefixes(mix: dict, vocab: int, seed: int) -> list[np.ndarray]:
    """The mix's shared prompt prefixes; only their tokens vary by seed."""
    spec = mix.get("prefixes")
    if not spec:
        return []
    rng = seed_rng(seed, "prefix-tokens")
    return [rng.integers(0, vocab, n).astype(np.int32) for n in spec["lengths"]]


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> tuple[list[np.ndarray], list[Planned]]:
    """The shared prefixes and the requests due in ``[0, seconds)``; only
    their tokens depend on ``seed``."""
    rate = float(mix["arrivals"]["rate_per_s"])
    n = max(1, int(math.floor(rate * seconds)))
    if n / rate >= seconds:
        n = max(1, n - 1)
    order = seed_rng(mix["schedule_seed"], "order")
    gap = order.permutation(gaps(mix["arrivals"], n))
    due = np.cumsum(gap) - gap[0]
    prompt_len = order.permutation(sizes(mix["prompt"], n))
    max_new = order.permutation(sizes(mix["output"], n))
    pre = prefixes(mix, vocab, seed)
    if pre:
        counts = apportion(popularity(mix["prefixes"]["popularity"], len(pre)), n)
        which = order.permutation(np.repeat(np.arange(len(pre)), counts))
    else:
        which = np.full(n, -1)
    tokens = seed_rng(seed, "prompt-tokens")
    out = []
    for i in range(n):
        own = tokens.integers(0, vocab, int(prompt_len[i])).astype(np.int32)
        if which[i] >= 0:
            own = np.concatenate([pre[which[i]], own])
        out.append(Planned(float(due[i]), own, int(max_new[i]), int(which[i])))
    return pre, out


class TrainBatches:
    """Training rows from a fixed random Markov chain over the vocabulary
    (each token has ``branch`` possible successors), so the loss has
    something to learn. The chain and every step's rows come from the
    seed; no two steps share rows. ``batch(step)`` has the program's
    data-source interface: ``{"tokens", "labels"}`` of (B, S) int32."""

    def __init__(self, vocab: int, seq_len: int, global_batch: int, branch: int, seed: int):
        self.vocab, self.seq_len, self.global_batch, self.branch = vocab, seq_len, global_batch, branch
        self.seed = seed
        self.table = seed_rng(seed, "markov-table").integers(0, vocab, (vocab, branch), dtype=np.int32)

    def batch(self, step: int, *, host_slice: slice = slice(None)) -> dict:
        rng = seed_rng(self.seed, f"rows-{step}")
        B, S = self.global_batch, self.seq_len
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, B)
        choice = rng.integers(0, self.branch, (B, S))
        for t in range(S):
            toks[:, t + 1] = self.table[toks[:, t], choice[:, t]]
        return {"tokens": toks[host_slice, :-1], "labels": toks[host_slice, 1:]}
