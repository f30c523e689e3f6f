"""The traffic generator: deterministic per seed, the stated distributions,
the same work for every seed."""

from __future__ import annotations

import math

import numpy as np
import pytest

from bench.lib import traffic
from bench.tests import smoke

BIG_SEED = 2**31 + 4_000_000_017  # wider than 32 signed bits


@pytest.mark.parametrize("mix", ["prefix-chat", "unshared-docs"])
def test_schedule_is_deterministic_per_seed(mix):
    m = smoke.load("bench", "traffic", f"{mix}.json")
    pa, a = traffic.schedule(m, 32000, BIG_SEED, 30.0)
    pb, b = traffic.schedule(m, 32000, BIG_SEED, 30.0)
    _, c = traffic.schedule(m, 32000, BIG_SEED + 1, 30.0)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    assert [(r.due_s, r.max_new, r.prefix) for r in a] == [(r.due_s, r.max_new, r.prefix) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", ["prefix-chat", "unshared-docs"])
def test_every_seed_gets_the_same_schedule(mix):
    """Sizes, prefixes and arrivals come from the mix; the seed draws only
    the tokens."""
    m = smoke.load("bench", "traffic", f"{mix}.json")
    pa, a = traffic.schedule(m, 32000, 11, 45.0)
    pb, b = traffic.schedule(m, 32000, BIG_SEED, 45.0)
    assert [(r.due_s, r.max_new, r.prefix, r.prompt.size) for r in a] == \
        [(r.due_s, r.max_new, r.prefix, r.prompt.size) for r in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(not np.array_equal(x, y) for x, y in zip(pa, pb))
    other = dict(m, schedule_seed=m["schedule_seed"] + 1)
    _, c = traffic.schedule(other, 32000, 11, 45.0)
    assert sorted(r.max_new for r in c) == sorted(r.max_new for r in a)
    assert [r.max_new for r in c] != [r.max_new for r in a]


def test_prefix_chat_distributions():
    m = smoke.load("bench", "traffic", "prefix-chat.json")
    rate = m["arrivals"]["rate_per_s"]
    pre, plan = traffic.schedule(m, 32000, 5, 400.0)
    n = len(plan)
    assert n == math.floor(rate * 400.0) - (math.floor(rate * 400.0) / rate >= 400.0)
    assert [p.size for p in pre] == m["prefixes"]["lengths"]
    # every request starts with its system prompt
    for r in plan:
        assert np.array_equal(r.prompt[: pre[r.prefix].size], pre[r.prefix])
    user = np.array([r.prompt.size - pre[r.prefix].size for r in plan])
    assert user.min() >= m["prompt"]["min"] and user.max() <= m["prompt"]["max"]
    assert abs(np.median(user) - m["prompt"]["median"]) <= 1
    out = np.array([r.max_new for r in plan])
    assert out.min() >= m["output"]["min"] and out.max() <= m["output"]["max"]
    assert abs(np.median(out) - m["output"]["median"]) <= 1
    # Zipf(1) popularity over the 4 prefixes: 12/25, 6/25, 4/25, 3/25
    counts = np.bincount([r.prefix for r in plan], minlength=4) / n
    assert np.allclose(counts, [12 / 25, 6 / 25, 4 / 25, 3 / 25], atol=1.0 / n)
    # Poisson arrivals at the stated mean rate, inside the window
    due = np.array([r.due_s for r in plan])
    assert due[0] == 0.0 and due[-1] < 400.0
    gaps = np.diff(due)
    assert abs(gaps.mean() - 1.0 / rate) < 0.02 / rate
    assert abs(np.median(gaps) - math.log(2) / rate) < 0.02 / rate


def test_unshared_docs_distributions():
    m = smoke.load("bench", "traffic", "unshared-docs.json")
    pre, plan = traffic.schedule(m, 64000, 5, 200.0)
    assert pre == [] and all(r.prefix == -1 for r in plan)
    lengths = np.array([r.prompt.size for r in plan])
    assert (lengths % 256 == 0).all()
    assert lengths.min() == 512 and lengths.max() == 4096
    assert len(set(lengths.tolist())) == 15  # one prefill shape per multiple of 256
    assert abs(np.median(lengths) - 1536) <= 256
    assert all(r.prompt.max() < 64000 for r in plan)


def test_train_batches():
    a = traffic.TrainBatches(256, 32, 4, 4, BIG_SEED)
    b = traffic.TrainBatches(256, 32, 4, 4, BIG_SEED)
    x, y = a.batch(0), b.batch(0)
    assert np.array_equal(x["tokens"], y["tokens"])
    assert np.array_equal(x["labels"][:, :-1], x["tokens"][:, 1:])
    nxt = a.batch(1)
    rows = {tuple(r) for r in np.concatenate([x["tokens"], nxt["tokens"]])}
    assert len(rows) == 8  # no row repeats within or across steps
    # every transition is one of the chain's successors
    t = x["tokens"]
    assert all(t[i, j + 1] in a.table[t[i, j]] for i in range(4) for j in range(31))
