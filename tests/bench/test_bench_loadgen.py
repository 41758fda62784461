"""Seeded wall-clock traffic (bench/loadgen.py)."""

import math
from statistics import NormalDist

import numpy as np
import pytest

from bench import loadgen

CHAT = {"arrivals": {"kind": "poisson", "rate_rps": 3.0},
        "prompt_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                          "min": 32, "max": 1024},
        "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                          "min": 16, "max": 256}}
BACKLOG = {"arrivals": {"kind": "backlog", "depth_per_slot": 2,
                        "requests": 64},
           "prompt_tokens": CHAT["prompt_tokens"],
           "output_tokens": CHAT["output_tokens"]}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["poisson", "backlog"])
def test_same_seed_same_schedule(mix):
    a = loadgen.schedule(mix, BIG_SEED, 40)
    assert a == loadgen.schedule(mix, BIG_SEED, 40)
    assert a != loadgen.schedule(mix, BIG_SEED + 1, 40)
    np.testing.assert_array_equal(
        loadgen.prompt_tokens(BIG_SEED, 3, 50, 1000),
        loadgen.prompt_tokens(BIG_SEED, 3, 50, 1000))


def test_every_seed_has_the_same_work():
    a = loadgen.schedule(CHAT, 1, 40)
    b = loadgen.schedule(CHAT, 2, 40)
    assert sorted(i.prompt_len for i in a) == sorted(i.prompt_len for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert a[-1].due == pytest.approx(b[-1].due)


def test_poisson_falls_due_in_the_window_at_the_rate():
    items = loadgen.schedule(CHAT, 7, 40)
    assert len(items) == math.ceil(3.0 * 40)
    due = np.array([i.due for i in items])
    assert np.all(np.diff(due) > 0) and due[-1] < 40
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert np.mean(gaps) == pytest.approx(1 / 3.0, rel=0.02)
    # exponential: the median gap is ln 2 / rate
    assert np.median(gaps) == pytest.approx(math.log(2) / 3.0, rel=0.05)


def test_lengths_have_the_stated_distribution():
    x = loadgen.quantiles(CHAT["prompt_tokens"], 2001)
    assert x.min() >= 32 and x.max() <= 1024
    assert np.median(x) == 384
    # the log of the unclipped part spreads as the stated sigma
    z = np.log(loadgen.quantiles(dict(CHAT["prompt_tokens"], min=1,
                                      max=10**9), 2001))
    assert np.std(z) == pytest.approx(0.8, rel=0.02)
    # the share clipped to the top is the lognormal's tail beyond it
    tail = 1 - NormalDist(0, 0.8).cdf(math.log(1024 / 384))
    assert np.mean(x == 1024) == pytest.approx(tail, abs=0.002)


def test_backlog_repeats_its_multiset():
    items = loadgen.schedule(BACKLOG, 5, 40)
    assert len(items) == 64 * loadgen.BACKLOG_BLOCKS
    assert all(i.due is None for i in items)
    first, second = items[:64], items[64:128]
    assert sorted(i.prompt_len for i in first) == \
        sorted(i.prompt_len for i in second)


def test_bursts_keep_the_rate():
    """Bursts of b fall due together, at the same mean rate."""
    mix = dict(CHAT, arrivals={"kind": "poisson", "rate_rps": 3.0,
                               "burst": 4})
    items = loadgen.schedule(mix, 7, 40)
    due = np.array([i.due for i in items])
    assert len(items) == 4 * math.ceil(3.0 * 40 / 4)
    assert np.all(due[0::4] == due[3::4]) and due[-1] < 40
    assert len(items) / due[-1] == pytest.approx(3.0, rel=0.1)
    plain = loadgen.schedule(CHAT, 7, 40)
    assert sorted(i.prompt_len for i in items) == \
        sorted(i.prompt_len for i in plain)


def test_a_shared_prefix_starts_every_prompt_of_its_group():
    mix = dict(CHAT, shared_prefix={"tokens": 40, "groups": 3})
    items = loadgen.schedule(mix, BIG_SEED, 10)
    assert sorted({i.prefix for i in items}) == [0, 1, 2]
    assert loadgen.length_range(mix) == (32 + 40, 1024 + 40)
    p = [loadgen.request_prompt(mix, BIG_SEED, k, it, 1000)
         for k, it in enumerate(items)]
    assert [len(x) for x in p] == [i.prompt_len for i in items]
    for g in range(3):
        heads = {tuple(x[:40]) for x, it in zip(p, items) if it.prefix == g}
        assert len(heads) == 1
    a, b = [k for k, it in enumerate(items) if it.prefix == 0][:2]
    assert not np.array_equal(p[a][40:48], p[b][40:48])
    # no prefix: the request's own ids, as before
    it = loadgen.schedule(CHAT, 1, 10)[0]
    np.testing.assert_array_equal(
        loadgen.request_prompt(CHAT, 1, 0, it, 1000),
        loadgen.prompt_tokens(1, 0, it.prompt_len, 1000))
