"""Seeded traffic from a mix file: request sizes and wall-clock due times.

A mix (``bench/traffic/<name>.json``) gives

  "arrivals": {"kind": "poisson", "rate_rps": r}
              requests fall due at exponential gaps, in seconds, open loop;
              with "burst": b they fall due b at a time, the bursts at
              exponential gaps of mean b / r (the same mean rate);
              {"kind": "backlog", "depth_per_slot": k, "requests": n}
              an offline queue: whenever fewer than k * max_batch requests
              wait, the next ones fall due at once;
  "prompt_tokens", "output_tokens":
              {"dist": "lognormal", "median": m, "sigma": s,
               "min": lo, "max": hi}, clipped to [lo, hi];
  "shared_prefix" (optional): {"tokens": t, "groups": g}
              every prompt starts with one of g prefixes of t tokens,
              drawn from the seed (the groups in turn, in a seeded
              order), followed by its own prompt_tokens.

Every seed gets the same multiset of sizes and of gaps, in another order:
the i-th of n values is the distribution's quantile at (i + 0.5) / n, and
the seed permutes them. So a seed changes which request comes when, not
how much work the window holds. Poisson traffic has n = ceil(rate *
seconds), and the gaps' quantiles sum to about n / rate, so every request
falls due inside the window.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


BACKLOG_BLOCKS = 8


@dataclasses.dataclass(frozen=True)
class Item:
    due: float | None      # seconds after the window opens; None: backlog
    prompt_len: int        # the whole prompt, shared prefix included
    max_new: int
    prefix: int = -1       # shared-prefix group, -1: none


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, in quantile order."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist(math.log(spec["median"]), spec["sigma"])
    x = np.array([math.exp(nd.inv_cdf((i + 0.5) / n)) for i in range(n)])
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """n stratified exponential gaps (seconds), in quantile order."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def _poisson(arr: dict, seed: int, seconds: float) -> list:
    b = int(arr.get("burst", 1))
    bursts = math.ceil(arr["rate_rps"] * seconds / b)
    due = np.cumsum(_rng(seed, 0).permutation(
        exp_gaps(arr["rate_rps"] / b, bursts)))
    return [float(d) for d in np.repeat(due, b)]


def _backlog(arr: dict, seed: int, seconds: float) -> list:
    return [None] * arr["requests"]


# arrival kind -> due times (seconds, or None for the backlog)
ARRIVALS = {"poisson": _poisson, "backlog": _backlog}


def schedule(mix: dict, seed: int, seconds: float) -> list[Item]:
    """The run's requests in the order they fall due."""
    arr = mix["arrivals"]
    if arr["kind"] not in ARRIVALS:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    due = ARRIVALS[arr["kind"]](arr, seed, seconds)
    if arr["kind"] != "backlog":
        return _items(mix, seed, due, 0)
    # the queue never runs dry: each further block of n is the same
    # multiset again, in the order of another stream
    return [it for c in range(BACKLOG_BLOCKS)
            for it in _items(mix, seed, due, 3 * c)]


def _items(mix, seed, due, stream):
    n = len(due)
    plen = _rng(seed, stream + 1).permutation(
        quantiles(mix["prompt_tokens"], n))
    out = _rng(seed, stream + 2).permutation(
        quantiles(mix["output_tokens"], n))
    sp = mix.get("shared_prefix")
    if sp:
        group = _rng(seed, stream + 3).permutation(
            np.arange(n) % sp["groups"])
        plen = plen + sp["tokens"]
    else:
        group = np.full(n, -1)
    return [Item(None if d is None else float(d), int(p), int(o), int(g))
            for d, p, o, g in zip(due, plen, out, group)]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> np.ndarray:
    """Token ids of request ``index``: uniform over the vocabulary."""
    return _rng(seed, 1 << 20 | index).integers(0, vocab, length,
                                             dtype=np.int32)


def request_prompt(mix: dict, seed: int, index: int, item: Item,
                   vocab: int) -> np.ndarray:
    """The prompt of request ``index``: its group's shared prefix, if the
    mix has one, then token ids of its own."""
    if item.prefix < 0:
        return prompt_tokens(seed, index, item.prompt_len, vocab)
    t = mix["shared_prefix"]["tokens"]
    pre = _rng(seed, 1 << 21 | item.prefix).integers(0, vocab, t,
                                                      dtype=np.int32)
    return np.concatenate([pre, prompt_tokens(seed, index,
                                              item.prompt_len - t, vocab)])


def length_range(mix: dict) -> tuple[int, int]:
    """The shortest and the longest prompt the mix can send."""
    p = mix["prompt_tokens"]
    extra = mix.get("shared_prefix", {}).get("tokens", 0)
    return int(p["min"]) + extra, int(p["max"]) + extra
