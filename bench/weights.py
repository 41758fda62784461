"""Seeded weights for the benchmark, made on the device in one jitted call.

A configuration's reference module gives a weight *plan*: one entry per
leaf, ``(path, shape, dtype, rule)``, in the layout the served program
takes. ``make(plan, seed)`` turns it into a nested dict of arrays, each
leaf drawn from its own key, ``fold_in(key(seed), crc32(path))``, so a
leaf's values depend on the seed and its path alone. The program is
given that dict, and the reference draws the same leaves again after
the program's state is freed: it takes no array the program made.

Rules (the second item is the rule's parameter):

  ("sign", e)      +-e with equal odds
  ("normal", std)  normal with that standard deviation
  ("uniform", a)   uniform on [-a, a]
  ("const", c)     every entry c
"""

from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp


def draw(key, shape, dtype, rule):
    kind, a = rule
    if kind == "sign":
        x = jnp.where(jax.random.bernoulli(key, 0.5, shape), a, -a)
    elif kind == "normal":
        x = jax.random.normal(key, shape, jnp.float32) * a
    elif kind == "uniform":
        x = jax.random.uniform(key, shape, jnp.float32, -a, a)
    elif kind == "const":
        x = jnp.full(shape, a, jnp.float32)
    else:
        raise ValueError(f"unknown weight rule {kind!r}")
    return x.astype(dtype)


def nest(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}."""
    out: dict = {}
    for path, x in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    return out


def flatten(tree, prefix="") -> dict:
    """Inverse of ``nest`` for a dict-of-dicts tree."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def _seed_key(seed: int):
    # the legacy uint32[2] key that PRNGKey(seed) gives, for any seed below
    # 2**64, passed as data so that one compile serves every seed
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)


def _draw_leaf(key, path, shape, dtype, rule):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return draw(k, shape, jnp.dtype(dtype), rule)


def _make(key, plan):
    return nest({p: _draw_leaf(key, p, s, d, r) for p, s, d, r in plan})


def make(plan, seed: int, shardings=None):
    """All leaves of ``plan`` as one jitted program on the default device,
    or laid out by ``shardings`` (a tree like the params). ``seed`` is a
    traced argument, so every seed reuses one compile."""
    plan = tuple((p, tuple(s), str(d), tuple(r)) for p, s, d, r in plan)
    return jax.jit(partial(_make, plan=plan),
                   out_shardings=shardings)(_seed_key(seed))
