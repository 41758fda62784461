"""Logical-axis sharding rules (MaxText-style), path-regex param specs.

Params are nested dicts; a *rule table* maps path regexes to tuples of
logical axis names, and a MeshRules table maps logical names to mesh axes.
Stacked (scan-over-layers) params carry a leading layer dim: when a leaf has
ndim == len(axes) + 1 the layer dim gets PartitionSpec entry None.

Logical axes used across the model zoo:
  batch      global batch              -> ("pod", "data")
  seq        sequence                  -> None (SP optional)
  vocab      vocabulary                -> "model"
  embed      model width (residual)    -> None  ("data" when fsdp)
  mlp        FFN hidden                -> "model"
  heads      flattened attention heads -> "model"
  kv         head_dim / per-head       -> None
  expert     MoE expert                -> "model"
  kv_lora    MLA compressed dim        -> None
  state      SSM state dims            -> None
  dconv      conv channels             -> "model"
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

DEFAULT_MESH_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "vocab": "model",
    "embed": None,
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",    # flattened KV projection dim (may differ: GQA)
    "cache_heads": "model",  # kv-cache head axis (needs head divisibility)
    "cache_seq": None,       # kv-cache sequence axis ("model" = flash-
                             # decoding-style sequence-parallel attention)
    "kv": None,
    "expert": "model",
    "kv_lora": None,
    "state": None,
    "dconv": "model",
    "fsdp": "data",
}


@dataclass
class MeshRules:
    rules: dict = field(default_factory=lambda: dict(DEFAULT_MESH_RULES))
    fsdp: bool = False

    def mesh_axes(self, logical, axis_names):
        """logical axis name -> mesh axis entry valid for this mesh."""
        m = self.rules.get(logical)
        if m is None:
            return None
        if isinstance(m, tuple):
            got = tuple(a for a in m if a in axis_names)
            return got if got else None
        return m if m in axis_names else None


def logical_to_spec(axes, mesh, mesh_rules: MeshRules) -> P:
    names = mesh.axis_names
    entries = [mesh_rules.mesh_axes(a, names) for a in axes]
    return P(*entries)



def partition_specs(params, rules, mesh, mesh_rules: MeshRules):
    """Build a PartitionSpec pytree for ``params``.

    rules: list of (path_regex, (logical_axis, ...)). First match wins.
    Unmatched leaves are replicated (and flagged when >1 MiB so silent
    replication of big tensors can't slip through).
    """
    compiled = [(re.compile(rx), axes) for rx, axes in rules]
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {}
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        spec = P()
        for rx, axes in compiled:
            if rx.search(pstr):
                core = [mesh_rules.mesh_axes(a, mesh.axis_names)
                        for a in axes]
                extra = leaf.ndim - len(axes)
                if extra not in (0, 1, 2):
                    raise ValueError(
                        f"rule {rx.pattern} axes {axes} vs leaf {pstr} "
                        f"shape {leaf.shape}")
                # FSDP (ZeRO-3): shard the first unsharded *matrix* dim over
                # "data" — never 1-D params, never the layer-stack prefix,
                # and only when that dim divides evenly
                if mesh_rules.fsdp and len(axes) >= 2 \
                        and "data" in mesh.axis_names:
                    used = {x for e in core if e is not None
                            for x in (e if isinstance(e, tuple) else (e,))}
                    if "data" not in used:
                        nd = mesh.shape["data"]
                        for i, e in enumerate(core):
                            if e is None and \
                                    leaf.shape[extra + i] % nd == 0:
                                core[i] = "data"
                                break
                entries = [None] * extra + core
                spec = P(*entries)
                break
        else:
            nbytes = leaf.size * getattr(leaf.dtype, "itemsize", 4)
            if nbytes > (1 << 20):
                import logging
                logging.getLogger(__name__).warning(
                    "replicating large unmatched param %s (%s)", pstr,
                    leaf.shape)
        specs[pstr] = spec
    # rebuild tree with same structure
    treedef = jax.tree_util.tree_structure(params)
    leaves = [specs["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                             for k in path)]
              for path, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def shardings_for(params, rules, mesh, mesh_rules):
    specs = partition_specs(params, rules, mesh, mesh_rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# activation constraints
# --------------------------------------------------------------------------

_ACTIVE_RULES: MeshRules | None = None
_ACTIVE_MESH = None


def set_logical_rules(mesh, mesh_rules: MeshRules):
    global _ACTIVE_RULES, _ACTIVE_MESH
    _ACTIVE_RULES, _ACTIVE_MESH = mesh_rules, mesh


def get_logical_rules():
    """(mesh, rules) currently active — callers that activate rules for a
    scoped region (the serving engines flip them around every jitted call
    so mesh and plain engines coexist in one process) save this and restore
    it afterwards via set_logical_rules(*saved)."""
    return _ACTIVE_MESH, _ACTIVE_RULES


def active_mesh():
    """The mesh activated by set_logical_rules, or None (single-device
    tests). Policy code (e.g. attention.resolve_cache_update) keys off
    this to pick GSPMD-friendly lowerings automatically."""
    return _ACTIVE_MESH


def sharded_mesh_active() -> bool:
    """True while a mesh of more than one device is active. GSPMD cannot
    partition a Mosaic (Pallas TPU) kernel, so "auto" lowerings resolve
    to their XLA twins then (read at trace time, like active_mesh)."""
    return _ACTIVE_MESH is not None and getattr(_ACTIVE_MESH, "size", 1) > 1


def with_logical_constraint(x, axes):
    """Constrain activation sharding by logical axis names (no-op when no
    rules are active, e.g. in single-device tests)."""
    if _ACTIVE_RULES is None or _ACTIVE_MESH is None:
        return x
    spec = logical_to_spec(axes, _ACTIVE_MESH, _ACTIVE_RULES)
    return jax.lax.with_sharding_constraint(x, spec)
