"""Pluggable KV-cache codecs — the BEANNA binary/fp mode-mux applied to
*storage* instead of compute.

Every serving engine preallocates a dense ``(layers, max_batch, max_len,
n_kv, head_dim)`` K/V pool per scan segment; after the slot engine (PR 1)
and flash attention (PR 2), that pool's residency — not score
materialization — caps ``max_batch x max_len`` per device. This module
relocates every cache-layout assumption behind one seam: a small codec
interface with three implementations,

  bf16     the reference layout (``nn/attention.init_kv_cache`` /
           ``cache_update_decode``), bit-compatible with everything that
           existed before this subsystem; ``kv_cache="auto"`` resolves here.
  int8     per-(token, head) absmax:  values int8 + scales bf16
           (~2x smaller: D + 2 bytes vs 2D per head-row).
  binary   the paper's binary-layer trade applied to K/V: sign bits packed
           32/uint32 lane + per-(token, head) absmean scale bf16
           (~14x smaller at D=128: D/8 + 2 bytes vs 2D).

Codec layouts are ordinary pytrees with a ``len`` leaf, so the engine's
slot scatter, ``jax.lax.scan`` stacking, and donation all work unchanged.
Quantized decode attends through a *dequant-fused* blockwise path: a scan
over kv blocks dequantizes one ``(B, kv_block, H, D)`` tile at a time
inside the online-softmax recurrence (same recurrence as
``kernels/flash_attention.blockwise_attention_xla``), so a full bf16 copy
of the cache is never resident in HBM — the live dequantized tile is
bounded by the block size. Quantize/dequantize lower through
``kernels/kv_quant`` (Pallas on accelerators, XLA twins on CPU).

MLA's compressed ``(c_kv, k_rope)`` cache is already the memory
optimization for that attention family and stays bf16; the ``kv_cache``
knob applies to GQA-family K/V pools (dense/MoE transformer blocks).

Stacked-pool views. The bf16 codec (``in_place``) also decodes against a
*view* of a segment's whole stacked pool: ``{"k", "v": (L, B, T, Hkv, D),
"len": (B,), "layer": int32 scalar}``. Its insert leaves the pool as it
is and puts the encoded row in the view (``"new"``); its attend reads
layer ``layer`` where it lies, with the new row beside it
(``kernels/decode_attention``). The decode scan collects every layer's
row and ``write_pool_rows`` puts them into the donated pool after it
(``models/lm_common.segments_decode``): nothing of the pool is sliced,
copied or written back per layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.binarize import packed_len
from repro.kernels import kv_quant as kvq
from repro.nn import attention as attn_lib

NEG_INF = attn_lib.NEG_INF


# ---------------------------------------------------------------------------
# layout-generic ops (every codec shares these; lm_common delegates here)
# ---------------------------------------------------------------------------

def set_cache_lengths(caches, seq_lens):
    """Override per-sequence cache lengths after a right-padded prefill.

    Prefill over a (B, Lb) bucket-padded batch writes K/V for the pad
    positions too and stamps ``len = Lb``. Resetting ``len`` to the true
    prompt lengths makes those pad entries invisible (every attention read
    masks positions >= len) and makes the next decode token overwrite
    position ``seq_lens`` — so a padded prefill is bit-identical to an
    unpadded one from the first decode step on. Layout-generic: only the
    ``len`` leaf is touched, whatever the codec stores alongside it.
    """
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    out = {}
    for name, seg in caches.items():
        seg = dict(seg)
        seg["len"] = jnp.broadcast_to(seq_lens[None, :], seg["len"].shape)
        out[name] = seg
    return out


def cache_insert_slots(pool, new, slots):
    """Scatter per-request prefill caches into decode-pool slots.

    pool leaves are (layers, max_batch, ...) and new leaves (layers, G, ...)
    with identical trailing dims (prefill must be called with the pool's
    max_len). slots (G,) int32 gives the destination batch row per request;
    out-of-range entries (>= max_batch) are dropped, which lets callers pad
    a prefill group to a fixed size without a spare slot to aim at.
    Layout-generic: prefill encodes into the same codec layout as the pool,
    so every leaf pair (quantized values, scales, lengths) lines up.
    """
    with jax.named_scope("kv_pool"):
        return jax.tree.map(
            lambda dst, src: dst.at[:, slots].set(src.astype(dst.dtype),
                                                  mode="drop"),
            pool, new)


def kv_pool_bytes(caches) -> int:
    """Resident bytes of a cache pytree, excluding the tiny ``len`` /
    ``table`` index leaves (so the number is directly comparable to
    bytes_per_token * tokens)."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        key = getattr(path[-1], "key", None)
        if key in ("len", "table"):
            continue
        total += leaf.size * jnp.dtype(leaf.dtype).itemsize
    return total


def kv_pool_byte_breakdown(caches) -> dict:
    """Resident pool bytes split by leaf role — the codec trade, itemized:

      values   quantized/raw K/V data leaves (k/v, packed k_q/v_q, ...)
      scales   per-(token, head) dequant scales (``*_s`` leaves)
      index    the tiny ``len`` / ``table`` bookkeeping leaves

    Host-side only (shape/dtype arithmetic, no device reads) — this is
    what the telemetry registry exposes as kv_pool_*_bytes gauges, so a
    scrape shows *where* the binary codec's 12.8x cut comes from (values
    collapse, scales become the visible share).
    """
    out = {"values": 0, "scales": 0, "index": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        key = getattr(path[-1], "key", None)
        nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
        if key in ("len", "table"):
            out["index"] += nbytes
        elif isinstance(key, str) and key.endswith("_s"):
            out["scales"] += nbytes
        else:
            out["values"] += nbytes
    return out


def kv_pool_bytes_per_device(caches) -> int:
    """Resident cache bytes *per device*: the shard each device actually
    holds, summed over the same leaves as kv_pool_bytes. Equal to
    kv_pool_bytes on a single device; with the head axis sharded over a
    ``model``-axis mesh it shrinks ~1/model — the number the mesh serving
    tests assert on."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        key = getattr(path[-1], "key", None)
        if key in ("len", "table"):
            continue
        shape = leaf.shape
        sh = getattr(leaf, "sharding", None)
        if sh is not None and hasattr(sh, "shard_shape"):
            shape = sh.shard_shape(shape)
        n = 1
        for d in shape:
            n *= d
        total += n * jnp.dtype(leaf.dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# cache shardings: the codec seam speaks NamedShardings
#
# Every codec's layout obeys one naming convention, which is what makes the
# sharding story name-driven instead of shape-driven:
#
#   values leaves  k, v, ek, ev, k_q, v_q, k_p, v_p   head axis at dim -2,
#                                                     time axis at dim -3
#   scale leaves   k_s, v_s                           head axis at dim -1,
#                                                     time axis at dim -2
#   index leaves   len, table                         replicated (host-
#                                                     driven scatters)
#
# and any leading dims (the per-segment layer stack, the slot batch or the
# physical-block axis of a paged pool) are unsharded. MLA's compressed
# ``c``/``kr`` leaves have no head axis and stay replicated. The same spec
# therefore covers the contiguous pool (count, B, T, H, D), the paged pool
# (count, n_blocks, block, H, D) and prefill outputs (count, G, T, H, D):
# cache blocks never gather to one device on their way between them.
# ---------------------------------------------------------------------------

_KV_VALUE_LEAVES = frozenset(
    ["k", "v", "ek", "ev", "k_q", "v_q", "k_p", "v_p"])
_KV_SCALE_LEAVES = frozenset(["k_s", "v_s"])


def cache_partition_specs(caches, mesh, mesh_rules):
    """PartitionSpec pytree for an engine cache pool (either layout, any
    codec). ``mesh`` only needs ``axis_names`` (tests pass a stand-in);
    ``mesh_rules`` is a distributed.sharding.MeshRules — build it with
    launch.specs.mesh_rules_for so head-count divisibility fallbacks
    apply."""
    from jax.sharding import PartitionSpec as P

    head = mesh_rules.mesh_axes("cache_heads", mesh.axis_names)
    seq = mesh_rules.mesh_axes("cache_seq", mesh.axis_names)

    def spec_for(path, leaf):
        name = getattr(path[-1], "key", None)
        if name in _KV_VALUE_LEAVES:
            return P(*([None] * (leaf.ndim - 3)), seq, head, None)
        if name in _KV_SCALE_LEAVES:
            return P(*([None] * (leaf.ndim - 2)), seq, head)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(path, leaf) for path, leaf in flat])


def cache_shardings(caches, mesh, mesh_rules):
    """NamedSharding pytree for device_put / jit out_shardings of a cache
    pool. ``caches`` may be concrete arrays or ShapeDtypeStructs (only leaf
    names and ranks are read)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    specs = cache_partition_specs(caches, mesh, mesh_rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _pad_time(a, max_len):
    """Pad (B, S, ...) to (B, max_len, ...) along axis 1 (zeros: a zero
    scale dequantizes to exactly 0, so pad rows stay inert even before
    set_cache_lengths masks them)."""
    pad = [(0, 0)] * a.ndim
    pad[1] = (0, max_len - a.shape[1])
    return jnp.pad(a, pad)


def _write_span(cache, new_leaves, *, method):
    """Insert S tokens per sequence starting at position cache['len'] for
    every named leaf — the multi-token generalization of _write_timestep
    used by the speculative-decoding verify step. ``len`` advances by S.

    The dus path is _write_timestep's verbatim (dynamic_update_slice takes
    any update length); the mask path gathers each written position's row
    out of ``new`` so one jnp.where covers the whole span."""
    method = attn_lib.resolve_cache_update(method)
    idx = cache["len"]  # (B,)
    s = next(iter(new_leaves.values())).shape[1]
    out = dict(cache)
    if method == "mask":
        for name, new in new_leaves.items():
            buf = cache[name]
            t = buf.shape[1]
            pos = jnp.arange(t)[None, :]                     # (1, T)
            m = (pos >= idx[:, None]) & (pos < idx[:, None] + s)
            src = jnp.clip(pos - idx[:, None], 0, s - 1)     # (B, T)
            src = src.reshape(*src.shape, *([1] * (buf.ndim - 2)))
            gathered = jnp.take_along_axis(new.astype(buf.dtype), src,
                                           axis=1)
            m = m.reshape(m.shape[0], t, *([1] * (buf.ndim - 2)))
            out[name] = jnp.where(m, gathered, buf)
    else:
        for name, new in new_leaves.items():
            buf = cache[name]
            out[name] = jax.vmap(
                lambda b_, n_, i: jax.lax.dynamic_update_slice_in_dim(
                    b_, n_, i, axis=0))(buf, new.astype(buf.dtype), idx)
    out["len"] = idx + s
    return out


def _write_timestep(cache, new_leaves, *, method):
    """Insert one token per sequence at position cache['len'] for every
    named leaf (values, scales, ...). Same dus/mask policy as
    ``nn/attention.cache_update_decode`` (see that docstring for the GSPMD
    rationale), generalized to arbitrary (B, T, ...) leaf ranks."""
    method = attn_lib.resolve_cache_update(method)
    idx = cache["len"]  # (B,)
    out = dict(cache)
    if method == "mask":
        for name, new in new_leaves.items():
            buf = cache[name]
            t = buf.shape[1]
            m = jnp.arange(t)[None, :] == idx[:, None]
            m = m.reshape(m.shape[0], t, *([1] * (buf.ndim - 2)))
            out[name] = jnp.where(m, new.astype(buf.dtype), buf)
    else:
        for name, new in new_leaves.items():
            buf = cache[name]
            out[name] = jax.vmap(
                lambda b_, n_, i: jax.lax.dynamic_update_slice_in_dim(
                    b_, n_, i, axis=0))(buf, new.astype(buf.dtype), idx)
    out["len"] = idx + 1
    return out


def write_pool_rows(pool, new_leaves, idx):
    """Write one decode tick's rows into a segment's stacked pool, after
    its layer scan: ``new_leaves`` holds (L, B, 1, ...) per leaf, written
    at ``[:, b, idx[b]]`` — every layer's ``len`` row is the same (prefill
    scatter, set_cache_lengths and decode keep them so). One
    dynamic_update_slice per slot, which XLA applies to the donated pool
    in place whatever its memory layout (a scatter would pin the pool
    row-major and copy it); start indices clamp as ``_write_timestep``'s
    per-slot dus does."""
    out = dict(pool)
    for name, new in new_leaves.items():
        buf = pool[name]
        new = new.astype(buf.dtype)
        for b in range(buf.shape[1]):
            start = (0, b, idx[b]) + (0,) * (buf.ndim - 3)
            buf = jax.lax.dynamic_update_slice(buf, new[:, b:b + 1], start)
        out[name] = buf
    return out


# ---------------------------------------------------------------------------
# dequant-fused decode: blockwise online softmax over the encoded cache
# ---------------------------------------------------------------------------

def _fused_quant_decode(q, cache, codec, *, scale=None, kv_block: int = 128,
                        q_lens=None):
    """Single-query attention over a quantized cache without materializing
    it. A scan over kv blocks dequantizes one (B, kb, H, D) tile per step
    and folds it into the flash-style (num, den, max) recurrence — the
    bounded-tile discipline of blockwise_attention_xla, with dequant fused
    into the block load. Returns (B, S, Hq, D) in q's dtype.

    q_lens (B, S), optional: per-query visible lengths for the speculative
    verify step — query j attends cols < q_lens[b, j] instead of every
    query sharing cache['len'] (the S>1 causal-suffix case). None keeps
    the decode path bit-identical to before the parameter existed."""
    b, s, hq, d = q.shape
    enc = codec.encoded_leaves(cache)
    t = next(iter(enc.values())).shape[1]
    hkv = codec.n_kv(cache)
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = jnp.minimum(cache["len"].astype(jnp.int32), t)
    if q_lens is not None:
        q_lim = jnp.minimum(q_lens.astype(jnp.int32), t)     # (B, S)

    kb = min(kv_block, t)
    nk = -(-t // kb)

    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32)

    def one_kv_block(carry, jk):
        num, den, m_prev = carry
        # slice the block out of the encoded pool in place — no padded /
        # transposed copy of the whole cache per decode step. A ragged
        # final block is handled by clamping the slice start to t - kb and
        # masking the columns block jk-1 already consumed.
        start = jnp.minimum(jk * kb, t - kb)
        blk = {name: jax.lax.dynamic_slice_in_dim(leaf, start, kb, axis=1)
               for name, leaf in enc.items()}
        k_blk, v_blk = codec.dequant_block(blk, d)     # (B, kb, Hkv, D) f32
        sij = jnp.einsum("bshgd,bkhd->bhgsk", qg, k_blk,
                         preferred_element_type=jnp.float32) * scale
        cols = start + jnp.arange(kb)
        if q_lens is None:
            valid = (cols >= jk * kb) & (cols[None, :] < kv_len[:, None])
            valid = valid[:, None, None, None, :]
        else:
            valid = ((cols >= jk * kb)[None, None, :]
                     & (cols[None, None, :] < q_lim[:, :, None]))
            valid = valid[:, None, None, :, :]      # (B, 1, 1, S, kb)
        sij = jnp.where(valid, sij, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(sij, -1))   # (B, Hkv, G, S)
        p = jnp.exp(sij - m_cur[..., None])
        alpha = jnp.exp(m_prev - m_cur)
        den = den * alpha + jnp.sum(p, -1)
        num = num * alpha[..., None] + jnp.einsum(
            "bhgsk,bkhd->bhgsd", p, v_blk,
            preferred_element_type=jnp.float32)
        return (num, den, m_cur), None

    init = (jnp.zeros((b, hkv, g, s, d), jnp.float32),
            jnp.zeros((b, hkv, g, s), jnp.float32),
            jnp.full((b, hkv, g, s), NEG_INF, jnp.float32))
    (num, den, _), _ = jax.lax.scan(one_kv_block, init, jnp.arange(nk))
    den = jnp.where(den == 0.0, 1.0, den)
    out = num / den[..., None]                          # (B, Hkv, G, S, D)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

class CacheCodec:
    """One KV-cache storage format. Layouts are flat dicts of arrays with a
    ``len`` leaf; all other leaves carry time on axis 1, so the engine's
    scatter / scan stacking / donation never see the codec."""

    name: str = ""
    # decodes against a stacked-pool view (module doc) in place
    in_place: bool = False

    # layout-generic by construction (time-axis leaves + a ``len`` leaf are
    # the layout contract, so one tree scatter / len rewrite serves every
    # codec): these are interface aliases of the module-level functions,
    # which remain the actual call targets (lm_common delegates there) — a
    # codec whose layout breaks the contract needs a new seam, not an
    # override here
    insert_slots = staticmethod(cache_insert_slots)
    set_lengths = staticmethod(set_cache_lengths)

    def init(self, batch, max_len, n_kv, head_dim, dtype=jnp.bfloat16):
        raise NotImplementedError

    def encode(self, k, v):
        """(B, S, H, D) bf16/f32 k, v -> dict of encoded leaves (no len)."""
        raise NotImplementedError

    def from_prefill(self, k, v, max_len):
        """Encode a prefilled (B, S, H, D) k/v pair into a max_len cache."""
        b, s = k.shape[:2]
        enc = {name: _pad_time(leaf, max_len)
               for name, leaf in self.encode(k, v).items()}
        enc["len"] = jnp.full((b,), s, jnp.int32)
        return enc

    def insert_timestep(self, cache, k_new, v_new, *, method="auto"):
        """Insert one token per sequence at position cache['len']."""
        return _write_timestep(cache, self.encode(k_new, v_new),
                               method=method)

    def insert_span(self, cache, k_new, v_new, *, method="auto"):
        """Insert S tokens per sequence starting at cache['len'] (the
        speculative verify step's cache-appending write; S >= 1)."""
        return _write_span(cache, self.encode(k_new, v_new), method=method)

    def materialize(self, cache, dtype=jnp.bfloat16, *, head_dim=None):
        """Full dequantized (k, v), both (B, T, H, D) — tests/debug only;
        the decode path never calls this for quantized codecs. ``head_dim``
        is required only for codecs whose layout can't recover D (binary
        bit-packing rounds D up to whole uint32 lanes)."""
        raise NotImplementedError

    def decode_attention(self, q, cache, *, scale=None, impl="auto",
                         q_lens=None):
        raise NotImplementedError

    def bytes_per_token(self, n_kv: int, head_dim: int) -> int:
        """Resident cache bytes per token per layer (k and v together)."""
        raise NotImplementedError

    # --- hooks for the fused decode paths (quantized and paged pools) ---

    def encoded_leaves(self, cache):
        return {k: v for k, v in cache.items() if k not in ("len", "table")}

    def n_kv(self, cache):
        raise NotImplementedError

    def dequant_block(self, blk, d):
        """dict of (B, kb, ...) encoded leaves -> (k, v) (B, kb, H, D) f32."""
        raise NotImplementedError


class Bf16Codec(CacheCodec):
    """The reference layout: exactly the pre-codec cache, so every existing
    parity test (and ``kv_cache="auto"``) is unchanged bit for bit."""

    name = "bf16"
    in_place = True

    def init(self, batch, max_len, n_kv, head_dim, dtype=jnp.bfloat16):
        return attn_lib.init_kv_cache(batch, max_len, n_kv, head_dim, dtype)

    def encode(self, k, v):
        return {"k": k, "v": v}

    def insert_timestep(self, cache, k_new, v_new, *, method="auto"):
        if "layer" in cache:
            return {**cache, "new": self.encode(k_new, v_new),
                    "len": cache["len"] + 1}
        # delegate to the historical update (bit-compatible by construction)
        return attn_lib.cache_update_decode(cache, k_new, v_new,
                                            method=method)

    def materialize(self, cache, dtype=jnp.bfloat16, *, head_dim=None):
        return cache["k"].astype(dtype), cache["v"].astype(dtype)

    def decode_attention(self, q, cache, *, scale=None, impl="auto",
                         q_lens=None):
        if q_lens is not None:
            # verify path: per-query lengths only exist on the fused
            # blockwise attend (bf16 passes through dequant_block)
            return _fused_quant_decode(q, cache, self, scale=scale,
                                       q_lens=q_lens)
        if "layer" in cache:
            return _pool_decode_attention(q, cache, scale=scale, impl=impl)
        return attn_lib.decode_attention(q, cache["k"], cache["v"],
                                         kv_len=cache["len"], scale=scale,
                                         impl=impl)

    def n_kv(self, cache):
        return cache["k"].shape[2]

    def dequant_block(self, blk, d):
        # stored dtype passes straight through: the paged decode / context
        # gather read exactly the bytes the insert wrote
        return blk["k"], blk["v"]

    def bytes_per_token(self, n_kv, head_dim):
        return 2 * n_kv * head_dim * 2


def _pool_decode_attention(q, view, *, scale=None, impl="auto"):
    """Single-query attend over layer ``view["layer"]`` of a stacked pool
    view plus its new row, as if the row sat at ``len - 1`` (``len``
    counts it; a full slot's row takes the last place, as the clamped
    insert does). ``auto`` picks the in-place kernel on one accelerator;
    the XLA paths insert the row into the layer's slice and attend."""
    impl = attn_lib.resolve_attn_impl(impl, family="decode")
    layer, new = view["layer"], view["new"]
    if impl == "pallas_flash":
        from repro.kernels.decode_attention import decode_attention_pallas
        t = view["k"].shape[2]
        return decode_attention_pallas(
            q[:, 0], new["k"][:, 0], new["v"][:, 0], view["k"], view["v"],
            layer, jnp.minimum(view["len"] - 1, t - 1), scale=scale)[:, None]
    cache = attn_lib.cache_update_decode(
        {"k": view["k"][layer], "v": view["v"][layer],
         "len": view["len"] - 1}, new["k"], new["v"], method="dus")
    return attn_lib.decode_attention(q, cache["k"], cache["v"],
                                     kv_len=cache["len"], scale=scale,
                                     impl=impl)


class Int8Codec(CacheCodec):
    """values int8 + per-(token, head) absmax scale bf16."""

    name = "int8"

    def init(self, batch, max_len, n_kv, head_dim, dtype=jnp.bfloat16):
        return {
            "k_q": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
            "k_s": jnp.zeros((batch, max_len, n_kv), jnp.bfloat16),
            "v_q": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
            "v_s": jnp.zeros((batch, max_len, n_kv), jnp.bfloat16),
            "len": jnp.zeros((batch,), jnp.int32),
        }

    def encode(self, k, v):
        k_q, k_s = kvq.kv_quant_int8(k)
        v_q, v_s = kvq.kv_quant_int8(v)
        return {"k_q": k_q, "k_s": k_s, "v_q": v_q, "v_s": v_s}

    def materialize(self, cache, dtype=jnp.bfloat16, *, head_dim=None):
        return (kvq.kv_dequant_int8(cache["k_q"], cache["k_s"], dtype=dtype),
                kvq.kv_dequant_int8(cache["v_q"], cache["v_s"], dtype=dtype))

    def decode_attention(self, q, cache, *, scale=None, impl="auto",
                         q_lens=None):
        del impl  # fused path is the whole point; decode is already O(T)
        return _fused_quant_decode(q, cache, self, scale=scale,
                                   q_lens=q_lens)

    def n_kv(self, cache):
        return cache["k_q"].shape[2]

    def dequant_block(self, blk, d):
        return (kvq.kv_dequant_int8_xla(blk["k_q"], blk["k_s"], jnp.float32),
                kvq.kv_dequant_int8_xla(blk["v_q"], blk["v_s"], jnp.float32))

    def bytes_per_token(self, n_kv, head_dim):
        return 2 * n_kv * (head_dim + 2)


class BinaryCodec(CacheCodec):
    """sign bits packed 32/lane + per-(token, head) absmean scale bf16 —
    the BEANNA binary-layer memory trade applied to K/V. Lossy (documented
    tolerance in tests/test_kvcache.py); greedy decode stays coherent but
    is not token-identical to bf16."""

    name = "binary"

    def init(self, batch, max_len, n_kv, head_dim, dtype=jnp.bfloat16):
        kp = packed_len(head_dim)
        return {
            "k_p": jnp.zeros((batch, max_len, n_kv, kp), jnp.uint32),
            "k_s": jnp.zeros((batch, max_len, n_kv), jnp.bfloat16),
            "v_p": jnp.zeros((batch, max_len, n_kv, kp), jnp.uint32),
            "v_s": jnp.zeros((batch, max_len, n_kv), jnp.bfloat16),
            "len": jnp.zeros((batch,), jnp.int32),
        }

    def encode(self, k, v):
        k_p, k_s = kvq.kv_quant_binary(k)
        v_p, v_s = kvq.kv_quant_binary(v)
        return {"k_p": k_p, "k_s": k_s, "v_p": v_p, "v_s": v_s}

    def materialize(self, cache, dtype=jnp.bfloat16, *, head_dim=None):
        if head_dim is None:
            raise ValueError("BinaryCodec.materialize needs head_dim "
                             "(bit-packing rounds D up to whole lanes)")
        return (kvq.kv_dequant_binary(cache["k_p"], cache["k_s"], head_dim,
                                      dtype=dtype),
                kvq.kv_dequant_binary(cache["v_p"], cache["v_s"], head_dim,
                                      dtype=dtype))

    def decode_attention(self, q, cache, *, scale=None, impl="auto",
                         q_lens=None):
        del impl
        return _fused_quant_decode(q, cache, self, scale=scale,
                                   q_lens=q_lens)

    def n_kv(self, cache):
        return cache["k_p"].shape[2]

    def dequant_block(self, blk, d):
        return (kvq.kv_dequant_binary_xla(blk["k_p"], blk["k_s"], d,
                                          jnp.float32),
                kvq.kv_dequant_binary_xla(blk["v_p"], blk["v_s"], d,
                                          jnp.float32))

    def bytes_per_token(self, n_kv, head_dim):
        return 2 * n_kv * (4 * packed_len(head_dim) + 2)


_CODECS = {"bf16": Bf16Codec(), "int8": Int8Codec(), "binary": BinaryCodec()}


def get_codec(name: str = "auto") -> CacheCodec:
    """Resolve a ``ModelConfig.kv_cache`` value ("auto" -> bf16)."""
    return _CODECS[attn_lib.resolve_kv_cache(name)]


# ---------------------------------------------------------------------------
# paged pool: a shared block pool + per-slot block tables
#
# The slot-contiguous pool above gives every slot a private (max_len, H, D)
# region; the paged pool replaces that with one shared pool of fixed-size
# blocks, (n_blocks, block_size, H, D) per layer in any codec's encoded
# layout, plus two index leaves per layer:
#
#   table (max_batch, n_pages) int32   physical block id per (slot, page);
#                                      entries >= n_blocks are holes (free
#                                      slots / pages past the allocation)
#   len   (max_batch,)         int32   valid tokens per slot, as before
#
# Physical blocks are position-agnostic (RoPE is applied before insert), so
# any slot's page j may live in any physical block — which is what lets the
# radix prefix cache (serving/prefix.py) point many slots' leading pages at
# the same blocks. Detection is structural: a cache dict with a "table"
# leaf is paged, so models (lm_common.gqa_decode) and the engine never
# thread an extra flag.
# ---------------------------------------------------------------------------

def init_paged(codec: CacheCodec, n_blocks: int, block_size: int, n_kv: int,
               head_dim: int, max_batch: int, n_pages: int,
               dtype=jnp.bfloat16):
    """One layer's paged pool: codec-encoded block leaves + table/len.

    Reuses ``codec.init`` with (batch=n_blocks, max_len=block_size): every
    codec's encoded leaves carry time on axis 1, so a stack of blocks is
    just a batch of short sequences as far as the codec is concerned."""
    one = codec.init(n_blocks, block_size, n_kv, head_dim, dtype)
    one.pop("len")
    one["table"] = jnp.full((max_batch, n_pages), n_blocks, jnp.int32)
    one["len"] = jnp.zeros((max_batch,), jnp.int32)
    return one


def paged_block_size(cache) -> int:
    """Block size of a paged per-layer cache: every codec's values leaf is
    (n_blocks, block_size, Hkv, ...), so take the deepest encoded leaf
    (scale leaves are one rank lower) and read its time axis."""
    leaf = max((v for k, v in cache.items() if k not in ("len", "table")),
               key=lambda a: a.ndim)
    return leaf.shape[1]


def paged_update_slots(pool, rows, lens, slots):
    """Rebind slots' block tables and lengths (admission / eviction).

    pool: full caches dict {seg: {...}} with per-segment table leaves
    (count, max_batch, n_pages); rows (G, n_pages) int32 physical ids
    (holes >= n_blocks); lens (G,); slots (G,) int32, out-of-range dropped
    (same padded-group contract as cache_insert_slots)."""
    out = {}
    for name, seg in pool.items():
        seg = dict(seg)
        seg["table"] = seg["table"].at[:, slots].set(rows, mode="drop")
        seg["len"] = seg["len"].at[:, slots].set(lens, mode="drop")
        out[name] = seg
    return out


def paged_insert_prefill(pool, new, dest_pages):
    """Scatter a prefill's codec-encoded caches into physical blocks.

    new is the ordinary contiguous prefill cache pytree (leaves
    (count, G, T, ...) with T = n_pages * block_size); each request row's
    time axis is cut into pages and page i is written to physical block
    dest_pages[g, i]. Holes (>= n_blocks) drop — that is how the engine
    (a) skips pages already covered by a shared cached prefix and (b) pads
    prefill groups. The ``len`` leaves of ``new`` are discarded; slot
    lengths are owned by paged_update_slots."""
    out = {}
    for name, seg in pool.items():
        seg = dict(seg)
        for leaf_name, src in new[name].items():
            if leaf_name == "len":
                continue
            dst = seg[leaf_name]
            bs = dst.shape[2]
            count, g, t = src.shape[:3]
            src_p = src.reshape(count, g, t // bs, bs, *src.shape[3:])
            with jax.named_scope("kv_pool"):
                seg[leaf_name] = dst.at[:, dest_pages].set(
                    src_p.astype(dst.dtype), mode="drop")
        out[name] = seg
    return out


def paged_insert_timestep(cache, k_new, v_new, codec: CacheCodec):
    """Per-layer decode insert: encode one token per slot and write it at
    (table[b, len // bs], len % bs). Free slots hit table holes and drop.
    The scatter is an elementwise .at[] gather-write, which partitions like
    the "mask" method (no per-batch dynamic slice start)."""
    idx = cache["len"]                                  # (B,)
    bs = paged_block_size(cache)
    page = idx // bs
    off = idx - page * bs
    phys = jnp.take_along_axis(cache["table"], page[:, None], axis=1)[:, 0]
    out = dict(cache)
    for name, new in codec.encode(k_new, v_new).items():
        buf = cache[name]
        out[name] = buf.at[phys, off].set(new[:, 0].astype(buf.dtype),
                                          mode="drop")
    out["len"] = idx + 1
    return out


def paged_insert_span(cache, k_new, v_new, codec: CacheCodec):
    """Per-layer verify insert: encode S tokens per slot and write token j
    at (table[b, (len+j) // bs], (len+j) % bs) — the multi-token
    generalization of paged_insert_timestep. Positions past the block
    table (free slots' hole rows, overflowing pages) drop."""
    idx = cache["len"]                                   # (B,)
    s = k_new.shape[1]
    bs = paged_block_size(cache)
    table = cache["table"]
    n_pages = table.shape[1]
    n_blocks = next(v for k, v in cache.items()
                    if k not in ("len", "table")).shape[0]
    pos = idx[:, None] + jnp.arange(s)[None, :]          # (B, S)
    page = pos // bs
    off = pos - page * bs
    phys = jnp.take_along_axis(table, jnp.minimum(page, n_pages - 1),
                               axis=1)                   # (B, S)
    phys = jnp.where(page < n_pages, phys, n_blocks)     # overflow -> hole
    out = dict(cache)
    for name, new in codec.encode(k_new, v_new).items():
        buf = cache[name]
        out[name] = buf.at[phys, off].set(new.astype(buf.dtype),
                                          mode="drop")
    out["len"] = idx + s
    return out


def paged_decode_attention(q, cache, codec: CacheCodec, *, scale=None,
                           q_lens=None):
    """Single-query attention through the block table: the same blockwise
    online-softmax recurrence as _fused_quant_decode, with the per-step
    contiguous time slice replaced by a gather of each slot's page-jk
    physical block — one (B, block_size, Hkv, D) tile live per step,
    dequantized (for quantized codecs) inside the block load.

    q_lens (B, S), optional: per-query visible lengths (speculative
    verify); None keeps every query on the slot's kv_len as before."""
    b, s, hq, d = q.shape
    enc = codec.encoded_leaves(cache)
    table = cache["table"]                              # (B, n_pages)
    n_pages = table.shape[1]
    n_blocks = next(iter(enc.values())).shape[0]
    bs_blk = paged_block_size(cache)
    hkv = codec.n_kv(cache)
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = jnp.minimum(cache["len"].astype(jnp.int32), n_pages * bs_blk)
    if q_lens is not None:
        q_lim = jnp.minimum(q_lens.astype(jnp.int32), n_pages * bs_blk)

    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32)

    def one_page(carry, jk):
        num, den, m_prev = carry
        # hole entries (>= n_blocks) clamp to an arbitrary real block; its
        # columns sit past kv_len for that slot, so they mask to NEG_INF
        phys = jnp.minimum(table[:, jk], n_blocks - 1)
        blk = {name: leaf[phys] for name, leaf in enc.items()}
        k_blk, v_blk = codec.dequant_block(blk, d)      # (B, bs, Hkv, D)
        sij = jnp.einsum("bshgd,bkhd->bhgsk", qg, k_blk,
                         preferred_element_type=jnp.float32) * scale
        cols = jk * bs_blk + jnp.arange(bs_blk)
        if q_lens is None:
            valid = (cols[None, :] < kv_len[:, None])[:, None, None,
                                                      None, :]
        else:
            valid = (cols[None, None, :]
                     < q_lim[:, :, None])[:, None, None, :, :]
        sij = jnp.where(valid, sij, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(sij, -1))   # (B, Hkv, G, S)
        p = jnp.exp(sij - m_cur[..., None])
        alpha = jnp.exp(m_prev - m_cur)
        den = den * alpha + jnp.sum(p, -1)
        num = num * alpha[..., None] + jnp.einsum(
            "bhgsk,bkhd->bhgsd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (num, den, m_cur), None

    init = (jnp.zeros((b, hkv, g, s, d), jnp.float32),
            jnp.zeros((b, hkv, g, s), jnp.float32),
            jnp.full((b, hkv, g, s), NEG_INF, jnp.float32))
    (num, den, _), _ = jax.lax.scan(one_page, init, jnp.arange(n_pages))
    den = jnp.where(den == 0.0, 1.0, den)
    out = num / den[..., None]                          # (B, Hkv, G, S, D)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, hq, d).astype(q.dtype)


def gather_prefix_context(pool, ctx_pages, codec: CacheCodec, head_dim: int):
    """Materialize cached-prefix K/V for suffix prefill.

    ctx_pages (G, P) physical block ids (host-clamped into range; rows with
    fewer matched pages repeat block 0, masked downstream by ctx_len).
    Returns {seg: {"k", "v"}} with leaves (count, G, P * block_size, Hkv,
    D) — decoded through the codec once per admission, bounded by the
    context-page bucket, never the whole pool."""
    out = {}
    for name, seg in pool.items():
        enc = {k: v for k, v in seg.items() if k not in ("len", "table")}
        resh = {}
        for leaf_name, leaf in enc.items():
            ga = jnp.take(leaf, ctx_pages, axis=1)  # (count, G, P, bs, ...)
            resh[leaf_name] = ga.reshape(ga.shape[0], ga.shape[1],
                                         ga.shape[2] * ga.shape[3],
                                         *ga.shape[4:])
        k, v = codec.dequant_block(resh, head_dim)
        out[name] = {"k": k, "v": v}
    return out
