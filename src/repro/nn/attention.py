"""Attention library + backend dispatch: GQA (qk-norm / bias variants),
MLA, cross-attention, KV caches.

This module mirrors ``kernels/ops.py``'s per-layer lowering mux, applied to
attention: every model calls one of three public entrypoints per shape
family and the concrete lowering is resolved per call —

  prefill_attention   full-sequence self attention (train / prefill),
                      causal by default, optional kv_len for right-padded
                      batches
  decode_attention    single-query attend over a preallocated KV cache
                      (kv_len = valid cache length per sequence)
  cross_attention     non-causal attention over an encoder context
                      (whisper cross-attn, llama-vision gated blocks,
                      whisper encoder self-attn)

Backends (``resolve_attn_impl``):

  "xla_ref"        score-materializing reference: unchunked dot_attention,
                   or a lax.scan over query chunks for long causal prefill
                   (score tile (B, H, chunk, T))
  "xla_blockwise"  blockwise online-softmax scan over query x kv blocks
                   (kernels/flash_attention.blockwise_attention_xla) — the
                   score matrix never exceeds one (q_block, kv_block) tile
  "pallas_flash"   the Pallas flash kernel (TPU; interpret=True on CPU)
  "auto"           xla_ref on CPU (bit-compatible with the historical
                   path) and under a multi-device mesh, pallas_flash on
                   one accelerator for prefill/cross;
                   decode always resolves to xla_ref (a single-query
                   attend is already O(T) with no score blowup)

MLA decode stays on the matrix-absorbed path (scores against the
compressed c_kv cache) — it never materializes expanded K/V at all, which
beats any blockwise scheme for that layout; MLA *prefill* (expanded KV)
routes through prefill_attention like everyone else.

Shapes: q (B, S, Hq, D), k/v (B, T, Hkv, D); GQA groups G = Hq // Hkv.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e9

ATTN_IMPLS = ("auto", "xla_ref", "xla_blockwise", "pallas_flash")

KV_CACHE_IMPLS = ("auto", "bf16", "int8", "binary")


def resolve_kv_cache(impl: str = "auto") -> str:
    """Resolve ``ModelConfig.kv_cache`` to a concrete cache codec name.

    "auto" is bf16 — the historical dense layout — so every pre-codec
    parity test and serving path is unchanged by default. The codec
    implementations (including the dequant-fused decode paths for int8 /
    binary) live in ``serving/kvcache.py``; this module keeps the bf16
    reference seams (``init_kv_cache`` / ``cache_update_decode`` /
    ``decode_attention``) that the bf16 codec delegates to.
    """
    if impl not in KV_CACHE_IMPLS:
        raise ValueError(
            f"unknown kv cache codec {impl!r}; known: {KV_CACHE_IMPLS}")
    return "bf16" if impl == "auto" else impl


def resolve_attn_impl(impl: str = "auto", *, family: str = "prefill") -> str:
    """family in {prefill, decode, cross} -> concrete impl for this call.

    Decode is one query against a cache: the scores are already O(T) and
    the blockwise machinery buys nothing, so auto keeps the reference path.
    On CPU auto also stays on xla_ref for prefill — it is bit-identical to
    the pre-flash behaviour (tests and the serving parity suite depend on
    that); the blockwise paths remain selectable explicitly everywhere.
    Under a multi-device mesh it stays on xla_ref too: GSPMD partitions
    that, not a Pallas kernel.
    """
    if impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attn impl {impl!r}; known: {ATTN_IMPLS}")
    if impl != "auto":
        return impl
    if family == "decode":
        return "xla_ref"
    from repro.distributed.sharding import sharded_mesh_active
    if jax.default_backend() == "cpu" or sharded_mesh_active():
        return "xla_ref"
    return "pallas_flash"


# ---------------------------------------------------------------------------
# xla_ref internals (score-materializing; kept as the oracle)
# ---------------------------------------------------------------------------

def _grouped_scores(q, k):
    """q (B,S,Hk,G,D), k (B,T,Hk,D) -> scores (B,Hk,G,S,T).

    Standard GQA pairing: query head h uses kv head h // G (kv-major)."""
    return jnp.einsum("bshgd,bthd->bhgst", q, k,
                      preferred_element_type=jnp.float32)


def _grouped_out(w, v):
    """w (B,Hk,G,S,T), v (B,T,Hk,D) -> (B,S,Hk,G,D)."""
    return jnp.einsum("bhgst,bthd->bshgd", w.astype(v.dtype), v)


def dot_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None,
                  scale: float | None = None):
    """Unchunked grouped attention (internal reference; models should call
    the dispatch entrypoints). q_offset: absolute pos of q[0] for causal
    masking against a longer k/v; kv_len: valid cache length (int or
    array)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, s, hkv, g, d)
    scores = _grouped_scores(qg, k) * scale  # (B,Hk,G,S,T)
    t = k.shape[1]
    mask = None
    if causal:
        qpos = jnp.arange(s) + q_offset
        kpos = jnp.arange(t)
        mask = qpos[:, None] >= kpos[None, :]
    if kv_len is not None:
        kvl = jnp.broadcast_to(
            jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
        valid = (jnp.arange(t)[None, :] < kvl[:, None]).reshape(b, 1, 1, 1, t)
        scores = jnp.where(valid, scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = _grouped_out(w, v)
    return out.reshape(b, s, hq, v.shape[-1])  # v head dim may differ (MLA)


def chunked_causal_attention(q, k, v, *, chunk: int = 1024,
                             scale: float | None = None, kv_len=None):
    """Causal self-attention, scanned over query chunks (bounded memory).

    Falls back to one chunk when S <= chunk. A final ragged chunk is
    handled by padding the query block — the padded rows attend only to
    real keys (causal mask over real positions) and are sliced off, so
    non-power-of-two prompt lengths are exact, not an assert.
    """
    b, s, hq, d = q.shape
    if s <= chunk:
        return dot_attention(q, k, v, causal=True, scale=scale,
                             kv_len=kv_len)
    n = -(-s // chunk)
    sp = n * chunk
    qp = q if sp == s else jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    qc = qp.reshape(b, n, chunk, hq, d).transpose(1, 0, 2, 3, 4)

    def body(_, args):
        i, qi = args
        off = i * chunk
        # attend only to keys < off + chunk: slice is dynamic in i, so attend
        # to the full prefix and mask; memory is (B,G,Hk,chunk,S).
        oi = dot_attention(qi, k, v, causal=True, q_offset=off, scale=scale,
                           kv_len=kv_len)
        return None, oi

    _, out = jax.lax.scan(body, None, (jnp.arange(n), qc))
    # v's head dim may differ from q's (MLA: dv != dn+dr)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, sp, hq, v.shape[-1])
    return out[:, :s]


# ---------------------------------------------------------------------------
# dispatch entrypoints (one per shape family)
# ---------------------------------------------------------------------------

def prefill_attention(q, k, v, *, causal: bool = True, kv_len=None,
                      chunk: int = 1024, scale: float | None = None,
                      impl: str = "auto"):
    """Full-sequence attention (train / prefill). kv_len masks keys past
    each sequence's true length in a right-padded batch (bit-identical for
    real rows — causality already hides trailing pads from them)."""
    impl = resolve_attn_impl(impl, family="prefill")
    if impl == "xla_ref":
        if causal:
            return chunked_causal_attention(q, k, v, chunk=chunk,
                                            scale=scale, kv_len=kv_len)
        return dot_attention(q, k, v, causal=False, kv_len=kv_len,
                             scale=scale)
    if impl == "xla_blockwise":
        from repro.kernels.flash_attention import blockwise_attention_xla
        return blockwise_attention_xla(q, k, v, causal=causal,
                                       kv_len=kv_len, scale=scale,
                                       q_block=chunk, kv_block=chunk)
    if impl == "pallas_flash":
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, kv_len=kv_len,
                                      scale=scale)
    raise ValueError(impl)


def decode_attention(q, k, v, *, kv_len, scale: float | None = None,
                     impl: str = "auto"):
    """Single-query (S small) attend over a preallocated cache; kv_len is
    the valid cache length per sequence (slot pools decode the full
    preallocated T every tick and mask the tail)."""
    impl = resolve_attn_impl(impl, family="decode")
    if impl == "xla_ref":
        return dot_attention(q, k, v, causal=False, kv_len=kv_len,
                             scale=scale)
    if impl == "xla_blockwise":
        from repro.kernels.flash_attention import blockwise_attention_xla
        return blockwise_attention_xla(q, k, v, causal=False, kv_len=kv_len,
                                       scale=scale)
    if impl == "pallas_flash":
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=False, kv_len=kv_len,
                                      scale=scale)
    raise ValueError(impl)


def cross_attention(q, k, v, *, kv_len=None, impl: str = "auto"):
    """Full (non-causal) attention of q over an encoder context."""
    impl = resolve_attn_impl(impl, family="cross")
    return prefill_attention(q, k, v, causal=False, kv_len=kv_len,
                             impl=impl)


def prefix_prefill_attention(q, k_ctx, v_ctx, ctx_len, k, v, *, kv_len=None,
                             scale: float | None = None):
    """Suffix prefill continuing a cached prefix: one softmax over
    [prefix context ++ suffix].

    Each query attends to (a) every valid position of a right-padded cached
    prefix (columns < ctx_len[b]) and (b) the suffix itself, causally —
    exactly the key set the same tokens would see in a full-sequence
    prefill, so with a lossless context this is the same attention up to
    fp summation order. Queries carry absolute positions (RoPE applied at
    ctx_len[b] + j by the caller); the context arrives already gathered /
    dequantized from the paged pool (serving/kvcache.gather_prefix_context).

    q (B, S, Hq, D); k_ctx/v_ctx (B, P, Hkv, D); ctx_len (B,) valid prefix
    tokens (0 = no cached prefix for that row); k/v (B, S, Hkv, D); kv_len
    (B,) true suffix lengths of a right-padded suffix batch. Score tile is
    (B, Hkv, G, S, P + S) — bounded by the admission buckets, never by the
    pool. Returns (B, S, Hq, D) in q's dtype.
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    p = k_ctx.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, s, hkv, g, d)
    s_ctx = _grouped_scores(qg, k_ctx) * scale          # (B,Hk,G,S,P)
    s_suf = _grouped_scores(qg, k) * scale              # (B,Hk,G,S,S)
    ctx_len = jnp.asarray(ctx_len, jnp.int32)
    valid_ctx = (jnp.arange(p)[None, :] <
                 ctx_len[:, None]).reshape(b, 1, 1, 1, p)
    s_ctx = jnp.where(valid_ctx, s_ctx, NEG_INF)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    mask_suf = causal[None, None, None]
    if kv_len is not None:
        kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                               (b,))
        mask_suf = mask_suf & (jnp.arange(s)[None, :]
                               < kvl[:, None]).reshape(b, 1, 1, 1, s)
    s_suf = jnp.where(mask_suf, s_suf, NEG_INF)
    w = jax.nn.softmax(jnp.concatenate([s_ctx, s_suf], axis=-1), axis=-1)
    out = (_grouped_out(w[..., :p], v_ctx).astype(jnp.float32)
           + _grouped_out(w[..., p:], v).astype(jnp.float32))
    return out.reshape(b, s, hq, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (bf16 reference layout; serving/kvcache.py wraps this and the
# quantized codecs behind one interface — resolve_kv_cache above picks one)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def resolve_cache_update(method: str = "auto") -> str:
    """"auto" picks the scatter that partitions: "mask" whenever a multi-
    device logical mesh is active (the per-batch dynamic_update_slice start
    index defeats GSPMD and all-gathers the cache every step — measured
    7.2 GB/token on whisper decode_32k), "dus" on a single device where
    the masked update's full-cache write would only waste bandwidth.

    Resolution happens at TRACE time: activate the mesh
    (sharding.set_logical_rules) before jitting decode steps. A step traced
    without the mesh keeps "dus" until something forces a retrace — which
    sharded inputs do, since jit cache keys include input shardings."""
    if method != "auto":
        return method
    from repro.distributed.sharding import sharded_mesh_active
    return "mask" if sharded_mesh_active() else "dus"


def cache_update_decode(cache, k_new, v_new, *, method: str = "auto"):
    """Insert one token per sequence at position cache['len'].

    method="dus": per-batch dynamic_update_slice (vmap). Under GSPMD the
    batch-varying start index defeats partitioning and the cache gets
    ALL-GATHERED every step (measured: whisper decode_32k moved 7.2 GB of
    all-gather per token). method="mask": an elementwise where-update that
    partitions trivially along every axis — pure memory traffic, no
    collectives (see EXPERIMENTS.md section Perf, whisper_decode H1).
    method="auto" (the default) picks "mask" when a sharded mesh is active.
    """
    method = resolve_cache_update(method)
    idx = cache["len"]  # (B,)

    if method == "mask":
        t = cache["k"].shape[1]
        mask = (jnp.arange(t)[None, :] == idx[:, None])[..., None, None]

        def upd(buf, new):
            return jnp.where(mask, new.astype(buf.dtype), buf)
    else:
        def upd(buf, new):
            return jax.vmap(
                lambda bufb, nb, i: jax.lax.dynamic_update_slice_in_dim(
                    bufb, nb, i, axis=0)
            )(buf, new, idx)

    return {
        "k": upd(cache["k"], k_new),
        "v": upd(cache["v"], v_new),
        "len": cache["len"] + 1,
    }


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2/V3, MiniCPM3)
# ---------------------------------------------------------------------------

def mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, *, chunk=1024,
                          kv_len=None, impl: str = "auto"):
    """Expanded-KV MLA prefill. q/k_nope (B,S,H,dn), q/k_rope (B,S,H,dr) with
    k_rope broadcast from a single shared rope head; v (B,S,H,dv)."""
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    scale = 1.0 / math.sqrt(q.shape[-1])
    return prefill_attention(q, k, v, causal=True, chunk=chunk, scale=scale,
                             kv_len=kv_len, impl=impl)


def mla_absorbed_decode(q_abs, q_rope, c_cache, kr_cache, kv_len, *,
                        sm_scale):
    """Matrix-absorbed MLA decode against the compressed cache.

    q_abs:  (B, 1, H, kv_lora)   — q_nope already multiplied by W_uk
    q_rope: (B, 1, H, dr)
    c_cache:(B, T, kv_lora), kr_cache: (B, T, dr)
    Returns attention over the compressed values: (B, 1, H, kv_lora).

    Deliberately NOT routed through the blockwise backends: the compressed
    cache is the whole point (T x (kv_lora + dr) resident, no per-head
    K/V), and the score tensor (B, H, 1, T) is already decode-sized.
    """
    s_nope = jnp.einsum("bshc,btc->bhst", q_abs, c_cache,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bshr,btr->bhst", q_rope, kr_cache,
                        preferred_element_type=jnp.float32)
    scores = (s_nope + s_rope) * sm_scale
    t = c_cache.shape[1]
    valid = (jnp.arange(t)[None, :] < kv_len[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btc->bshc", w.astype(c_cache.dtype), c_cache)
    return ctx
