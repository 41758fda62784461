"""Shared LM machinery: attention blocks (GQA / MLA / cross), hybrid FFNs,
segmented scan-over-layers, losses, KV caches.

Precision policy integration (the paper's technique as a first-class
feature): every FFN goes through ``ffn_init/ffn_apply`` which lower to
either a float SwiGLU or the BEANNA-style binary hardtanh MLP depending on
the block's binary flag. Layers are grouped into *segments* of identical
structure so jax.lax.scan keeps the HLO depth-independent even when the
edge blocks differ from the hidden blocks.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.binary_dense import binary_dense_apply, binary_dense_init
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.nn import layers as nn
from repro.nn import attention as attn_lib
from repro.serving import kvcache as kvc


def padded_vocab(v: int) -> int:
    """Embedding tables are padded to a multiple of 256 so the vocab dim
    shards evenly (Megatron's make_vocab_size_divisible_by); padded logits
    are masked to -1e9 before the softmax."""
    return -(-v // 256) * 256


def mask_pad_logits(logits, vocab: int):
    vp = logits.shape[-1]
    if vp == vocab:
        return logits
    pad = jnp.arange(vp) >= vocab
    return jnp.where(pad, jnp.asarray(-1e9, logits.dtype), logits)


def cdt(cfg):  # compute dtype
    return jnp.dtype(cfg.compute_dtype)


def pdt(cfg):  # param dtype
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# hybrid FFN
# ---------------------------------------------------------------------------

def ffn_init(key, cfg: ModelConfig, *, binary: bool, d_ff: int | None = None):
    """Binary FFNs are identified structurally (keys 'bin_in'/'bin_out') so
    the param tree stays pure arrays for vmap/scan."""
    d_ff = d_ff or cfg.d_ff
    if binary:
        k1, k2 = jax.random.split(key)
        return {
            "bin_in": binary_dense_init(k1, cfg.d_model, d_ff,
                                        dtype=pdt(cfg)),
            "bin_out": binary_dense_init(k2, d_ff, cfg.d_model,
                                         dtype=pdt(cfg)),
        }
    return nn.swiglu_init(key, cfg.d_model, d_ff, dtype=pdt(cfg))


def ffn_apply(p, x, cfg: ModelConfig):
    if "bin_in" in p:
        from repro.core.binary_dense import binary_dense_apply_any
        mode = cfg.policy.binary_mode
        # norm'd residual input feeds sign() inside binary_dense (BEANNA
        # hidden-layer structure: binarize activations and weights);
        # dispatches on latent (training) vs packed/int8 (deployed) params
        h = binary_dense_apply_any(p["bin_in"], x, mode=mode)
        h = wlc(h, ("batch", "seq", "mlp"))
        y = binary_dense_apply_any(p["bin_out"], h, mode=mode)
        return y.astype(x.dtype)
    # binary_impl only matters when these dicts are sign-packed draft
    # weights (serving/spec.binarize_draft_params) — float denses ignore it
    h = nn.dense_apply(p["w_gate"], x, compute_dtype=cdt(cfg),
                       binary_impl=cfg.spec_draft_impl)
    u = nn.dense_apply(p["w_up"], x, compute_dtype=cdt(cfg),
                       binary_impl=cfg.spec_draft_impl)
    h = jax.nn.silu(h.astype(jnp.float32)).astype(cdt(cfg)) * u
    h = wlc(h, ("batch", "seq", "mlp"))
    return nn.dense_apply(p["w_down"], h, compute_dtype=cdt(cfg),
                          binary_impl=cfg.spec_draft_impl)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig):
    d, hq, hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dh = cfg.kv_head_dim()
    ks = jax.random.split(key, 4)
    p = {
        "wq": nn.dense_init(ks[0], d, hq * dh, bias=cfg.qkv_bias, dtype=pdt(cfg)),
        "wk": nn.dense_init(ks[1], d, hkv * dh, bias=cfg.qkv_bias, dtype=pdt(cfg)),
        "wv": nn.dense_init(ks[2], d, hkv * dh, bias=cfg.qkv_bias, dtype=pdt(cfg)),
        "wo": nn.dense_init(ks[3], hq * dh, d, dtype=pdt(cfg)),
    }
    if cfg.qk_norm:
        p["q_norm"] = nn.rmsnorm_init(dh)
        p["k_norm"] = nn.rmsnorm_init(dh)
    return p


def gqa_qkv(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    dh = cfg.kv_head_dim()
    q = nn.dense_apply(p["wq"], x, compute_dtype=cdt(cfg),
                       binary_impl=cfg.spec_draft_impl)
    k = nn.dense_apply(p["wk"], x, compute_dtype=cdt(cfg),
                       binary_impl=cfg.spec_draft_impl)
    v = nn.dense_apply(p["wv"], x, compute_dtype=cdt(cfg),
                       binary_impl=cfg.spec_draft_impl)
    q = q.reshape(b, s, cfg.n_heads, dh)
    k = k.reshape(b, s, cfg.n_kv_heads, dh)
    v = v.reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = nn.rmsnorm_apply(p["q_norm"], q)
        k = nn.rmsnorm_apply(p["k_norm"], k)
    if cfg.use_rope:
        q = nn.apply_rope(q, positions, base=cfg.rope_base)
        k = nn.apply_rope(k, positions, base=cfg.rope_base)
    return q, k, v


def gqa_apply(p, x, cfg: ModelConfig, *, positions):
    """Causal self attention over the full sequence (train / prefill)."""
    q, k, v = gqa_qkv(p, x, cfg, positions)
    q = wlc(q, ("batch", "seq", "heads", "kv"))
    k = wlc(k, ("batch", "seq", "heads", "kv"))
    o = attn_lib.prefill_attention(q, k, v, chunk=cfg.attn_chunk,
                                   impl=cfg.attn_impl)
    o = o.reshape(*x.shape[:2], -1)
    return nn.dense_apply(p["wo"], o, compute_dtype=cdt(cfg),
                          binary_impl=cfg.spec_draft_impl)


def gqa_decode(p, x, cfg: ModelConfig, cache):
    """One-token decode against the cache. x (B, 1, d). The cache layout
    (and for quantized codecs, the dequant-fused attend) is owned by the
    ``cfg.kv_cache`` codec — see serving/kvcache.py. A paged cache (block
    pool + per-slot block table, detected by its "table" leaf) inserts and
    attends through the block table instead; a stacked-pool view (its
    "layer" leaf, from segments_decode) reads its layer of the whole
    segment's pool in place and hands the new row back to the scan."""
    positions = cache["len"][:, None]  # (B, 1)
    q, k, v = gqa_qkv(p, x, cfg, positions)
    codec = kvc.get_codec(cfg.kv_cache)
    # named scopes (op metadata only) let a device trace charge the pool's
    # writes and the attention reads to their layers
    paged = "table" in cache
    with jax.named_scope("kv_pool"):
        cache = (kvc.paged_insert_timestep(cache, k, v, codec) if paged else
                 codec.insert_timestep(cache, k, v, method=cfg.cache_update))
    with jax.named_scope("attn"):
        o = (kvc.paged_decode_attention(q, cache, codec) if paged else
             codec.decode_attention(q, cache, impl=cfg.attn_impl))
    o = o.reshape(*x.shape[:2], -1)
    return nn.dense_apply(p["wo"], o, compute_dtype=cdt(cfg),
                          binary_impl=cfg.spec_draft_impl), cache


def gqa_verify(p, x, cfg: ModelConfig, cache):
    """Multi-token decode against the cache — the speculative-decoding
    verify step. x (B, S, d) carries a draft wave (S = k+1 tokens); their
    exact K/V are appended at positions len..len+S-1 (overwriting the
    draft's approximate entries, which were never visible — every read
    masks by len) and query j attends causally to cols < len + j + 1
    through the same fused blockwise attend the quantized/paged decode
    uses, so one pass scores every draft position. Works on both pool
    layouts and all cache codecs; ``len`` advances by S (the engine rolls
    it back to len + accepted after the accept/reject pass)."""
    s = x.shape[1]
    base = cache["len"]                                 # (B,) pre-insert
    positions = base[:, None] + jnp.arange(s)[None, :]  # (B, S) absolute
    q, k, v = gqa_qkv(p, x, cfg, positions)
    codec = kvc.get_codec(cfg.kv_cache)
    q_lens = positions + 1
    paged = "table" in cache
    with jax.named_scope("kv_pool"):
        cache = (kvc.paged_insert_span(cache, k, v, codec) if paged else
                 codec.insert_span(cache, k, v, method=cfg.cache_update))
    with jax.named_scope("attn"):
        o = (kvc.paged_decode_attention(q, cache, codec, q_lens=q_lens)
             if paged else codec.decode_attention(q, cache, q_lens=q_lens))
    o = o.reshape(*x.shape[:2], -1)
    return nn.dense_apply(p["wo"], o, compute_dtype=cdt(cfg),
                          binary_impl=cfg.spec_draft_impl), cache


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek V2/V3, MiniCPM3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    c, qc = cfg.kv_lora_rank, cfg.q_lora_rank
    ks = jax.random.split(key, 8)
    p = {}
    if qc:
        p["w_dq"] = nn.dense_init(ks[0], d, qc, dtype=pdt(cfg))
        p["q_norm"] = nn.rmsnorm_init(qc)
        p["w_uq"] = nn.dense_init(ks[1], qc, h * (dn + dr), dtype=pdt(cfg))
    else:
        p["w_q"] = nn.dense_init(ks[1], d, h * (dn + dr), dtype=pdt(cfg))
    p["w_dkv"] = nn.dense_init(ks[2], d, c + dr, dtype=pdt(cfg))
    p["kv_norm"] = nn.rmsnorm_init(c)
    p["w_uk"] = nn.dense_init(ks[3], c, h * dn, dtype=pdt(cfg))
    p["w_uv"] = nn.dense_init(ks[4], c, h * dv, dtype=pdt(cfg))
    p["wo"] = nn.dense_init(ks[5], h * dv, d, dtype=pdt(cfg))
    return p


def _mla_q(p, x, cfg, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if "w_dq" in p:
        ql = nn.dense_apply(p["w_dq"], x, compute_dtype=cdt(cfg))
        ql = nn.rmsnorm_apply(p["q_norm"], ql)
        q = nn.dense_apply(p["w_uq"], ql, compute_dtype=cdt(cfg))
    else:
        q = nn.dense_apply(p["w_q"], x, compute_dtype=cdt(cfg))
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = nn.apply_rope(q_rope, positions, base=cfg.rope_base)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, positions):
    c = cfg.kv_lora_rank
    ckv = nn.dense_apply(p["w_dkv"], x, compute_dtype=cdt(cfg))
    c_kv, k_rope = ckv[..., :c], ckv[..., c:]
    c_kv = nn.rmsnorm_apply(p["kv_norm"], c_kv)
    k_rope = nn.apply_rope(k_rope[:, :, None, :], positions,
                           base=cfg.rope_base)[:, :, 0, :]
    return c_kv, k_rope


def mla_apply(p, x, cfg: ModelConfig, *, positions):
    """Full-sequence MLA (expanded KV, chunked causal)."""
    b, s, _ = x.shape
    h, dn, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_ckv(p, x, cfg, positions)
    k_nope = nn.dense_apply(p["w_uk"], c_kv,
                            compute_dtype=cdt(cfg)).reshape(b, s, h, dn)
    v = nn.dense_apply(p["w_uv"], c_kv,
                       compute_dtype=cdt(cfg)).reshape(b, s, h, dv)
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                (*k_rope.shape[:2], h, k_rope.shape[-1]))
    o = attn_lib.mla_prefill_attention(q_nope, q_rope, k_nope, k_rope_b, v,
                                       chunk=cfg.attn_chunk,
                                       impl=cfg.attn_impl)  # (B,S,H,dv)
    o = o.reshape(b, s, -1)
    return nn.dense_apply(p["wo"], o, compute_dtype=cdt(cfg))


def mla_decode(p, x, cfg: ModelConfig, cache):
    """Matrix-absorbed decode against the compressed (c_kv, k_rope) cache."""
    b = x.shape[0]
    h, dn, dv, c = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    positions = cache["len"][:, None]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)           # (B,1,H,dn/dr)
    c_kv, k_rope = _mla_ckv(p, x, cfg, positions)           # (B,1,c),(B,1,dr)
    # append to compressed cache (same GSPMD scatter concern as the KV
    # cache: mask method partitions trivially; see attention.py)
    idx = cache["len"]
    if attn_lib.resolve_cache_update(cfg.cache_update) == "mask":
        t = cache["c"].shape[1]
        m = (jnp.arange(t)[None, :] == idx[:, None])[..., None]
        cache = {
            "c": jnp.where(m, c_kv.astype(cache["c"].dtype), cache["c"]),
            "kr": jnp.where(m, k_rope.astype(cache["kr"].dtype),
                            cache["kr"]),
            "len": cache["len"] + 1,
        }
    else:
        upd = jax.vmap(lambda buf, new, i:
                       jax.lax.dynamic_update_slice_in_dim(buf, new, i,
                                                           axis=0))
        cache = {
            "c": upd(cache["c"], c_kv, idx),
            "kr": upd(cache["kr"], k_rope, idx),
            "len": cache["len"] + 1,
        }
    w_uk = p["w_uk"]["w"].reshape(c, h, dn).astype(cdt(cfg))
    q_abs = jnp.einsum("bshd,chd->bshc", q_nope, w_uk)
    sm_scale = 1.0 / math.sqrt(dn + cfg.qk_rope_dim)
    ctx = attn_lib.mla_absorbed_decode(q_abs, q_rope, cache["c"],
                                       cache["kr"], cache["len"],
                                       sm_scale=sm_scale)  # (B,1,H,c)
    w_uv = p["w_uv"]["w"].reshape(c, h, dv).astype(cdt(cfg))
    o = jnp.einsum("bshc,chv->bshv", ctx, w_uv).reshape(b, 1, -1)
    return nn.dense_apply(p["wo"], o, compute_dtype=cdt(cfg)), cache


# ---------------------------------------------------------------------------
# decoder block (pre-norm residual; attention variant + hybrid FFN + MoE)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockSig:
    attn: str        # "gqa" | "mla"
    ffn: str         # "float" | "binary"
    moe: bool = False


def block_sig(cfg: ModelConfig, idx: int) -> BlockSig:
    binary = cfg.policy.block_is_binary(idx, cfg.n_layers)
    attn = "mla" if cfg.use_mla else "gqa"
    moe = cfg.family == "moe" and idx >= cfg.first_dense_layers
    return BlockSig(attn, "binary" if binary else "float", moe)


def block_init(key, cfg: ModelConfig, sig: BlockSig):
    k1, k2, k3 = jax.random.split(key, 3)
    attn_p = mla_init(k1, cfg) if sig.attn == "mla" else gqa_init(k1, cfg)
    if sig.moe:
        from repro.models.moe import moe_init
        ffn_p = moe_init(k2, cfg, binary=sig.ffn == "binary")
    else:
        ffn_p = ffn_init(k2, cfg, binary=sig.ffn == "binary")
    return {
        "attn": attn_p,
        "ffn": ffn_p,
        "ln1": nn.rmsnorm_init(cfg.d_model),
        "ln2": nn.rmsnorm_init(cfg.d_model),
    }


def block_apply(p, x, cfg: ModelConfig, sig: BlockSig, *, positions):
    """Returns (x, aux) where aux is the MoE balance loss (0.0 for dense)."""
    h = nn.rmsnorm_apply(p["ln1"], x)
    if sig.attn == "mla":
        a = mla_apply(p["attn"], h, cfg, positions=positions)
    else:
        a = gqa_apply(p["attn"], h, cfg, positions=positions)
    x = x + a
    h = nn.rmsnorm_apply(p["ln2"], x)
    aux = jnp.float32(0.0)
    if sig.moe:
        from repro.models.moe import moe_apply
        f, aux = moe_apply(p["ffn"], h, cfg)
    else:
        f = ffn_apply(p["ffn"], h, cfg)
    x = x + f
    return wlc(x, ("batch", "seq", "embed")), aux


def block_decode(p, x, cfg: ModelConfig, sig: BlockSig, cache):
    h = nn.rmsnorm_apply(p["ln1"], x)
    if sig.attn == "mla":
        a, cache = mla_decode(p["attn"], h, cfg, cache)
    else:
        a, cache = gqa_decode(p["attn"], h, cfg, cache)
    x = x + a
    h = nn.rmsnorm_apply(p["ln2"], x)
    if sig.moe:
        from repro.models.moe import moe_apply
        f, _ = moe_apply(p["ffn"], h, cfg)
    else:
        f = ffn_apply(p["ffn"], h, cfg)
    return x + f, cache


def block_verify(p, x, cfg: ModelConfig, sig: BlockSig, cache):
    """block_decode generalized to an S-token verify wave (GQA only —
    MLA's absorbed decode has no multi-token causal-suffix form here)."""
    if sig.attn == "mla":
        raise ValueError("speculative verify requires GQA attention "
                         "blocks; MLA families decode one token at a time")
    h = nn.rmsnorm_apply(p["ln1"], x)
    a, cache = gqa_verify(p["attn"], h, cfg, cache)
    x = x + a
    h = nn.rmsnorm_apply(p["ln2"], x)
    if sig.moe:
        from repro.models.moe import moe_apply
        f, _ = moe_apply(p["ffn"], h, cfg)
    else:
        f = ffn_apply(p["ffn"], h, cfg)
    return x + f, cache


# pad (B, S, ...) to (B, max_len, ...) along axis 1 — one definition for
# both the codec layer and the MLA/whisper cache paths
_pad_time = kvc._pad_time


def block_prefill(p, x, cfg: ModelConfig, sig: BlockSig, *, positions,
                  max_len, seq_lens=None, ctx=None, ctx_len=None):
    """Full-sequence forward that also emits this block's decode cache.

    seq_lens (B,) masks keys past each sequence's true length in a right-
    padded batch. Real rows are bit-identical either way (causality already
    hides trailing pads from them); passing it keeps the pad rows' scores
    from wandering and exercises the kernels' kv_len path.

    ctx / ctx_len carry a cached-prefix context for suffix prefill (the
    radix prefix cache): ctx is this block's {"k", "v"} (B, P, Hkv, D)
    gathered from the paged pool, ctx_len (B,) its valid lengths, and
    ``positions`` must then be the absolute (B, S) positions of the suffix
    tokens. GQA only — MLA's compressed cache is not paged."""
    b, s, _ = x.shape
    h = nn.rmsnorm_apply(p["ln1"], x)
    if sig.attn == "mla":
        if ctx is not None:
            raise ValueError("cached-prefix (suffix) prefill requires GQA "
                             "blocks; MLA caches are not paged")
        q_nope, q_rope = _mla_q(p["attn"], h, cfg, positions)
        c_kv, k_rope = _mla_ckv(p["attn"], h, cfg, positions)
        hh, dn, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
        k_nope = nn.dense_apply(p["attn"]["w_uk"], c_kv,
                                compute_dtype=cdt(cfg)).reshape(b, s, hh, dn)
        v = nn.dense_apply(p["attn"]["w_uv"], c_kv,
                           compute_dtype=cdt(cfg)).reshape(b, s, hh, dv)
        kr_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                (b, s, hh, k_rope.shape[-1]))
        o = attn_lib.mla_prefill_attention(q_nope, q_rope, k_nope, kr_b, v,
                                           chunk=cfg.attn_chunk,
                                           kv_len=seq_lens,
                                           impl=cfg.attn_impl)
        a = nn.dense_apply(p["attn"]["wo"], o.reshape(b, s, -1),
                           compute_dtype=cdt(cfg))
        cache = {"c": _pad_time(c_kv, max_len),
                 "kr": _pad_time(k_rope, max_len),
                 "len": jnp.full((b,), s, jnp.int32)}
    else:
        q, k, v = gqa_qkv(p["attn"], h, cfg, positions)
        with jax.named_scope("attn"):
            if ctx is not None:
                o = attn_lib.prefix_prefill_attention(q, ctx["k"], ctx["v"],
                                                      ctx_len, k, v,
                                                      kv_len=seq_lens)
            else:
                o = attn_lib.prefill_attention(q, k, v, chunk=cfg.attn_chunk,
                                               kv_len=seq_lens,
                                               impl=cfg.attn_impl)
        a = nn.dense_apply(p["attn"]["wo"], o.reshape(b, s, -1),
                           compute_dtype=cdt(cfg),
                           binary_impl=cfg.spec_draft_impl)
        # encode k/v into the configured cache codec (bf16 layout for
        # "auto"; int8/binary quantize at prefill time so the pool never
        # holds a dense bf16 copy)
        with jax.named_scope("kv_pool"):
            cache = kvc.get_codec(cfg.kv_cache).from_prefill(k, v, max_len)
    x = x + a
    h = nn.rmsnorm_apply(p["ln2"], x)
    if sig.moe:
        from repro.models.moe import moe_apply
        f, _ = moe_apply(p["ffn"], h, cfg)
    else:
        f = ffn_apply(p["ffn"], h, cfg)
    return x + f, cache


def segments_prefill(params, x, cfg: ModelConfig, *, positions, max_len,
                     seq_lens=None, ctx=None, ctx_len=None):
    """ctx (optional): per-segment cached-prefix context for suffix prefill
    — {"seg{i}": {"k"/"v": (count, B, P, Hkv, D)}}, scanned over layers
    alongside the stacked params."""
    segs = build_segments(cfg)
    caches = {}
    for si, (sig, start, count) in enumerate(segs):
        stacked = params[f"seg{si}"]
        ctx_seg = None if ctx is None else ctx[f"seg{si}"]

        def one(x, pc, sig=sig):
            p, c = pc
            return block_prefill(p, x, cfg, sig, positions=positions,
                                 max_len=max_len, seq_lens=seq_lens,
                                 ctx=c, ctx_len=ctx_len)

        if cfg.scan_layers and count > 1:
            x, cache = jax.lax.scan(one, x, (stacked, ctx_seg))
        else:
            outs = []
            for i in range(count):
                p_i = jax.tree.map(lambda a: a[i], stacked)
                c_i = (None if ctx_seg is None
                       else jax.tree.map(lambda a: a[i], ctx_seg))
                x, c_out = one(x, (p_i, c_i))
                outs.append(c_out)
            cache = jax.tree.map(lambda *a: jnp.stack(a), *outs)
        caches[f"seg{si}"] = cache
    return x, caches


# ---------------------------------------------------------------------------
# segments: consecutive blocks with identical structure get scanned together
# ---------------------------------------------------------------------------

def build_segments(cfg: ModelConfig) -> list[tuple[BlockSig, int, int]]:
    """Returns [(sig, start, count)], covering blocks 0..n_layers-1."""
    segs = []
    for i in range(cfg.n_layers):
        sig = block_sig(cfg, i)
        if segs and segs[-1][0] == sig:
            segs[-1] = (sig, segs[-1][1], segs[-1][2] + 1)
        else:
            segs.append((sig, i, 1))
    return segs


def segments_init(key, cfg: ModelConfig):
    """Stacked params per segment: {'seg0': stacked_block_params, ...}."""
    segs = build_segments(cfg)
    out = {}
    for si, (sig, start, count) in enumerate(segs):
        keys = jax.random.split(jax.random.fold_in(key, si), count)
        out[f"seg{si}"] = jax.vmap(
            lambda k: block_init(k, cfg, sig))(keys)
    return out


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def segments_apply(params, x, cfg: ModelConfig, *, positions):
    """Returns (x, total_aux)."""
    segs = build_segments(cfg)
    total_aux = jnp.float32(0.0)
    for si, (sig, start, count) in enumerate(segs):
        stacked = params[f"seg{si}"]

        def one(x, p, sig=sig):
            return block_apply(p, x, cfg, sig, positions=positions)

        if cfg.scan_layers and count > 1:
            x, auxs = jax.lax.scan(_maybe_remat(one, cfg), x, stacked)
            total_aux = total_aux + auxs.sum()
        else:
            for i in range(count):
                p_i = jax.tree.map(lambda a: a[i], stacked)
                x, aux = _maybe_remat(one, cfg)(x, p_i)
                total_aux = total_aux + aux
    return x, total_aux


def _decodes_in_place(cfg: ModelConfig, sig: BlockSig, cache) -> bool:
    """A contiguous GQA pool in an ``in_place`` codec (bf16), updated by
    per-slot dus (no sharded mesh): the scan reads the pool in place."""
    return (sig.attn == "gqa" and "table" not in cache
            and kvc.get_codec(cfg.kv_cache).in_place
            and attn_lib.resolve_cache_update(cfg.cache_update) == "dus")


def _segment_decode_in_place(stacked, x, cfg: ModelConfig, sig: BlockSig,
                             cache, count: int):
    """One segment's decode that leaves its stacked pool where it lies:
    the layer scan reads each layer's K/V in place (kvcache's pool view,
    the layer index in ``xs``) and collects each layer's new row as
    ``ys``; one write per slot then puts every layer's row into the
    donated pool. No layer is sliced out, relaid or written back. Each
    layer reads its own ``len`` row (pre-insert) and all advance by one —
    the values the per-layer scan produced."""
    pool = {k: a for k, a in cache.items() if k != "len"}

    def one(x, xs, sig=sig):
        p, layer, lens = xs
        y, view = block_decode(p, x, cfg, sig,
                               {**pool, "len": lens, "layer": layer})
        return y, view["new"]

    xs = (stacked, jnp.arange(count, dtype=jnp.int32), cache["len"])
    if cfg.scan_layers and count > 1:
        x, rows = jax.lax.scan(one, x, xs)
    else:
        outs = []
        for i in range(count):
            x, r = one(x, jax.tree.map(lambda a: a[i], xs))
            outs.append(r)
        rows = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    with jax.named_scope("kv_pool"):
        pool = kvc.write_pool_rows(pool, rows, cache["len"][0])
    return x, {**pool, "len": cache["len"] + 1}


def decode_read_block(cfg: ModelConfig, max_len: int) -> int:
    """Rows per block that one decode tick's attention fetches from each
    slot of a contiguous pool of ``max_len``: the in-place kernel's K/V
    block where every segment takes it (a slot with n rows in the pool
    reads ceil(n / block) blocks), else ``max_len`` (the whole slot)."""
    from repro.kernels.decode_attention import block_rows
    contiguous = {}                     # no "table" leaf
    if (not _decodes_in_place(cfg, block_sig(cfg, 0), contiguous)
            or attn_lib.resolve_attn_impl(cfg.attn_impl,
                                          family="decode") != "pallas_flash"):
        return max_len
    return block_rows(max_len, cfg.n_kv_heads, cfg.kv_head_dim())


def segments_decode(params, x, cfg: ModelConfig, caches):
    """caches: {'seg{i}': stacked_cache}; returns (x, new_caches)."""
    segs = build_segments(cfg)
    new_caches = {}
    for si, (sig, start, count) in enumerate(segs):
        stacked = params[f"seg{si}"]
        cache = caches[f"seg{si}"]
        if _decodes_in_place(cfg, sig, cache):
            x, new_caches[f"seg{si}"] = _segment_decode_in_place(
                stacked, x, cfg, sig, cache, count)
            continue

        def one(x, pc, sig=sig):
            p, c = pc
            y, c2 = block_decode(p, x, cfg, sig, c)
            return y, c2

        if cfg.scan_layers and count > 1:
            x, c2 = jax.lax.scan(one, x, (stacked, cache))
        else:
            outs = []
            for i in range(count):
                p_i = jax.tree.map(lambda a: a[i], stacked)
                c_i = jax.tree.map(lambda a: a[i], cache)
                x, ci2 = one(x, (p_i, c_i))
                outs.append(ci2)
            c2 = jax.tree.map(lambda *a: jnp.stack(a), *outs)
        new_caches[f"seg{si}"] = c2
    return x, new_caches


def segments_verify(params, x, cfg: ModelConfig, caches):
    """segments_decode for an S-token verify wave: same scan-over-layers
    structure, block_verify per block."""
    segs = build_segments(cfg)
    new_caches = {}
    for si, (sig, start, count) in enumerate(segs):
        stacked = params[f"seg{si}"]
        cache = caches[f"seg{si}"]

        def one(x, pc, sig=sig):
            p, c = pc
            y, c2 = block_verify(p, x, cfg, sig, c)
            return y, c2

        if cfg.scan_layers and count > 1:
            x, c2 = jax.lax.scan(one, x, (stacked, cache))
        else:
            outs = []
            for i in range(count):
                p_i = jax.tree.map(lambda a: a[i], stacked)
                c_i = jax.tree.map(lambda a: a[i], cache)
                x, ci2 = one(x, (p_i, c_i))
                outs.append(ci2)
            c2 = jax.tree.map(lambda *a: jnp.stack(a), *outs)
        new_caches[f"seg{si}"] = c2
    return x, new_caches


def set_cache_lengths(caches, seq_lens):
    """Override per-sequence cache lengths after a right-padded prefill.

    Lives behind the cache-codec seam now (serving/kvcache.py, where the
    pad-invisibility contract is documented); layout-generic because every
    codec stores time-axis leaves plus the same ``len`` leaf. Kept here as
    the public model-side entrypoint.
    """
    return kvc.set_cache_lengths(caches, seq_lens)


def cache_insert_slots(pool, new, slots):
    """Scatter per-request prefill caches into decode-pool slots.

    Lives behind the cache-codec seam now (serving/kvcache.py): prefill
    encodes into the same codec layout as the pool, so the scatter
    (including the out-of-range ``mode="drop"`` contract for padded
    prefill groups) is one tree map whatever the codec.
    """
    return kvc.cache_insert_slots(pool, new, slots)


def init_segment_caches(cfg: ModelConfig, batch: int, max_len: int,
                        dtype=jnp.bfloat16):
    """Empty decode caches per segment. GQA segments allocate in the
    ``cfg.kv_cache`` codec's layout; MLA's compressed cache is already the
    memory optimization for that family and stays dense."""
    segs = build_segments(cfg)
    codec = kvc.get_codec(cfg.kv_cache)
    caches = {}
    for si, (sig, start, count) in enumerate(segs):
        if sig.attn == "mla":
            one = {
                "c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
                "len": jnp.zeros((batch,), jnp.int32),
            }
        else:
            one = codec.init(batch, max_len, cfg.n_kv_heads,
                             cfg.kv_head_dim(), dtype)
        caches[f"seg{si}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (count, *a.shape)), one)
    return caches


def init_paged_segment_caches(cfg: ModelConfig, n_blocks: int,
                              block_size: int, max_batch: int, n_pages: int,
                              dtype=jnp.bfloat16):
    """Paged decode pool per segment: a shared (n_blocks, block_size, ...)
    block pool in the ``cfg.kv_cache`` codec's layout plus per-slot block
    tables (see serving/kvcache.init_paged). GQA segments only: MLA's
    compressed per-slot cache is already its memory optimization and has
    no block layout to share."""
    segs = build_segments(cfg)
    codec = kvc.get_codec(cfg.kv_cache)
    caches = {}
    for si, (sig, start, count) in enumerate(segs):
        if sig.attn == "mla":
            raise ValueError(
                "paged KV pool requires GQA attention blocks; "
                f"segment {si} of {cfg.name!r} is MLA (use the "
                "slot-contiguous pool for MLA families)")
        one = kvc.init_paged(codec, n_blocks, block_size, cfg.n_kv_heads,
                             cfg.kv_head_dim(), max_batch, n_pages, dtype)
        caches[f"seg{si}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (count, *a.shape)), one)
    return caches


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits, labels, *, z_loss: float = 1e-4):
    """Mean token CE with z-loss; logits (..., V) f32, labels (...) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = lse - ll
    if z_loss:
        ce = ce + z_loss * lse**2
    return ce.mean()
