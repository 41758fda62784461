"""Blockwise online-softmax attention — the BEANNA on-chip-reuse discipline
applied to QK^T (same tiling family as the partial-sum accumulator BRAMs of
the matmul kernels, and as XNORBIN's on-chip reuse; formulation follows the
Blockwise Parallel Transformer / FlashAttention online softmax).

Two lowerings with identical semantics:

  flash_attention_pallas   grid (B, Hq, S/bq, T/bk) with the kv-block axis
                           innermost; running (m, l, acc) accumulators live
                           in VMEM scratch across kv steps, so the score
                           matrix is never larger than (bq, bk) and the
                           output tile is written exactly once. GQA maps
                           query head h onto kv head h // G in the k/v
                           index_maps — the repeated K/V are never
                           materialized. interpret=True on CPU.
  blockwise_attention_xla  the same recurrence as a lax.scan over query
                           blocks with an inner scan over kv blocks
                           (numerator / denominator / running-max carry, as
                           in the BPT reference) — the GSPMD-shardable path
                           and the oracle the kernel is tested against.

Both support causal + non-causal masking, a q_offset for query blocks taken
from a longer sequence, and per-batch ``kv_len`` masking (padded prefill,
slot-cache decode). Scores/accumulation are f32; output is v's dtype.

VMEM per grid step at defaults (bq=bk=128, D=Dv=128, bf16 in / f32 acc):
  q tile 32 KiB + k tile 32 KiB + v tile 32 KiB + out tile 32 KiB
  + acc scratch 64 KiB + m/l scratch 2*64 KiB (128-lane broadcast)
  + (bq, bk) score intermediate 64 KiB  ->  ~0.4 MiB, far under ~16 MiB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9  # matches nn/attention.py: finite, so fully-masked rows
#                 degrade to a uniform softmax instead of NaN


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _flash_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, q_offset: int,
                  bq: int, bk: int, nk: int):
    kv_len = kvlen_ref[pl.program_id(0)]
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # rows/cols are absolute positions of this (bq, bk) score tile
    row0 = q_offset + i * bq
    col0 = j * bk
    # causal: a kv block strictly above the diagonal contributes nothing —
    # skip its flops entirely (the classic flash-attention block skip)
    visible = True if not causal else (col0 <= row0 + bq - 1)

    @pl.when(visible)
    def _step():
        q = q_ref[0, 0]                       # (bq, D)
        k = k_ref[0, 0]                       # (bk, D)
        v = v_ref[0, 0]                       # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = cols < kv_len
        if causal:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = valid & (rows >= cols)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[:, :1]                                # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, 1, keepdims=True))
        p = jnp.exp(s - m_cur)                               # (bq, bk)
        alpha = jnp.exp(m_prev - m_cur)                      # (bq, 1)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(
            p, 1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)   # rows with no visible block
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "q_offset", "bq", "bk", "interpret"))
def _flash_call(qt, kt, vt, kvlen, *, causal, scale, q_offset, bq, bk,
                interpret):
    """qt (B, Hq, Sp, D), kt/vt (B, Hkv, Tp, D/Dv), kvlen (B,) int32.

    kvlen is a scalar-prefetch operand: it lands in SMEM before the grid
    runs, so any batch size tiles (a (1, 1) VMEM block of a (B, 1) array
    does not satisfy the chip's (8, 128) block rule once B > 1)."""
    b, hq, sp, d = qt.shape
    hkv, tp, dv = kt.shape[1], kt.shape[2], vt.shape[3]
    g = hq // hkv
    nq, nk = sp // bq, tp // bk
    grid = (b, hq, nq, nk)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          q_offset=q_offset, bq=bq, bk=bk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b, h, i, j, _: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b, h, i, j, _: (b, h // g, j, 0)),
                pl.BlockSpec((1, 1, bk, dv),
                             lambda b, h, i, j, _: (b, h // g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, dv),
                                   lambda b, h, i, j, _: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),   # running max
                pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
                pltpu.VMEM((bq, dv), jnp.float32),    # unnormalized output
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, sp, dv), vt.dtype),
        interpret=interpret,
        name="_flash_call",
    )(kvlen, qt, kt, vt)


def _flash_fwd_impl(q, k, v, kvlen, *, causal, scale, q_offset, bq, bk,
                    interpret):
    """Padding + layout around _flash_call. kvlen is always a (B,) int32
    array here (the public wrapper normalizes None/scalars)."""
    b, s, hq, d = q.shape
    t = k.shape[1]
    bq = min(bq, _round_up(s, 8))
    bk = min(bk, _round_up(t, 8))
    sp, tp = _round_up(s, bq), _round_up(t, bk)

    qt = jnp.moveaxis(q, 2, 1)                       # (B, Hq, S, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if sp != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    if tp != t:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, tp - t), (0, 0)))

    out = _flash_call(qt, kt, vt, kvlen, causal=causal, scale=scale,
                      q_offset=q_offset, bq=bq, bk=bk, interpret=interpret)
    return jnp.moveaxis(out[:, :, :s, :], 1, 2)      # (B, S, Hq, Dv)


@functools.lru_cache(maxsize=None)
def _make_flash_vjp(causal, scale, q_offset, bq, bk, interpret):
    """pallas_call has no autodiff rule; back out through the XLA blockwise
    twin instead (identical semantics, and XLA's remat keeps the recompute
    blockwise) — the classic flash recompute-backward."""
    @jax.custom_vjp
    def f(q, k, v, kvlen):
        return _flash_fwd_impl(q, k, v, kvlen, causal=causal, scale=scale,
                               q_offset=q_offset, bq=bq, bk=bk,
                               interpret=interpret)

    def fwd(q, k, v, kvlen):
        return f(q, k, v, kvlen), (q, k, v, kvlen)

    def bwd(res, g):
        q, k, v, kvlen = res
        _, pull = jax.vjp(
            lambda q, k, v: blockwise_attention_xla(
                q, k, v, causal=causal, kv_len=kvlen, scale=scale,
                q_offset=q_offset, q_block=max(bq, 8), kv_block=max(bk, 8)),
            q, k, v)
        dq, dk, dv = pull(g.astype(v.dtype))
        return dq, dk, dv, None

    f.defvjp(fwd, bwd)
    return f


def flash_attention_pallas(q, k, v, *, causal: bool, kv_len=None,
                           scale: float | None = None, q_offset: int = 0,
                           bq: int = 128, bk: int = 128,
                           interpret: bool | None = None):
    """Online-softmax attention. q (B, S, Hq, D), k/v (B, T, Hkv, D/Dv)
    with GQA groups G = Hq // Hkv; kv_len (B,) or scalar masks positions
    >= kv_len (padded prefill / partially-filled decode caches). Returns
    (B, S, Hq, Dv) in v's dtype. Differentiable: the backward pass
    recomputes through blockwise_attention_xla (same semantics)."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    # padded keys are masked the same way short caches are: via kv_len
    if kv_len is None:
        kvlen = jnp.full((b,), t, jnp.int32)
    else:
        kvlen = jnp.minimum(
            jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                             (b,)), t)

    return _make_flash_vjp(causal, scale, q_offset, bq, bk, interpret)(
        q, k, v, kvlen)


# ---------------------------------------------------------------------------
# XLA blockwise-scan reference (identical semantics, shardable HLO)
# ---------------------------------------------------------------------------

def blockwise_attention_xla(q, k, v, *, causal: bool, kv_len=None,
                            scale: float | None = None, q_offset: int = 0,
                            q_block: int = 512, kv_block: int = 512):
    """Same online-softmax recurrence as the Pallas kernel, expressed as a
    scan over query blocks with an inner scan over kv blocks. Memory high-
    water mark is the (B, Hkv, G, q_block, kv_block) score tile instead of
    the full (B, H, S, T) matrix."""
    b, s, hq, d = q.shape
    t, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qb = min(q_block, s)
    kb = min(kv_block, t)
    sp, tp = _round_up(s, qb), _round_up(t, kb)
    nq, nk = sp // qb, tp // kb

    qp = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0))) if sp != s else q
    kp = jnp.pad(k, ((0, 0), (0, tp - t), (0, 0), (0, 0))) if tp != t else k
    vp = jnp.pad(v, ((0, 0), (0, tp - t), (0, 0), (0, 0))) if tp != t else v

    if kv_len is None:
        kvlen = jnp.full((b,), t, jnp.int32)
    else:
        kvlen = jnp.minimum(
            jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                             (b,)), t)

    # blocks leading: q (nq, B, qb, Hkv, G, D), k/v (nk, B, kb, Hkv, D)
    qs = jnp.moveaxis(
        qp.reshape(b, nq, qb, hkv, g, d), 1, 0)
    ks = jnp.moveaxis(kp.reshape(b, nk, kb, hkv, d), 1, 0)
    vs = jnp.moveaxis(vp.reshape(b, nk, kb, hkv, dv), 1, 0)

    def one_q_block(_, args):
        qi, iq = args
        qi = qi.astype(jnp.float32)

        def one_kv_block(carry, args2):
            num, den, m_prev = carry
            kj, vj, jk = args2
            sij = jnp.einsum("bqhgd,bkhd->bhgqk", qi,
                             kj.astype(jnp.float32),
                             preferred_element_type=jnp.float32) * scale
            cols = jk * kb + jnp.arange(kb)
            valid = cols[None, :] < kvlen[:, None]           # (B, kb)
            valid = valid[:, None, None, None, :]
            if causal:
                rows = q_offset + iq * qb + jnp.arange(qb)
                cmask = rows[:, None] >= cols[None, :]       # (qb, kb)
                valid = valid & cmask[None, None, None]
            sij = jnp.where(valid, sij, NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(sij, -1))    # (B,Hk,G,qb)
            p = jnp.exp(sij - m_cur[..., None])
            alpha = jnp.exp(m_prev - m_cur)
            den = den * alpha + jnp.sum(p, -1)
            num = num * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
            return (num, den, m_cur), None

        init = (jnp.zeros((b, hkv, g, qb, dv), jnp.float32),
                jnp.zeros((b, hkv, g, qb), jnp.float32),
                jnp.full((b, hkv, g, qb), NEG_INF, jnp.float32))
        (num, den, _), _ = jax.lax.scan(
            one_kv_block, init, (ks, vs, jnp.arange(nk)))
        den = jnp.where(den == 0.0, 1.0, den)
        oi = num / den[..., None]                            # (B,Hk,G,qb,Dv)
        return None, jnp.moveaxis(oi, 3, 1).reshape(b, qb, hq, dv)

    _, out = jax.lax.scan(one_q_block, None, (qs, jnp.arange(nq)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, sp, hq, dv)[:, :s]
    return out.astype(v.dtype)
