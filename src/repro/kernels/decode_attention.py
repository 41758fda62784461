"""Decode attention that reads a segment's stacked KV pool where it lies.

One query per slot attends over rows ``[0, kv_len[b])`` of layer ``layer``
of a ``(L, B, T, Hkv, D)`` pool, plus the slot's new row (this tick's K/V,
not yet in the pool) — the decode scan leaves the pool untouched and
writes the rows after it (``models/lm_common.segments_decode``). The
layer index and the per-slot lengths are scalar-prefetch operands, so
the K/V ``index_map`` picks ``(layer, slot, block)`` straight out of the
pool: no per-layer slice, no relayout, no copy. Blocks past a slot's
last valid block are clamped to it, which re-selects the block already
in VMEM, so no DMA is issued for them and their step is skipped.

Layout. The TPU keeps a pool whose head_dim is not a multiple of 128
(stablelm's 80) time-minor, ``(L, B, Hkv, D, T)`` in memory, rather than
pad D to 128 lanes; one whose head_dim is (qwen3's 128) stays row-major.
The kernel takes the view that matches that memory order — a logical
transpose that XLA lowers as a bitcast — so the pool is read as stored:

  time-minor  K/V blocks (Hkv, D, bk): scores and output are batched
              matmuls over kv heads, time on lanes
  row-major   K/V blocks (bk, Hkv, D), moved head-major in VMEM

A shape whose layout differs from this rule still computes the same
numbers; XLA then copies the pool to the view (``tests/test_tpu_compile``
checks both cells' shapes compile without one).

GQA groups ride in the query block (Hkv, G, D), as in ``_flash_call``;
the online softmax starts from the new row and accumulates in f32. Rows
at or past ``kv_len`` score NEG_INF and their V rows are zeroed, so
whatever a block holds past the slot's length (stale rows, padding past
T) never reaches the output; a slot with ``kv_len`` 0 reads no block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF

# one K (or V) block's bytes; four are resident (K and V, double-buffered)
_BLOCK_BYTES = 3 * 2**19


def block_rows(t: int, hkv: int, d: int, itemsize: int = 2) -> int:
    """Pool rows per K/V block: a multiple of 128 (the lane tile when time
    is minor), at most 512, halved until one block fits ``_BLOCK_BYTES``;
    the whole pool when it is shorter than that."""
    bk = 512
    while bk > 128 and bk * hkv * d * itemsize > _BLOCK_BYTES:
        bk //= 2
    return t if t <= bk else bk


def _kernel(layer_ref, lens_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale: float, bk: int,
            nk: int, time_minor: bool):
    del layer_ref                       # used by the index maps only
    kv_len = lens_ref[pl.program_id(0)]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():                        # the softmax of the new row alone
        m_scr[...] = jnp.einsum("hgd,hzd->hgz", q_ref[...], kn_ref[...],
                                preferred_element_type=jnp.float32) * scale
        l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = jnp.broadcast_to(vn_ref[...].astype(jnp.float32),
                                        acc_scr.shape)

    @pl.when(j * bk < kv_len)
    def _step():
        q = q_ref[...]                                   # (Hkv, G, D)
        k = k_ref[...]
        v = v_ref[...]
        if time_minor:                                   # (Hkv, D, bk)
            t_axis, dims_s = 2, "hgd,hdt->hgt"
            dims_o = "hgt,hdt->hgd"
        else:                                            # (bk, Hkv, D)
            k, v = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)
            t_axis, dims_s = 1, "hgd,htd->hgt"
            dims_o = "hgt,htd->hgd"
        s = jnp.einsum(dims_s, q, k,
                       preferred_element_type=jnp.float32) * scale
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(cols < kv_len, s, NEG_INF)         # (Hkv, G, bk)
        rows = j * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, t_axis)
        v = jnp.where(rows < kv_len, v, jnp.zeros_like(v))

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.einsum(
            dims_o, p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def _decode_call(q, k_new, v_new, k_pool, v_pool, layer, kv_len, *, scale,
                 bk, interpret):
    _, b, t, hkv, d = k_pool.shape
    g = q.shape[1] // hkv
    tm = d % 128 != 0                  # the pool is stored time-minor
    nk = pl.cdiv(t, bk)
    if tm:
        k_pool = jnp.transpose(k_pool, (0, 1, 3, 4, 2))
        v_pool = jnp.transpose(v_pool, (0, 1, 3, 4, 2))
        kv_block = (None, None, hkv, d, bk)
    else:
        kv_block = (None, None, bk, hkv, d)

    def kv_map(bi, j, layer_ref, lens_ref):
        last = jnp.maximum(pl.cdiv(lens_ref[bi], bk) - 1, 0)
        blk = jnp.minimum(j, last)
        return ((layer_ref[0], bi, 0, 0, blk) if tm
                else (layer_ref[0], bi, blk, 0, 0))

    q_spec = pl.BlockSpec((None, hkv, g, d), lambda bi, j, *_: (bi, 0, 0, 0))
    row_spec = pl.BlockSpec((None, hkv, 1, d),
                            lambda bi, j, *_: (bi, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, nk=nk, time_minor=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nk),
            in_specs=[q_spec, row_spec, row_spec,
                      pl.BlockSpec(kv_block, kv_map),
                      pl.BlockSpec(kv_block, kv_map)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, g, 1), jnp.float32),    # running max
                pltpu.VMEM((hkv, g, 1), jnp.float32),    # running denominator
                pltpu.VMEM((hkv, g, d), jnp.float32),    # unnormalized output
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn_pallas",
    )(layer.reshape(1), kv_len, q.reshape(b, hkv, g, d),
      k_new[:, :, None], v_new[:, :, None], k_pool, v_pool)
    return out.reshape(b, hkv * g, d)


def decode_attention_pallas(q, k_new, v_new, k_pool, v_pool, layer, kv_len,
                            *, scale: float | None = None,
                            block: int | None = None,
                            interpret: bool | None = None):
    """q (B, Hq, D): one query per slot; k_new/v_new (B, Hkv, D): the
    slot's new row; k_pool/v_pool (L, B, T, Hkv, D): a segment's stacked
    pool; layer: int32 scalar; kv_len (B,): pool rows to attend besides
    the new row (clamped to [0, T]). Returns (B, Hq, D) in q's dtype. GQA
    pairs query head h with kv head h // G. ``block`` overrides the rows
    per K/V block (``block_rows``); interpret defaults to the CPU."""
    t, hkv, d = k_pool.shape[2:]
    assert q.shape[1] % hkv == 0, (q.shape, k_pool.shape)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bk = block or block_rows(t, hkv, d, jnp.dtype(k_pool.dtype).itemsize)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    kv_len = jnp.clip(jnp.asarray(kv_len, jnp.int32), 0, t)
    dt = k_pool.dtype
    return _decode_call(q, k_new.astype(dt), v_new.astype(dt), k_pool,
                        v_pool, jnp.asarray(layer, jnp.int32), kv_len,
                        scale=float(scale), bk=int(bk), interpret=interpret)
