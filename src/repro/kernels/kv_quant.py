"""KV-cache quantize/dequantize kernels — BEANNA's binary *storage* trade
applied to K/V instead of weights.

The paper's headline serving win is memory (binary hidden layers cut
per-inference memory 68% for 0.23% accuracy); in this repro the analogous
hot memory is the serving KV-cache pool. Two kernel families, each with a
Pallas lowering (interpret=True on CPU) and an XLA twin with *identical*
semantics (the oracle, and the GSPMD-shardable path traced inside models):

  int8     per-(token, head) absmax:  scale = absmax / 127 stored bf16,
           values = round(x / scale) clipped to [-127, 127] stored int8.
           2x smaller than bf16 (D + 2 bytes vs 2D per head-row).
  binary   the BEANNA sign + scale trade: values = sign bits packed 32 per
           uint32 lane (core/binarize.pack_bits layout), scale = mean|x|
           per (token, head) stored bf16 (XNOR-Net style absmean).
           ~14x smaller at D=128 (D/8 + 2 bytes vs 2D).

Both quantizers divide by the *stored* (bf16-rounded) scale, so dequant is
consistent between the insert path and every later read, and the Pallas /
XLA lowerings agree bit for bit (same op order, same rounding).

All entrypoints take (..., D) and quantize along the last axis; rows are
flattened to a (N, D) grid for the Pallas calls. ``impl="auto"`` resolves
like the attention backends for int8 (XLA twin on CPU and under a
multi-device mesh, Pallas on one accelerator) and to the XLA twin
everywhere for binary: packing sums
unsigned lanes and splits the lane axis into (D/32, 32), and unpacking
casts uint32 to float, none of which the TPU compiler lowers, so the
binary Pallas kernels stay interpret-mode references.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.binarize import LANE_BITS, packed_len

KV_QUANT_IMPLS = ("auto", "xla", "pallas")


def resolve_kv_quant_impl(impl: str = "auto", *, codec: str = "int8") -> str:
    if impl not in KV_QUANT_IMPLS:
        raise ValueError(
            f"unknown kv-quant impl {impl!r}; known: {KV_QUANT_IMPLS}")
    if impl != "auto":
        return impl
    from repro.distributed.sharding import sharded_mesh_active
    if codec == "binary" or sharded_mesh_active() \
            or jax.default_backend() == "cpu":
        return "xla"
    return "pallas"


# ---------------------------------------------------------------------------
# shared row math (both lowerings call exactly this, so parity is exact)
# ---------------------------------------------------------------------------

def _int8_rows(x):
    """x (..., D) f32 -> (values int8, scales bf16 (..., 1))."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = (amax / 127.0).astype(jnp.bfloat16)
    sf = scale.astype(jnp.float32)
    sf = jnp.where(sf == 0.0, 1.0, sf)
    q = jnp.clip(jnp.round(x / sf), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def _binary_rows(x):
    """x (..., D) f32 -> (packed uint32 (..., ceil(D/32)), scales bf16).

    Bit layout matches core/binarize.pack_bits: bit=1 <-> x >= 0; padding
    bits (D % 32 != 0) are 1 and are never read back (unpack slices [:D]).
    """
    d = x.shape[-1]
    kp = packed_len(d)
    pad = kp * LANE_BITS - d
    scale = jnp.mean(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.bfloat16)
    bits = (x >= 0).astype(jnp.uint32)
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.ones((*x.shape[:-1], pad), jnp.uint32)], axis=-1)
    bits = bits.reshape(*x.shape[:-1], kp, LANE_BITS)
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint32)
    packed = jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)
    return packed, scale


def _int8_dequant_rows(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def _binary_dequant_rows(packed, scale, d, dtype):
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(*packed.shape[:-1], packed.shape[-1] * LANE_BITS)
    signs = bits[..., :d].astype(jnp.float32) * 2.0 - 1.0
    return (signs * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# XLA twins (traced inside models; shardable; the parity oracles)
# ---------------------------------------------------------------------------

def kv_quant_int8_xla(x):
    """(..., D) -> (values int8 (..., D), scales bf16 (...,))."""
    q, scale = _int8_rows(x.astype(jnp.float32))
    return q, scale[..., 0]


def kv_dequant_int8_xla(values, scales, dtype=jnp.bfloat16):
    return _int8_dequant_rows(values, scales[..., None], dtype)


def kv_quant_binary_xla(x):
    """(..., D) -> (packed uint32 (..., ceil(D/32)), scales bf16 (...,))."""
    p, scale = _binary_rows(x.astype(jnp.float32))
    return p, scale[..., 0]


def kv_dequant_binary_xla(packed, scales, d, dtype=jnp.bfloat16):
    return _binary_dequant_rows(packed, scales[..., None], d, dtype)


# ---------------------------------------------------------------------------
# Pallas lowerings: grid over row blocks, one (bn, D) tile per step
# ---------------------------------------------------------------------------

def _quant_int8_kernel(x_ref, v_ref, s_ref):
    q, scale = _int8_rows(x_ref[...].astype(jnp.float32))
    v_ref[...] = q
    s_ref[...] = scale


def _dequant_int8_kernel(v_ref, s_ref, o_ref):
    o_ref[...] = _int8_dequant_rows(v_ref[...], s_ref[...], o_ref.dtype)


def _quant_binary_kernel(x_ref, p_ref, s_ref):
    p, scale = _binary_rows(x_ref[...].astype(jnp.float32))
    p_ref[...] = p
    s_ref[...] = scale


def _dequant_binary_kernel(p_ref, s_ref, o_ref, *, d):
    o_ref[...] = _binary_dequant_rows(p_ref[...], s_ref[...], d, o_ref.dtype)


def _rows(x):
    """(..., D) -> ((N, D), unflatten) with N padded to a block multiple."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _pad_rows(x, bn):
    n = x.shape[0]
    npad = -(-n // bn) * bn - n
    if npad:
        x = jnp.pad(x, ((0, npad), (0, 0)))
    return x, n


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def _quant_int8_call(x2, *, bn, interpret):
    n, d = x2.shape
    grid = (n // bn,)
    return pl.pallas_call(
        _quant_int8_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, d), jnp.int8),
                   jax.ShapeDtypeStruct((n, 1), jnp.bfloat16)],
        interpret=interpret,
        name="_quant_int8_call",
    )(x2)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def _dequant_int8_call(v2, s2, *, bn, interpret):
    # f32 out: int8 * bf16-scale products need > 8 mantissa bits, and the
    # XLA twin computes in f32 — a bf16 out tile would break bit-parity
    n, d = v2.shape
    grid = (n // bn,)
    return pl.pallas_call(
        _dequant_int8_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
        name="_dequant_int8_call",
    )(v2, s2)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def _quant_binary_call(x2, *, bn, interpret):
    n, d = x2.shape
    kp = packed_len(d)
    grid = (n // bn,)
    return pl.pallas_call(
        _quant_binary_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bn, kp), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, kp), jnp.uint32),
                   jax.ShapeDtypeStruct((n, 1), jnp.bfloat16)],
        interpret=interpret,
        name="_quant_binary_call",
    )(x2)


@functools.partial(jax.jit, static_argnames=("d", "bn", "interpret"))
def _dequant_binary_call(p2, s2, *, d, bn, interpret):
    n, kp = p2.shape
    grid = (n // bn,)
    return pl.pallas_call(
        functools.partial(_dequant_binary_kernel, d=d),
        grid=grid,
        in_specs=[pl.BlockSpec((bn, kp), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
        name="_dequant_binary_call",
    )(p2, s2)


def kv_quant_int8_pallas(x, *, bn: int = 256, interpret: bool | None = None):
    """(..., D) -> (values int8 (..., D), scales bf16 (...,))."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    x2, lead = _rows(x)
    x2, n = _pad_rows(x2, bn := min(bn, x2.shape[0]))
    v, s = _quant_int8_call(x2, bn=bn, interpret=interpret)
    return v[:n].reshape(*lead, -1), s[:n, 0].reshape(lead)


def kv_dequant_int8_pallas(values, scales, *, dtype=jnp.bfloat16,
                           bn: int = 256, interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    v2, lead = _rows(values)
    s2 = scales.reshape(-1, 1)
    bn = min(bn, v2.shape[0])
    v2, n = _pad_rows(v2, bn)
    s2, _ = _pad_rows(s2, bn)
    out = _dequant_int8_call(v2, s2, bn=bn, interpret=interpret)
    return out[:n].reshape(*lead, -1).astype(dtype)


def kv_quant_binary_pallas(x, *, bn: int = 256, interpret: bool | None = None):
    """(..., D) -> (packed uint32 (..., ceil(D/32)), scales bf16 (...,))."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    x2, lead = _rows(x)
    x2, n = _pad_rows(x2, bn := min(bn, x2.shape[0]))
    p, s = _quant_binary_call(x2, bn=bn, interpret=interpret)
    return p[:n].reshape(*lead, -1), s[:n, 0].reshape(lead)


def kv_dequant_binary_pallas(packed, scales, d: int, *, dtype=jnp.bfloat16,
                             bn: int = 256, interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    p2, lead = _rows(packed)
    s2 = scales.reshape(-1, 1)
    bn = min(bn, p2.shape[0])
    p2, n = _pad_rows(p2, bn)
    s2, _ = _pad_rows(s2, bn)
    out = _dequant_binary_call(p2, s2, d=d, bn=bn, interpret=interpret)
    return out[:n].reshape(*lead, -1).astype(dtype)


# ---------------------------------------------------------------------------
# dispatch (mirrors kernels/ops.py: one mux per op, "auto" per backend)
# ---------------------------------------------------------------------------

def kv_quant_int8(x, *, impl: str = "auto"):
    impl = resolve_kv_quant_impl(impl)
    return (kv_quant_int8_pallas(x) if impl == "pallas"
            else kv_quant_int8_xla(x))


def kv_dequant_int8(values, scales, *, dtype=jnp.bfloat16,
                    impl: str = "auto"):
    impl = resolve_kv_quant_impl(impl)
    if impl == "pallas":
        return kv_dequant_int8_pallas(values, scales, dtype=dtype)
    return kv_dequant_int8_xla(values, scales, dtype)


def kv_quant_binary(x, *, impl: str = "auto"):
    impl = resolve_kv_quant_impl(impl, codec="binary")
    return (kv_quant_binary_pallas(x) if impl == "pallas"
            else kv_quant_binary_xla(x))


def kv_dequant_binary(packed, scales, d: int, *, dtype=jnp.bfloat16,
                      impl: str = "auto"):
    impl = resolve_kv_quant_impl(impl, codec="binary")
    if impl == "pallas":
        return kv_dequant_binary_pallas(packed, scales, d, dtype=dtype)
    return kv_dequant_binary_xla(packed, scales, d, dtype)
