"""bf16 MXU matmul with optional fused hardtanh epilogue — BEANNA's high
precision mode. K-loop accumulation directly in the revisited f32 output
tile; MXU-aligned 128-multiple block shapes."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, w_ref, out_ref, *, nk: int, hardtanh: bool):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    if hardtanh:
        @pl.when(kstep == nk - 1)
        def _finish():
            out_ref[...] = jnp.clip(out_ref[...], -1.0, 1.0)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "hardtanh",
                                             "interpret"))
def bf16_matmul_pallas(a: jax.Array, w: jax.Array, *, bm: int = 256,
                       bn: int = 256, bk: int = 512, hardtanh: bool = False,
                       interpret: bool = False) -> jax.Array:
    """a (M, K) bf16 x w (K, N) bf16 -> (M, N) f32 (hardtanh optional)."""
    m, k = a.shape
    n = w.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, hardtanh=hardtanh),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="bf16_matmul_pallas",
    )(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
