"""Fused binary dense layer: XNOR-matmul -> affine (batch-norm in inference
form) -> hardtanh -> sign -> re-pack, all VMEM-resident.

This is BEANNA's dataflow step 9 ("partial sums accumulators through
activation and normalization units, then back into the activation BRAMs")
as a single Pallas kernel: the float intermediate never touches HBM, and the
layer's output is already bit-packed for the next binary layer.

Grid is (M // bm,): each step holds the FULL packed weight matrix (N, Kp)
in VMEM — for the paper's 1024x1024 layers that is 1024*32*4 B = 128 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.binarize import LANE_BITS


def _kernel(pa_ref, pw_ref, scale_ref, shift_ref, out_ref, *, k_total: int,
            kp: int):
    def lane(l, acc):
        a = pa_ref[:, l]
        w = pw_ref[:, l]
        x = jnp.bitwise_xor(a[:, None], w[None, :])
        return acc + jax.lax.population_count(x).astype(jnp.int32)

    bm = pa_ref.shape[0]
    n = pw_ref.shape[0]
    pc = jax.lax.fori_loop(0, kp, lane, jnp.zeros((bm, n), jnp.int32))
    dot = (jnp.int32(k_total) - 2 * pc).astype(jnp.float32)
    y = dot * scale_ref[0, :][None, :] + shift_ref[0, :][None, :]
    bits = (y >= 0).astype(jnp.uint32)
    bits = bits.reshape(bm, n // LANE_BITS, LANE_BITS)
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint32)
    out_ref[...] = jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("k", "bm", "interpret"))
def hybrid_dense_pallas(pa: jax.Array, pw: jax.Array, scale: jax.Array,
                        shift: jax.Array, *, k: int, bm: int = 256,
                        interpret: bool = False) -> jax.Array:
    """pa (M, Kp) u32, pw (N, Kp) u32, scale/shift (N,) f32 -> (M, N/32) u32."""
    m, kp = pa.shape
    n = pw.shape[0]
    assert n % LANE_BITS == 0
    bm = min(bm, m)
    assert m % bm == 0
    grid = (m // bm,)
    return pl.pallas_call(
        functools.partial(_kernel, k_total=k, kp=kp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i: (i, 0)),
            pl.BlockSpec((n, kp), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n // LANE_BITS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n // LANE_BITS), jnp.uint32),
        interpret=interpret,
        name="hybrid_dense_pallas",
    )(pa, pw, scale.reshape(1, n).astype(jnp.float32),
      shift.reshape(1, n).astype(jnp.float32))
