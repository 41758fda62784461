"""+-1-as-int8 MXU matmul with on-the-fly unpack from bit-packed weights.

The TPU-native *compute-bound* realization of BEANNA's binary mode: weights
live in HBM bit-packed (16x smaller than bf16); each grid step unpacks a
(bn, bkp) packed tile inside VMEM and feeds the MXU at its 394 TOP/s int8
rate (2x bf16 peak). Activations arrive as +-1 int8.

Layout. Bit b of packed lane j holds weight column 32*j + b. Unpacking a
lane into 32 adjacent columns would need a lane-splitting reshape, which
Mosaic does not lower; instead the *activations* are regrouped by bit
outside the kernel — a_bits[b, m, j] = a[m, 32*j + b] — so bit plane b of
the weight tile, ((pw >> b) & 1) as +-1 int8 with shape (bn, bkp), meets
a_bits[b] (bm, bkp) in an ordinary dot. The 32 plane dots sum to the full
contraction. The packed-K block is the whole packed K or a multiple of 128
lanes, so any K (FFN in 2560 -> 80 lanes, FFN out 6912 -> 216 lanes)
tiles the chip's (8, 128) layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.binarize import LANE_BITS, packed_len

# packed-K lanes per grid step when the packed K is 128-lane aligned and
# too long to hold whole: 512 lanes = 16384 contraction elements
_MAX_BKP = 512


def _kernel(a_ref, pw_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pw = pw_ref[...]                          # (bn, bkp) int32 bit lanes
    acc = out_ref[...]
    for b in range(LANE_BITS):
        w = (((pw >> b) & 1) * 2 - 1).astype(jnp.int8)   # (bn, bkp)
        acc += jax.lax.dot_general(
            a_ref[b], w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
    out_ref[...] = acc


def _k_block(kp: int) -> int:
    """Packed-K lanes per step: all of them, unless they are 128-aligned
    and longer than _MAX_BKP (then the largest aligned divisor <= it)."""
    if kp <= _MAX_BKP or kp % 128:
        return kp
    return max(c for c in range(128, _MAX_BKP + 1, 128) if kp % c == 0)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def int8_matmul_pallas(a: jax.Array, pw: jax.Array, *, bm: int = 256,
                       bn: int = 256, interpret: bool = False) -> jax.Array:
    """a (M, K) int8 in {-1, +1}, pw (N, ceil(K/32)) uint32 -> (M, N) int32.

    Exact int32, bit-identical to ``binary_matmul_int8`` and the XLA XNOR
    twin for any K: padding bits of pw meet zero activations.
    """
    m, k = a.shape
    n, kp = pw.shape
    assert kp == packed_len(k), (k, kp)
    bkp = _k_block(kp)
    bm = min(bm, m)
    bn = bn if n % bn == 0 else n
    mp = -(-m // bm) * bm
    a = jnp.pad(a, ((0, mp - m), (0, kp * LANE_BITS - k)))
    a_bits = a.reshape(mp, kp, LANE_BITS).transpose(2, 0, 1)   # (32, M, kp)
    pw = jax.lax.bitcast_convert_type(pw, jnp.int32)
    grid = (mp // bm, n // bn, kp // bkp)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((LANE_BITS, bm, bkp), lambda i, j, s: (0, i, s)),
            pl.BlockSpec((bn, bkp), lambda i, j, s: (j, s)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.int32),
        interpret=interpret,
        name="int8_matmul_pallas",
    )(a_bits, pw)
    return out[:m]
