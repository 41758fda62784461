"""XNOR-popcount binary matmul — the BEANNA binary mode as a Pallas TPU kernel.

The FPGA's 256x16 effective binary array maps onto the TPU VPU: operands are
bit-packed 32/lane uint32; each grid step XORs a (bm, bk) activation tile with
a (bn, bk) weight tile lane-by-lane, popcounts, and accumulates int32 partial
sums in the revisited output tile (classic Pallas K-loop accumulation, which
doubles as the BEANNA partial-sum accumulator BRAM).

VMEM budget per step (defaults bm=bn=256, bk=8):
  a tile 256*8*4 B = 8 KiB, w tile 8 KiB, out tile 256*256*4 B = 256 KiB,
  loop intermediate (bm, bn) int32 = 256 KiB  -> well under the ~16 MiB VMEM.

``binary_matmul_int8`` below is the same logical op lowered for hardware
*without* cheap popcount: sign bits become +-1 int8 and the contraction runs
as a dot_general with int32 accumulation — on TPU that is the MXU at its
int8 rate (2x bf16 peak), with weights still bit-packed in HBM and the
unpack a shift/mask on the way into the systolic array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.binarize import unpack_bits


def _kernel(pa_ref, pw_ref, out_ref, *, k_total: int, bk: int, nk: int):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def lane(l, acc):
        a = pa_ref[:, l]                      # (bm,) uint32
        w = pw_ref[:, l]                      # (bn,) uint32
        x = jnp.bitwise_xor(a[:, None], w[None, :])
        return acc + jax.lax.population_count(x).astype(jnp.int32)

    acc = jax.lax.fori_loop(0, bk, lane,
                            jnp.zeros(out_ref.shape, jnp.int32))
    out_ref[...] += acc

    @pl.when(kstep == nk - 1)
    def _finish():
        # dot = K - 2 * popcount(xor)
        out_ref[...] = jnp.int32(k_total) - 2 * out_ref[...]


@functools.partial(jax.jit, static_argnames=("k", "bm", "bn", "bk",
                                             "interpret"))
def binary_matmul_pallas(pa: jax.Array, pw: jax.Array, *, k: int,
                         bm: int = 256, bn: int = 256, bk: int = 8,
                         interpret: bool = False) -> jax.Array:
    """pa (M, Kp) uint32, pw (N, Kp) uint32 -> (M, N) int32.

    M % bm == N % bn == Kp % bk == 0 (callers pad; model dims already align).
    """
    m, kp = pa.shape
    n = pw.shape[0]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kp)
    assert m % bm == 0 and n % bn == 0 and kp % bk == 0, (m, n, kp, bm, bn, bk)
    nk = kp // bk
    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, k_total=k, bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bn, bk), lambda i, j, s: (j, s)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="binary_matmul_pallas",
    )(pa, pw)


@functools.partial(jax.jit, static_argnames=("k",))
def binary_matmul_int8(a: jax.Array, pw: jax.Array, *,
                       k: int | None = None) -> jax.Array:
    """a (M, K) int8 in {-1, +1}, pw (N, Kp) uint32 -> (M, N) int32.

    The +-1 int8 MXU twin of the XNOR-popcount kernel: weight sign bits
    lower to +-1 int8 and the contraction is a ``dot_general`` with int32
    accumulation — the TPU-friendly path where popcount hardware is absent
    (the MXU's int8 rate is 2x bf16 peak; the VPU popcount loop is
    lane-serial). Weights stay bit-packed at rest (16x smaller than bf16);
    padding lanes are sliced off after the unpack, so any K — including
    K % 32 != 0 — is exact int32, bit-identical to ``binary_matmul_pallas``
    and the XLA XNOR twin (``kernels/ref.binary_matmul_packed_ref``).
    """
    k = k if k is not None else a.shape[-1]
    w = unpack_bits(pw, k, dtype=jnp.int8)          # (N, K) in {-1, +1}
    return jax.lax.dot_general(
        a, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
