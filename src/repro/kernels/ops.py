"""jit'd dispatch layer over the binary kernels + the trainable binary dense.

Three lowerings of the same logical op  y = sign(x) @ sign(w)  (BEANNA's PE
mode mux, re-imagined as a per-layer lowering choice):

  impl "xla_xnor"   bit-packed XOR + popcount via native XLA ops (shardable
                    by GSPMD -> used by the multi-pod dry-run; also the CPU
                    execution path)
  impl "xla_int8"   +-1 int8 dot_general (MXU int8 path through XLA)
  impl "pallas_*"   the Pallas kernels (TPU target; interpret=True on CPU)
  impl "bf16"       plain bf16 matmul of the sign matrices (float fallback,
                    bit-identical values, used for ablation)

Training uses a custom_vjp so the fast integer forward coexists with the
straight-through-estimator backward of Courbariaux et al. (paper eq. 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.binarize import pack_bits, pack_signs_int8
from repro.kernels import ref as kref
from repro.kernels.binary_matmul import binary_matmul_int8, binary_matmul_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas

# packed-weight lowering choices for the binary self-draft (threaded from
# ModelConfig.spec_draft_impl down to nn/layers.dense_apply): "auto" keeps
# the resolve_impl default (the XLA XNOR twin on every backend); "int8_mxu"
# is the +-1 int8 dot_general MXU twin; "pallas_xnor" runs only in
# interpret mode (Mosaic refuses its lane-serial popcount loop).
SPEC_DRAFT_IMPLS = ("auto", "xla_xnor", "int8_mxu", "pallas_xnor")


def resolve_impl(mode: str, impl: str = "auto") -> str:
    """mode in {xnor, int8, bf16} -> concrete impl for this backend.

    xnor resolves to the XLA twin everywhere: the Pallas popcount kernel
    indexes packed lanes one at a time in (bm, 8)-lane blocks, which the
    TPU compiler refuses, so it stays an interpret-mode reference. int8
    takes the Pallas kernel on one accelerator; on CPU, and under a
    multi-device mesh (GSPMD cannot partition a Mosaic kernel), the XLA
    twin."""
    if impl != "auto":
        return impl
    if mode == "bf16":
        return "bf16"
    if mode == "xnor":
        return "xla_xnor"
    if mode == "int8":
        from repro.distributed.sharding import sharded_mesh_active
        if jax.default_backend() == "cpu" or sharded_mesh_active():
            return "xla_int8"
        return "pallas_int8"
    raise ValueError(f"unknown binary mode {mode!r}")


# latent weight (K, N) -> the packed operand each lowering multiplies by
_WEIGHT_PACK = {
    "bf16": lambda w: jnp.where(w >= 0, 1.0, -1.0).astype(jnp.bfloat16),
    "xla_xnor": lambda w: pack_bits(w.T),
    "pallas_xnor": lambda w: pack_bits(w.T),
    "xla_int8": lambda w: pack_signs_int8(w.T),
    "pallas_int8": lambda w: pack_bits(w.T),
}


def _binary_matmul_fwd(x2d: jax.Array, w: jax.Array, impl: str) -> jax.Array:
    """x2d (M, K), w (K, N) latent -> (M, N) in x2d's dtype (integer-valued;
    bf16 IO keeps the TP all-reduce wire format narrow — see EXPERIMENTS.md
    section Perf, qwen3 H5; |dot| <= K so bf16 rounds above 256 by <0.4%)."""
    k = x2d.shape[-1]
    out_dtype = x2d.dtype
    if impl not in _WEIGHT_PACK:
        raise ValueError(impl)
    # the weight-side sign-and-pack, redone every call from the latent
    # weights; its own scope lets a device trace measure it
    with jax.named_scope("ffn_pack"):
        wq = _WEIGHT_PACK[impl](w)
    if impl == "bf16":
        y = kref.bf16_matmul_ref(
            jnp.where(x2d >= 0, 1.0, -1.0).astype(jnp.bfloat16), wq)
    elif impl == "xla_xnor":
        y = kref.binary_matmul_packed_ref(pack_bits(x2d), wq, k)
    elif impl == "pallas_xnor":
        interp = jax.default_backend() == "cpu"
        y = binary_matmul_pallas(pack_bits(x2d), wq, k=k, interpret=interp)
    elif impl == "xla_int8":
        y = kref.int8_matmul_ref(pack_signs_int8(x2d), wq)
    else:                                   # pallas_int8
        interp = jax.default_backend() == "cpu"
        y = int8_matmul_pallas(pack_signs_int8(x2d), wq, interpret=interp)
    return y.astype(out_dtype)


@functools.lru_cache(maxsize=None)
def _make_binary_dense(impl: str):
    @jax.custom_vjp
    def bd(x, w):
        return _binary_matmul_fwd(x, w, impl)

    def fwd(x, w):
        return bd(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        gf = g.astype(jnp.float32)
        xf = x.astype(jnp.float32)
        sw = jnp.where(w >= 0, 1.0, -1.0)
        sx = jnp.where(xf >= 0, 1.0, -1.0)
        # STE: grads pass where |.| <= 1 (paper eq. 2 + hardtanh window);
        # activation grads return in x's dtype (bf16 wire format for TP)
        gx = (gf @ sw.T) * (jnp.abs(xf) <= 1.0)
        gw = (sx.T @ gf) * (jnp.abs(w) <= 1.0)
        return gx.astype(x.dtype), gw.astype(w.dtype)

    bd.defvjp(fwd, bwd)
    return bd


def binary_dense(x: jax.Array, w_latent: jax.Array, *, mode: str = "xnor",
                 impl: str = "auto") -> jax.Array:
    """Trainable binary dense: y = sign(x) @ sign(w), STE backward.

    x (..., K) -> (..., N), keeping x's dtype end to end (exact in f32;
    bf16 rounds |values| > 256 by < 0.4% — the deployment-accurate choice
    because the TP all-reduce then moves bf16, not f32/s32).
    """
    impl = resolve_impl(mode, impl)
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    y = _make_binary_dense(impl)(x2d, w_latent)
    return y.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# batched (grouped) binary dense — MoE experts: (G, M, K) x (G, K, N)
# ---------------------------------------------------------------------------

def _binary_matmul_batched_fwd(x3, w3, impl):
    if impl in ("bf16",):
        sx = jnp.where(x3 >= 0, 1.0, -1.0).astype(jnp.bfloat16)
        with jax.named_scope("ffn_pack"):
            sw = jnp.where(w3 >= 0, 1.0, -1.0).astype(jnp.bfloat16)
        return jax.lax.dot_general(
            sx, sw, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    if impl in ("xla_int8", "pallas_int8"):
        # grouped int8 dot (pallas path would vmap the kernel; the XLA
        # batched dot is what GSPMD shards over the expert axis)
        sx = pack_signs_int8(x3)
        with jax.named_scope("ffn_pack"):
            sw = pack_signs_int8(w3)
        return jax.lax.dot_general(
            sx, sw, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    if impl in ("xla_xnor", "pallas_xnor"):
        k = x3.shape[-1]
        pa = pack_bits(x3)                       # (G, M, Kp)
        with jax.named_scope("ffn_pack"):
            pw = pack_bits(jnp.swapaxes(w3, 1, 2))   # (G, N, Kp)
        x = jnp.bitwise_xor(pa[:, :, None, :], pw[:, None, :, :])
        pc = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
        return (jnp.int32(k) - 2 * pc).astype(jnp.float32)
    raise ValueError(impl)


@functools.lru_cache(maxsize=None)
def _make_binary_dense_batched(impl: str):
    @jax.custom_vjp
    def bd(x, w):
        return _binary_matmul_batched_fwd(x, w, impl)

    def fwd(x, w):
        return bd(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        g = g.astype(jnp.float32)
        sw = jnp.where(w >= 0, 1.0, -1.0)
        sx = jnp.where(x >= 0, 1.0, -1.0)
        gx = jax.lax.dot_general(g, sw, (((2,), (2,)), ((0,), (0,))))
        gx = gx * (jnp.abs(x) <= 1.0)
        gw = jax.lax.dot_general(sx, g, (((1,), (1,)), ((0,), (0,))))
        gw = gw * (jnp.abs(w) <= 1.0)
        return gx.astype(x.dtype), gw.astype(w.dtype)

    bd.defvjp(fwd, bwd)
    return bd


def binary_dense_batched(x3: jax.Array, w3: jax.Array, *, mode: str = "int8",
                         impl: str = "auto") -> jax.Array:
    """Grouped trainable binary dense: (G, M, K) x (G, K, N) -> (G, M, N)."""
    impl = resolve_impl(mode, impl)
    return _make_binary_dense_batched(impl)(
        x3.astype(jnp.float32), w3.astype(jnp.float32))


def binary_dense_batched_deployed(x3: jax.Array, wq: jax.Array, *,
                                  mode: str = "int8") -> jax.Array:
    """Deployed grouped binary dense (no latents, forward only).

    int8: wq (G, K, N) int8;  xnor: wq (G, N, K/32) uint32."""
    if mode == "xnor":
        k = x3.shape[-1]
        pa = pack_bits(x3)                           # (G, M, Kp)
        x = jnp.bitwise_xor(pa[:, :, None, :], wq[:, None, :, :])
        pc = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
        return (jnp.int32(k) - 2 * pc).astype(jnp.float32)
    sx = pack_signs_int8(x3)
    return jax.lax.dot_general(
        sx, wq, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32).astype(jnp.float32)


# ---------------------------------------------------------------------------
# deployment (pre-packed weights, no latent floats)
# ---------------------------------------------------------------------------

def binary_dense_packed(x: jax.Array, w_packed: jax.Array, k: int, *,
                        mode: str = "xnor", impl: str = "auto") -> jax.Array:
    """Inference path: w_packed (N, Kp) uint32 as produced at deploy time."""
    impl = resolve_impl(mode, impl)
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    if impl in ("xla_xnor", "bf16"):
        y = kref.binary_matmul_packed_ref(pack_bits(x2d), w_packed, k)
    elif impl == "pallas_xnor":
        y = binary_matmul_pallas(pack_bits(x2d), w_packed, k=k,
                                 interpret=jax.default_backend() == "cpu")
    elif impl in ("xla_int8", "int8_mxu"):
        # +-1 int8 MXU twin: activations sign-pack to int8 directly, the
        # bit-packed weight unpacks on the way into the dot_general
        y = binary_matmul_int8(pack_signs_int8(x2d), w_packed, k=k)
    elif impl == "pallas_int8":
        y = int8_matmul_pallas(pack_signs_int8(x2d), w_packed,
                               interpret=jax.default_backend() == "cpu")
    else:
        raise ValueError(impl)
    return y.astype(jnp.float32).reshape(*lead, -1)
