"""Chip-compiler rehearsal: every kernel that ``auto`` selects on a TPU
compiles for a described v5e chip at stablelm-3b's published widths
(d_model 2560, d_ff 6912, 32 heads of 80).

Nothing runs: the TPU compiler installed with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse
(misaligned blocks, unsupported casts, VMEM overruns) — the faults that
interpret-mode tests cannot see. Code that asks ``jax.default_backend()``
is steered to its TPU choice inside each test.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.binarize import packed_len
from repro.kernels import kv_quant as kvq
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas

D_MODEL, D_FF, HEADS, HEAD_DIM = 2560, 6912, 32, 80


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off (a TPU entry written here could not be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def on_tpu(monkeypatch):
    """Make ``auto`` resolve (and kernels drop interpret mode) as they
    would on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["ffn_in", "ffn_out"])
def test_int8_matmul_pallas_compiles(one_chip, m, k, n):
    text = _compile(lambda a, pw: int8_matmul_pallas(a, pw), one_chip,
                    ((m, k), jnp.int8), ((n, packed_len(k)), jnp.uint32))
    assert "tpu_custom_call" in text


def test_binary_ffn_auto_is_pallas_int8(one_chip, on_tpu):
    """The served FFN call site (latent weights, binary_mode int8)."""
    assert ops.resolve_impl("int8") == "pallas_int8"
    text = _compile(
        lambda x, w: ops.binary_dense(x, w, mode="int8"), one_chip,
        ((8, D_MODEL), jnp.bfloat16), ((D_MODEL, D_FF), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_attention_pallas_compiles_batched(one_chip, on_tpu):
    q = ((4, 128, HEADS, HEAD_DIM), jnp.bfloat16)
    text = _compile(
        lambda q, k, v, kv_len: flash_attention_pallas(
            q, k, v, causal=True, kv_len=kv_len),
        one_chip, q, q, q, ((4,), jnp.int32))
    assert "tpu_custom_call" in text


def test_spec_draft_auto_compiles(one_chip, on_tpu):
    """The binary self-draft's packed xnor matmul, as ``auto`` picks it."""
    assert ops.resolve_impl("xnor") == "xla_xnor"
    _compile(lambda x, pw: ops.binary_dense_packed(x, pw, D_MODEL,
                                                   mode="xnor"),
             one_chip, ((8, D_MODEL), jnp.float32),
             ((D_FF, packed_len(D_MODEL)), jnp.uint32))


@pytest.mark.parametrize("codec", ["int8", "binary"])
def test_kv_codec_auto_compiles(one_chip, on_tpu, codec):
    """Quantize on insert and dequantize on read, for the pool of a
    B=8 x 512-token prefill at head_dim 80."""
    x = ((8, 512, HEADS, HEAD_DIM), jnp.bfloat16)
    if codec == "int8":
        assert kvq.resolve_kv_quant_impl() == "pallas"
        text = _compile(lambda x: kvq.kv_dequant_int8(*kvq.kv_quant_int8(x)),
                        one_chip, x)
        assert "tpu_custom_call" in text
    else:
        assert kvq.resolve_kv_quant_impl(codec="binary") == "xla"
        _compile(lambda x: kvq.kv_dequant_binary(*kvq.kv_quant_binary(x),
                                                 HEAD_DIM), one_chip, x)
