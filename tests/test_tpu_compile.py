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


# a segment of each cell's pool: (layers, slots, max_len, kv heads, head_dim)
# and query heads — stablelm-3b decode-batch (binary scan of 28 layers and
# a float edge of 2) and qwen3-8b-18L long-prompt (14 and 2)
DECODE_POOLS = {"stablelm_28": ((28, 8, 1544, 32, 80), 32),
                "stablelm_2": ((2, 8, 1544, 32, 80), 32),
                "qwen3_14": ((14, 4, 8264, 8, 128), 32),
                "qwen3_2": ((2, 4, 8264, 8, 128), 32)}


@pytest.mark.parametrize("pool", list(DECODE_POOLS), ids=list(DECODE_POOLS))
def test_decode_attention_pallas_compiles(one_chip, pool):
    """The in-place decode kernel reads the pool as the chip stores it:
    no copy of the pool (temp bytes stay far below one layer's K/V)."""
    from repro.kernels.decode_attention import decode_attention_pallas
    shape, hq = DECODE_POOLS[pool]
    n, b, t, hkv, d = shape
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((b, hq, d), jnp.bfloat16), ((b, hkv, d), jnp.bfloat16),
        ((b, hkv, d), jnp.bfloat16), (shape, jnp.bfloat16),
        (shape, jnp.bfloat16), ((), jnp.int32), ((b,), jnp.int32))]
    compiled = jax.jit(
        lambda q, kn, vn, k, v, layer, lens: decode_attention_pallas(
            q, kn, vn, k, v, layer, lens, interpret=False)).lower(
                *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**24


@pytest.mark.parametrize("arch,batch,max_len", [
    ("stablelm-3b", 8, 1544), ("qwen3-8b", 4, 8264)])
def test_decode_step_keeps_one_pool(one_chip, on_tpu, arch, batch, max_len):
    """The whole decode step at a cell's pool size reads the pool in place
    and writes its rows into the donated buffer: its temporaries stay
    under 1 GiB, where carrying the pool through the layer scan as xs/ys
    held a second pool (4.07 GiB on stablelm-3b, 2.65 GiB on
    qwen3-8b-18L)."""
    from repro.configs import get_config
    from repro.models import get_model
    cfg = get_config(arch)
    if arch == "qwen3-8b":
        cfg = cfg.replace(n_layers=18)
    api = get_model(cfg)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    caches = shapes(jax.eval_shape(lambda: api.init_cache(batch, max_len)))
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.decode, donate_argnums=1).lower(
        params, caches, tok).compile()
    assert "decode_attn_pallas" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
