"""Decode attention over the stacked KV pool, read in place.

The kernel (``kernels/decode_attention.py``, interpret mode here) against
the reference attend over one layer of the pool with the new row
inserted, and the decode scan that reads the pool in place
(``lm_common.segments_decode``) against the per-layer scan it replaced:
bit-identical logits and pool contents on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels.decode_attention import block_rows, decode_attention_pallas
from repro.models import get_model
from repro.models import lm_common as lc
from repro.nn import attention as A

L, B, T, BLOCK = 3, 4, 72, 16       # 5 blocks of 16 rows, the last partial

# (kv heads, groups, head_dim): stablelm's MHA at 80, qwen3's GQA at 128
SHAPES = {"mha_d80": (2, 1, 80), "gqa_d128": (2, 4, 128)}
# pool rows per slot besides the new one: 1, a block boundary, the whole
# pool, and mid block
LENS = {"one": [1, 1, 1, 1], "block_edge": [BLOCK, 2 * BLOCK, 1, 3],
        "max_len": [T, T - 1, 5, T], "mixed": [7, 33, T, 17]}


def _pool(hkv, g, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    kp = jax.random.normal(ks[0], (L, B, T, hkv, d)).astype(dtype)
    vp = jax.random.normal(ks[1], (L, B, T, hkv, d)).astype(dtype)
    q = jax.random.normal(ks[2], (B, hkv * g, d)).astype(dtype)
    kn = jax.random.normal(ks[3], (B, hkv, d)).astype(dtype)
    vn = jax.random.normal(ks[4], (B, hkv, d)).astype(dtype)
    return q, kn, vn, kp, vp


def _ref(q, kn, vn, kp, vp, layer, lens, dtype=jnp.float32):
    """The layer, one row longer, with the new row at each slot's len."""
    def ins(pool, new):
        lay = jnp.pad(pool[layer], ((0, 0), (0, 1), (0, 0), (0, 0)))
        return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
            c, n[None], i, axis=0))(lay, new, lens)
    c = lambda a: a.astype(dtype)
    return A.dot_attention(c(q)[:, None], c(ins(kp, kn)), c(ins(vp, vn)),
                           causal=False, kv_len=lens + 1)[:, 0]


@pytest.mark.parametrize("lens", list(LENS), ids=list(LENS))
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_matches_reference(shape, lens, layer):
    """f32 pool: the online softmax over blocks equals the one-shot
    softmax to f32 accumulation order."""
    q, kn, vn, kp, vp = _pool(*SHAPES[shape], jnp.float32)
    lens = jnp.asarray(LENS[lens], jnp.int32)
    got = decode_attention_pallas(q, kn, vn, kp, vp, layer, lens,
                                  block=BLOCK)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref(q, kn, vn, kp, vp, layer, lens)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_kernel_bf16_matches_xla_ref(shape):
    """bf16 pool at the default block: within bf16 rounding of xla_ref."""
    q, kn, vn, kp, vp = _pool(*SHAPES[shape], jnp.bfloat16, seed=1)
    lens = jnp.asarray(LENS["mixed"], jnp.int32)
    got = decode_attention_pallas(q, kn, vn, kp, vp, 1, lens)
    want = _ref(q, kn, vn, kp, vp, 1, lens, dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_kernel_len_zero_slot_reads_nothing():
    """A slot with len 0 reads no pool row: its output is its new row's
    V exactly, and the other slots are unchanged."""
    q, kn, vn, kp, vp = _pool(*SHAPES["gqa_d128"], jnp.float32, seed=2)
    lens = jnp.asarray([0, 9, 0, T], jnp.int32)
    got = np.asarray(decode_attention_pallas(q, kn, vn, kp, vp, 1, lens,
                                             block=BLOCK))
    # query head h reads kv head h // G
    np.testing.assert_array_equal(
        got[[0, 2]], np.repeat(np.asarray(vn)[[0, 2]], 4, axis=1))
    want = np.asarray(_ref(q, kn, vn, kp, vp, 1, lens))
    np.testing.assert_allclose(got[[1, 3]], want[[1, 3]],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fill", [np.nan, 3e4], ids=["nan", "large"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_kernel_rows_past_len_change_nothing(shape, fill):
    """Rows at or past each slot's length, in its last block and beyond,
    never reach the output: overwriting them leaves it bit-identical."""
    q, kn, vn, kp, vp = _pool(*SHAPES[shape], jnp.float32, seed=3)
    lens = jnp.asarray(LENS["mixed"], jnp.int32)
    clean = decode_attention_pallas(q, kn, vn, kp, vp, 2, lens, block=BLOCK)
    past = (jnp.arange(T)[None, :] >= lens[:, None])[None, :, :, None, None]
    dirty = decode_attention_pallas(q, kn, vn, jnp.where(past, fill, kp),
                                    jnp.where(past, fill, vp), 2, lens,
                                    block=BLOCK)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_block_rows():
    """A multiple of 128 within the VMEM budget, or the whole pool."""
    assert block_rows(1544, 32, 80) == 256      # stablelm-3b
    assert block_rows(8264, 8, 128) == 512      # qwen3-8b
    assert block_rows(72, 2, 80) == 72


# ---------------------------------------------------------------------------
# the decode scan: pool read in place vs the per-layer scan it replaced
# ---------------------------------------------------------------------------

PARITY = {
    # MHA at head_dim 80; 6 layers = float edge, a 4-layer binary scan,
    # float edge (segments of 1, 4, 1)
    "stablelm_like": ("stablelm-3b", dict(n_layers=6, n_heads=2,
                                          n_kv_heads=2, head_dim=80)),
    # GQA 4/2 with qk-norm
    "qwen3_like": ("qwen3-8b", dict(n_layers=6, head_dim=32)),
}


def _decode_run(api, params, caches, tok, steps):
    dec = jax.jit(lambda p, c, t: api.decode(p, c, t))
    logits = []
    for _ in range(steps):
        lg, caches = dec(params, caches, tok)
        logits.append(np.asarray(lg))
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
    return np.stack(logits), jax.tree.map(np.asarray, caches)


@pytest.mark.parametrize("case", list(PARITY), ids=list(PARITY))
def test_in_place_scan_bit_identical(case, monkeypatch):
    arch, kw = PARITY[case]
    cfg = smoke_config(arch).replace(**kw)
    api = get_model(cfg)
    assert [c for _, _, c in lc.build_segments(cfg)] == [1, 4, 1]
    params = api.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 7), 0, cfg.vocab)
    _, caches = api.prefill(params, {"tokens": toks}, max_len=32)
    caches = lc.set_cache_lengths(caches, jnp.asarray([7, 3, 5]))
    new = _decode_run(api, params, caches, toks[:, -1:], 16)
    monkeypatch.setattr(lc, "_decodes_in_place", lambda *a: False)
    old = _decode_run(api, params, caches, toks[:, -1:], 16)
    np.testing.assert_array_equal(new[0], old[0])
    for a, b in zip(jax.tree.leaves(new[1]), jax.tree.leaves(old[1])):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kv_cache,update,paged,mla,want", [
    ("auto", "auto", False, False, True),
    ("bf16", "dus", False, False, True),
    ("int8", "auto", False, False, False),
    ("binary", "auto", False, False, False),
    ("auto", "mask", False, False, False),
    ("auto", "auto", True, False, False),
    ("auto", "auto", False, True, False),
])
def test_in_place_selection(kv_cache, update, paged, mla, want):
    """Only the contiguous bf16 GQA pool updated by dus is read in place;
    paged pools, quantized codecs, MLA and the mask update keep the
    per-layer scan."""
    cfg = smoke_config("qwen3-8b").replace(kv_cache=kv_cache,
                                           cache_update=update)
    sig = lc.BlockSig("mla" if mla else "gqa", "float")
    cache = {"k": None, "len": None, **({"table": None} if paged else {})}
    assert lc._decodes_in_place(cfg, sig, cache) is want
