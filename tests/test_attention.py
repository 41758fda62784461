import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import attention as A
from repro.nn.layers import apply_rope


def _qkv(b=2, s=64, hq=4, hkv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    return q, k, v


def _reference_attention(q, k, v, causal=True):
    """repeat-KV reference."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    k = np.repeat(np.asarray(k), rep, axis=2)
    v = np.repeat(np.asarray(v), rep, axis=2)
    q = np.asarray(q)
    scores = np.einsum("bshd,bthd->bhst", q, k) / math.sqrt(d)
    if causal:
        mask = np.tril(np.ones((s, s), bool))
        scores = np.where(mask[None, None], scores, -1e9)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", w, v)


def test_gqa_matches_repeat_kv_reference():
    q, k, v = _qkv()
    got = A.dot_attention(q, k, v, causal=True)
    ref = _reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                               rtol=2e-3, atol=2e-3)


def test_chunked_equals_unchunked():
    q, k, v = _qkv(s=128)
    full = A.dot_attention(q, k, v, causal=True)
    chunked = A.chunked_causal_attention(q, k, v, chunk=32)
    np.testing.assert_allclose(np.asarray(full, np.float32),
                               np.asarray(chunked, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_chunked_with_mla_style_dv_neq_dq():
    """MLA: value head dim differs from query head dim (sweep regression)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 24))
    k = jax.random.normal(ks[1], (2, 64, 4, 24))
    v = jax.random.normal(ks[2], (2, 64, 4, 16))     # dv = 16 != 24
    full = A.dot_attention(q, k, v, causal=True)
    chunked = A.chunked_causal_attention(q, k, v, chunk=16)
    assert chunked.shape == (2, 64, 4, 16)
    np.testing.assert_allclose(np.asarray(full, np.float32),
                               np.asarray(chunked, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_decode_matches_last_position():
    q, k, v = _qkv(s=33)
    full = A.dot_attention(q, k, v, causal=True)
    cache = A.init_kv_cache(2, 64, 2, 16, jnp.float32)
    # fill cache with first 32 k/v
    cache["k"] = cache["k"].at[:, :32].set(k[:, :32])
    cache["v"] = cache["v"].at[:, :32].set(v[:, :32])
    cache["len"] = jnp.full((2,), 32, jnp.int32)
    cache = A.cache_update_decode(cache, k[:, 32:33], v[:, 32:33])
    got = A.dot_attention(q[:, 32:33], cache["k"], cache["v"], causal=False,
                          kv_len=cache["len"])
    np.testing.assert_allclose(np.asarray(got[:, 0], np.float32),
                               np.asarray(full[:, 32], np.float32),
                               rtol=2e-3, atol=2e-3)


def test_rope_preserves_norm_and_relativity():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    pos = jnp.arange(8)
    y = apply_rope(x, pos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-4)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))
    def dot_at(i, j):
        qi = apply_rope(q, jnp.array([i]))
        kj = apply_rope(k, jnp.array([j]))
        return float(jnp.sum(qi * kj))
    np.testing.assert_allclose(dot_at(3, 1), dot_at(7, 5), rtol=1e-4)


# ---------------------------------------------------------------------------
# blockwise backends: pallas_flash (interpret) and xla_blockwise parity
# against the score-materializing dot_attention reference
# ---------------------------------------------------------------------------

# per-dtype tolerances: f32 differs only by the online-softmax reassociation;
# bf16 additionally rounds the p@v accumulation differently (ref accumulates
# in bf16, flash in f32)
TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}
BLOCKWISE = ["pallas_flash", "xla_blockwise"]


def _run_impl(impl, q, k, v, *, causal, kv_len=None, small_blocks=True):
    """Invoke a blockwise backend with blocks small enough that the grid
    actually iterates (both q and kv axes see multiple blocks)."""
    if impl == "pallas_flash":
        from repro.kernels.flash_attention import flash_attention_pallas
        kw = dict(bq=16, bk=16) if small_blocks else {}
        return flash_attention_pallas(q, k, v, causal=causal, kv_len=kv_len,
                                      interpret=True, **kw)
    from repro.kernels.flash_attention import blockwise_attention_xla
    kw = dict(q_block=16, kv_block=16) if small_blocks else {}
    return blockwise_attention_xla(q, k, v, causal=causal, kv_len=kv_len,
                                   **kw)


@pytest.mark.parametrize("impl", BLOCKWISE)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blockwise_parity(impl, causal, g, dtype):
    hkv = 2
    q, k, v = _qkv(s=96, hq=hkv * g, hkv=hkv, seed=7)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    ref = A.dot_attention(q, k, v, causal=causal)
    got = _run_impl(impl, q, k, v, causal=causal)
    assert got.dtype == v.dtype
    tol = TOLS[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", BLOCKWISE)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blockwise_kv_len_masked_padded_batch(impl, dtype):
    """Right-padded batch: rows past kv_len must not contribute."""
    q, k, v = _qkv(s=64, seed=11)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    kv_len = jnp.array([37, 64], jnp.int32)
    ref = A.dot_attention(q, k, v, causal=True, kv_len=kv_len)
    got = _run_impl(impl, q, k, v, causal=True, kv_len=kv_len)
    tol = TOLS[dtype]
    # compare only valid query rows (pad rows are discarded downstream)
    for b in range(2):
        n = int(kv_len[b])
        np.testing.assert_allclose(np.asarray(got[b, :n], np.float32),
                                   np.asarray(ref[b, :n], np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", BLOCKWISE)
def test_blockwise_decode_over_slot_cache(impl):
    """Single-query decode against a partially-filled cache pool."""
    b, t, hq, hkv, d = 3, 40, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    kc = jax.random.normal(ks[1], (b, t, hkv, d))
    vc = jax.random.normal(ks[2], (b, t, hkv, d))
    kv_len = jnp.array([5, 17, 40], jnp.int32)
    ref = A.decode_attention(q, kc, vc, kv_len=kv_len, impl="xla_ref")
    got = _run_impl(impl, q, kc, vc, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", BLOCKWISE)
def test_blockwise_ragged_and_rect(impl):
    """Non-block-multiple S and S != T (cross-attention shapes)."""
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (2, 50, 4, 16))
    k = jax.random.normal(ks[1], (2, 70, 2, 16))
    v = jax.random.normal(ks[2], (2, 70, 2, 16))
    ref = A.dot_attention(q, k, v, causal=False)
    got = _run_impl(impl, q, k, v, causal=False)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_blockwise_mla_style_dv_neq_dq():
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    q = jax.random.normal(ks[0], (2, 48, 4, 24))
    k = jax.random.normal(ks[1], (2, 48, 4, 24))
    v = jax.random.normal(ks[2], (2, 48, 4, 16))
    ref = A.dot_attention(q, k, v, causal=True)
    for impl in BLOCKWISE:
        got = _run_impl(impl, q, k, v, causal=True)
        assert got.shape == (2, 48, 4, 16)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

def test_resolve_attn_impl():
    # explicit impls pass through untouched
    for impl in ("xla_ref", "xla_blockwise", "pallas_flash"):
        assert A.resolve_attn_impl(impl, family="prefill") == impl
    # auto picks per backend, for decode (the in-place pool kernel) as
    # for prefill
    expected = ("xla_ref" if jax.default_backend() == "cpu"
                else "pallas_flash")
    assert A.resolve_attn_impl("auto", family="decode") == expected
    assert A.resolve_attn_impl("auto", family="prefill") == expected
    with pytest.raises(ValueError):
        A.resolve_attn_impl("triton_flash")


@pytest.mark.parametrize("impl", ["xla_ref", "xla_blockwise",
                                  "pallas_flash"])
def test_entrypoints_agree_across_impls(impl):
    q, k, v = _qkv(s=64, seed=23)
    ref = A.dot_attention(q, k, v, causal=True)
    got = A.prefill_attention(q, k, v, chunk=32, impl=impl)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5)
    kv_len = jnp.full((2,), 64, jnp.int32)
    refd = A.dot_attention(q[:, -1:], k, v, causal=False, kv_len=kv_len)
    gotd = A.decode_attention(q[:, -1:], k, v, kv_len=kv_len, impl=impl)
    np.testing.assert_allclose(np.asarray(gotd, np.float32),
                               np.asarray(refd, np.float32),
                               rtol=2e-5, atol=2e-5)
    refx = A.dot_attention(q, k, v, causal=False)
    gotx = A.cross_attention(q, k, v, impl=impl)
    np.testing.assert_allclose(np.asarray(gotx, np.float32),
                               np.asarray(refx, np.float32),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla_ref", "xla_blockwise",
                                  "pallas_flash"])
def test_scalar_kv_len_all_impls(impl):
    """A python-int kv_len must broadcast over the batch in every backend."""
    q, k, v = _qkv(s=32, seed=31)
    ref = A.dot_attention(q, k, v, causal=False,
                          kv_len=jnp.full((2,), 20, jnp.int32))
    got = A.decode_attention(q, k, v, kv_len=20, impl=impl)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_flash_grad_matches_reference():
    """Training goes through prefill_attention: the Pallas kernel's
    custom_vjp (recompute via the XLA blockwise twin) must match grads of
    the score-materializing reference."""
    from repro.kernels.flash_attention import flash_attention_pallas
    q, k, v = _qkv(s=32, seed=37)

    def loss_flash(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True, bq=16,
                                      bk=16, interpret=True).sum()

    def loss_ref(q, k, v):
        return A.dot_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_chunked_ragged_prompt_no_crash():
    """s % chunk != 0 pads the final query block instead of asserting."""
    q, k, v = _qkv(s=100, seed=29)
    full = A.dot_attention(q, k, v, causal=True)
    chunked = A.chunked_causal_attention(q, k, v, chunk=32)
    assert chunked.shape == full.shape
    np.testing.assert_allclose(np.asarray(chunked, np.float32),
                               np.asarray(full, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_resolve_cache_update_auto():
    from repro.distributed import sharding

    class _FakeMesh:
        size = 8

    prev_mesh, prev_rules = sharding._ACTIVE_MESH, sharding._ACTIVE_RULES
    try:
        sharding.set_logical_rules(None, None)
        assert A.resolve_cache_update("auto") == "dus"
        sharding.set_logical_rules(_FakeMesh(), sharding.MeshRules())
        assert A.resolve_cache_update("auto") == "mask"
        # explicit settings always win
        assert A.resolve_cache_update("dus") == "dus"
        assert A.resolve_cache_update("mask") == "mask"
    finally:
        sharding._ACTIVE_MESH, sharding._ACTIVE_RULES = prev_mesh, prev_rules


def test_cache_update_methods_agree():
    cache = A.init_kv_cache(2, 8, 2, 4, jnp.float32)
    cache["len"] = jnp.array([0, 3], jnp.int32)
    kn = jnp.ones((2, 1, 2, 4))
    vn = jnp.full((2, 1, 2, 4), 2.0)
    dus = A.cache_update_decode(dict(cache), kn, vn, method="dus")
    msk = A.cache_update_decode(dict(cache), kn, vn, method="mask")
    for key in ("k", "v", "len"):
        np.testing.assert_array_equal(np.asarray(dus[key]),
                                      np.asarray(msk[key]))


def test_mla_absorbed_decode_consistency():
    """Absorbed-matrix decode == explicit expand-then-attend."""
    b, t, h, dn, dr, c = 2, 16, 3, 8, 4, 12
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    q_nope = jax.random.normal(ks[0], (b, 1, h, dn))
    q_rope = jax.random.normal(ks[1], (b, 1, h, dr))
    c_cache = jax.random.normal(ks[2], (b, t, c))
    kr_cache = jax.random.normal(ks[3], (b, t, dr))
    w_uk = jax.random.normal(ks[4], (c, h, dn)) * 0.3
    kv_len = jnp.full((b,), t, jnp.int32)
    sm = 1.0 / math.sqrt(dn + dr)

    q_abs = jnp.einsum("bshd,chd->bshc", q_nope, w_uk)
    ctx = A.mla_absorbed_decode(q_abs, q_rope, c_cache, kr_cache, kv_len,
                                sm_scale=sm)

    # reference: expand keys, standard attention over concat dims
    k_nope = jnp.einsum("btc,chd->bthd", c_cache, w_uk)
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + jnp.einsum("bshr,btr->bhst", q_rope, kr_cache)) * sm
    w = jax.nn.softmax(scores, -1)
    ctx_ref = jnp.einsum("bhst,btc->bshc", w, c_cache)
    np.testing.assert_allclose(np.asarray(ctx, np.float32),
                               np.asarray(ctx_ref, np.float32),
                               rtol=2e-3, atol=2e-3)
