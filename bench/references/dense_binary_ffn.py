"""Plain reference of the served dense decoder with BEANNA's binary FFNs.

Used by the configurations ``stablelm-3b`` and ``qwen3-8b-18L``. It is
written from the model's description, in straightforward ``jax.numpy``,
and imports nothing of the program:

- pre-norm blocks, RMSNorm (eps 1e-6, f32 statistics) before attention
  and before the FFN, then a final RMSNorm and an untied head;
- grouped-query attention: query head h reads kv head h // (Hq / Hkv);
  optional RMSNorm on q and k per head (qk-norm); rotary embedding over
  the whole head, pairing element i with element i + head_dim / 2;
  causal softmax in f32 with scale 1 / sqrt(head_dim);
- blocks ``edge <= i < n_layers - edge`` have the binary FFN: the input
  is binarised by sign (0 counts as +1), multiplied by sign(W_in), the
  integer sums scaled per output, binarised again, multiplied by
  sign(W_out) and scaled per output; the other blocks have a SwiGLU FFN.

Precision follows what the configuration states, ``bfloat16``: weights
and activations are bf16 between operations, matrix products accumulate
in f32 and round their result to bf16, norms, rotary angles and softmax
are f32. The binary products are exact integers. The final logits are
kept in f32 (the f32 sums that a bf16 head would round). ``lowp`` names
a dtype that every matrix operand is rounded through first: the control,
``float8_e4m3fn``, is the precision below bf16.

The whole sequence is run at once, teacher-forced, one layer at a time,
attention in blocks of query rows, so that a long prompt fits beside the
weights.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32

# Weight scales (see PERF.md, "How correct is decided"). The embedding rows
# are +-1, and each attention and FFN branch adds to the residual at about
# BRANCH std per element, so after every layer the residual keeps the sign
# of its embedding entry by a wide margin. A binary FFN binarises that
# residual; with the usual init an entry near 0 flips on the last bit of
# rounding and the flip spreads through every later layer (a random-weight
# binary model is chaotic at depth), which no comparison between two
# correct implementations survives. With this plan a rounding difference
# moves no sign, and every layer still adds context to the logits.
EMBED_SIGN = 1.0
BRANCH = 0.02
ROW_BLOCK = 256          # query rows per attention block and head block


def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def segments(m: dict) -> list[tuple[bool, int, int]]:
    """[(binary, first layer, count)] over runs of equal blocks."""
    e, n = m["edge_blocks_float"], m["n_layers"]
    segs: list = []
    for i in range(n):
        b = bool(m["binary_ffn"]) and e <= i < n - e
        if segs and segs[-1][0] == b:
            segs[-1] = (b, segs[-1][1], segs[-1][2] + 1)
        else:
            segs.append((b, i, 1))
    return segs


def weight_plan(m: dict) -> list:
    """(path, shape, dtype, rule) of every leaf, in the served layout."""
    d, hq, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    f, vp = m["d_ff"], padded_vocab(m["vocab"])
    bf, f32 = "bfloat16", "float32"
    plan = [("embed/table", (vp, d), bf, ("sign", EMBED_SIGN)),
            ("head/w", (d, vp), bf, ("normal", 1 / math.sqrt(d))),
            ("ln_f/scale", (d,), f32, ("const", 1.0))]
    for si, (binary, _, c) in enumerate(segments(m)):
        s = f"blocks/seg{si}"
        plan += [
            (f"{s}/attn/wq/w", (c, d, hq * dh), bf, ("normal", 1 / math.sqrt(d))),
            (f"{s}/attn/wk/w", (c, d, hkv * dh), bf, ("normal", 1 / math.sqrt(d))),
            (f"{s}/attn/wv/w", (c, d, hkv * dh), bf, ("normal", 1 / math.sqrt(d))),
            (f"{s}/attn/wo/w", (c, hq * dh, d), bf,
             ("normal", BRANCH / math.sqrt(hq * dh))),
            (f"{s}/ln1/scale", (c, d), f32, ("const", 1.0)),
            (f"{s}/ln2/scale", (c, d), f32, ("const", 1.0))]
        if m["qk_norm"]:
            plan += [(f"{s}/attn/q_norm/scale", (c, dh), f32, ("const", 1.0)),
                     (f"{s}/attn/k_norm/scale", (c, dh), f32, ("const", 1.0))]
        if binary:
            plan += [
                (f"{s}/ffn/bin_in/w_latent", (c, d, f), bf, ("uniform", 1.0)),
                (f"{s}/ffn/bin_in/scale", (c, f), f32, ("const", 1 / math.sqrt(d))),
                (f"{s}/ffn/bin_out/w_latent", (c, f, d), bf, ("uniform", 1.0)),
                (f"{s}/ffn/bin_out/scale", (c, d), f32,
                 ("const", BRANCH / math.sqrt(f)))]
        else:
            plan += [
                (f"{s}/ffn/w_gate/w", (c, d, f), bf, ("normal", 1 / math.sqrt(d))),
                (f"{s}/ffn/w_up/w", (c, d, f), bf, ("normal", 1 / math.sqrt(d))),
                (f"{s}/ffn/w_down/w", (c, f, d), bf,
                 ("normal", BRANCH / math.sqrt(f)))]
    return plan


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _q(x, lowp):
    """Round a matrix operand through ``lowp`` (None: bf16 as stated)."""
    x = x.astype(BF16)
    if lowp is not None:
        x = x.astype(jnp.dtype(lowp)).astype(BF16)
    return x


def _mm(a, b, lowp):
    return jnp.dot(_q(a, lowp), _q(b, lowp),
                   preferred_element_type=F32).astype(BF16)


def _rms(x, scale, eps=1e-6):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(BF16)


def _rope(x, pos, base):
    """x (L, H, D); rotate pairs (i, i + D/2) by pos * base^(-2i/D)."""
    dim = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(BF16)


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(BF16)


def _binary(x, w, scale):
    """bf16(bf16(sign(x) . sign(w)) * scale): exact integer sums."""
    dot = jnp.dot(_sign(x), _sign(w), preferred_element_type=F32)
    return (dot.astype(BF16).astype(F32) * scale).astype(BF16)


@partial(jax.jit, static_argnames=("m", "binary", "lowp"))
def _block(x, p, *, m, binary, lowp):
    m = dict(m)
    n = x.shape[0]
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    g = hq // hkv
    pos = jnp.arange(n)
    h = _rms(x, p["ln1"]["scale"])
    a = p["attn"]
    q = _mm(h, a["wq"]["w"], lowp).reshape(n, hq, dh)
    k = _mm(h, a["wk"]["w"], lowp).reshape(n, hkv, dh)
    v = _mm(h, a["wv"]["w"], lowp).reshape(n, hkv, dh)
    if m["qk_norm"]:
        q = _rms(q, a["q_norm"]["scale"])
        k = _rms(k, a["k_norm"]["scale"])
    q = _rope(q, pos, m["rope_base"])
    k = _rope(k, pos, m["rope_base"])
    kq, vq = _q(k, lowp), _q(v, lowp)
    nb = n // ROW_BLOCK

    def attend(i):
        rows = jax.lax.dynamic_slice_in_dim(q, i * ROW_BLOCK, ROW_BLOCK)
        rows = _q(rows, lowp).reshape(ROW_BLOCK, hkv, g, dh)
        s = jnp.einsum("qhgd,khd->hgqk", rows, kq,
                       preferred_element_type=F32) / math.sqrt(dh)
        qpos = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", _q(w, lowp), vq,
                       preferred_element_type=F32)
        return o.astype(BF16).reshape(ROW_BLOCK, hq * dh)

    o = jax.lax.map(attend, jnp.arange(nb)).reshape(n, hq * dh)
    x = x + _mm(o, a["wo"]["w"], lowp)
    h = _rms(x, p["ln2"]["scale"])
    f = p["ffn"]
    if binary:
        hb = _binary(h, f["bin_in"]["w_latent"], f["bin_in"]["scale"])
        y = _binary(hb, f["bin_out"]["w_latent"], f["bin_out"]["scale"])
    else:
        gate = _mm(h, f["w_gate"]["w"], lowp)
        up = _mm(h, f["w_up"]["w"], lowp)
        act = jax.nn.silu(gate.astype(F32)).astype(BF16) * up
        y = _mm(act, f["w_down"]["w"], lowp)
    return x + y


@partial(jax.jit, static_argnames=("vocab", "lowp"))
def _head(x, scale, w, ids, *, vocab, lowp):
    """Per row: (max logit, argmax, logit of each ids column), f32."""
    h = _rms(x, scale)
    lg = jnp.dot(_q(h, lowp), _q(w, lowp),
                 preferred_element_type=F32)[:, :vocab]
    return (lg.max(-1), jnp.argmax(lg, -1).astype(jnp.int32),
            jnp.take_along_axis(lg, ids, axis=1))


def _padded_len(n: int) -> int:
    """Row count a sequence is padded to: few shapes, little waste."""
    step = max(ROW_BLOCK, (1 << max(n - 1, 1).bit_length()) // 8)
    return -(-n // step) * step


def logit_stats(weights, m: dict, tokens, rows, ids, *, lowp=None):
    """Teacher-force ``tokens`` (L,) and read the logits at ``rows``.

    ``ids`` (len(rows), k) are token ids to read at each row. Returns
    numpy (max (R,), argmax (R,), picked (R, k)) over the true vocab.
    """
    m_key = tuple(sorted(m.items()))
    n = len(tokens)
    npad = _padded_len(n)
    tok = np.zeros((npad,), np.int32)
    tok[:n] = tokens
    x = weights["embed"]["table"][jnp.asarray(tok)].astype(BF16)
    for si, (binary, _, count) in enumerate(segments(m)):
        stacked = weights["blocks"][f"seg{si}"]
        for j in range(count):
            p = jax.tree.map(lambda a, j=j: a[j], stacked)
            x = _block(x, p, m=m_key, binary=binary, lowp=lowp)
    rows = np.asarray(rows, np.int32)
    ids = np.asarray(ids, np.int32).reshape(len(rows), -1)
    outs = []
    for r0 in range(0, len(rows), ROW_BLOCK):
        r = rows[r0:r0 + ROW_BLOCK]
        idb = ids[r0:r0 + ROW_BLOCK]
        pad = ROW_BLOCK - len(r)
        r = np.pad(r, (0, pad))
        idb = np.pad(idb, ((0, pad), (0, 0)))
        got = _head(x[jnp.asarray(r)], weights["ln_f"]["scale"],
                    weights["head"]["w"], jnp.asarray(idb),
                    vocab=m["vocab"], lowp=lowp)
        outs.append([np.asarray(a)[:ROW_BLOCK - pad] for a in got])
    return tuple(np.concatenate([o[i] for o in outs]) for i in range(3))


def served_gaps(weights, m: dict, prompt, served):
    """Per served token: how far its reference logit lies below the
    reference's best at the position that produced it."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]])
    rows = len(prompt) - 1 + np.arange(len(served))
    best, _, got = logit_stats(weights, m, seq, rows, served[:, None])
    return best - got[:, 0]


def control_gaps(weights, m: dict, prompt, served, *, lowp,
                 all_positions=False):
    """The control: at the positions of the served tokens (or at every
    position of prompt + tokens), the gap of the token that ``lowp``
    arithmetic puts first, read in the reference's logits."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]])
    rows = (np.arange(len(seq)) if all_positions
            else len(prompt) - 1 + np.arange(len(served)))
    _, pick, _ = logit_stats(weights, m, seq, rows,
                             np.zeros((len(rows), 1), np.int32), lowp=lowp)
    best, _, got = logit_stats(weights, m, seq, rows, pick[:, None])
    return best - got[:, 0]
