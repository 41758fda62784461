"""Operations and HBM bytes that the algorithm needs, from shapes alone.

The per-call formulas are those of the program's analytic cost model
(``distributed/analytic_cost.py``: ``_attn_gqa``, ``_ffn``, ``_head``),
copied here so that no later change to the program can move them. A
multiply-add counts 2 operations; causal attention over S tokens counts
S * S / 2 score entries. Padding that a kernel adds for its own layout
(lanes, bucket rows) is not work the algorithm needs and is not counted,
except that a kernel call is charged for every row it is given.

``m`` is a configuration's "model" dict (bench/configs/<name>.json).
"""

from __future__ import annotations


def binary_layers(m: dict) -> int:
    if not m["binary_ffn"]:
        return 0
    return max(0, m["n_layers"] - 2 * m["edge_blocks_float"])


def attn_proj_ops(m: dict, tokens: float) -> float:
    d, dh = m["d_model"], m["head_dim"]
    return (2 * tokens * d * (m["n_heads"] * dh) * 2
            + 2 * tokens * d * (m["n_kv_heads"] * dh) * 2)


def attn_score_ops(m: dict, q: float, t: float, *, causal: bool) -> float:
    """One layer, one sequence: q queries against t keys (QK and PV)."""
    half = 0.5 if (causal and q == t) else 1.0
    return 2 * m["n_heads"] * q * t * m["head_dim"] * 2 * half


def ffn_ops(m: dict, tokens: float, *, binary: bool) -> float:
    d, f = m["d_model"], m["d_ff"]
    return 2 * tokens * d * f * (2 if binary else 3)


def head_ops(m: dict, tokens: float) -> float:
    return 2 * tokens * m["d_model"] * m["vocab"]


def token_ops(m: dict, tokens: float) -> tuple[float, float]:
    """(bf16 ops, binary ops) of the matmuls of ``tokens`` tokens through
    every layer and the head, attention scores excluded."""
    nb = binary_layers(m)
    nf = m["n_layers"] - nb
    bf16 = (m["n_layers"] * attn_proj_ops(m, tokens)
            + nf * ffn_ops(m, tokens, binary=False) + head_ops(m, tokens))
    return bf16, nb * ffn_ops(m, tokens, binary=True)


def prefill_ops(m: dict, length: int) -> tuple[float, float]:
    """(bf16, binary) ops of prefilling one prompt of ``length`` tokens:
    the head runs once, on the last token."""
    bf16, binary = token_ops(m, length)
    bf16 -= head_ops(m, length - 1)
    bf16 += m["n_layers"] * attn_score_ops(m, length, length, causal=True)
    return bf16, binary


def decode_ops(m: dict, context: int) -> tuple[float, float]:
    """(bf16, binary) ops of one decoded token that attends to ``context``
    cached positions, itself included."""
    bf16, binary = token_ops(m, 1)
    bf16 += m["n_layers"] * attn_score_ops(m, 1, context, causal=False)
    return bf16, binary


def flash_call(m: dict, lengths) -> tuple[float, float]:
    """(ops, bytes) of one layer's causal prefill attention over prompts of
    the given true lengths: Q, K, V read and O written once, bf16."""
    dh, hq, hkv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    ops = sum(attn_score_ops(m, n, n, causal=True) for n in lengths)
    nbytes = sum(n * dh * 2 * (2 * hq + 2 * hkv) for n in lengths)
    return ops, nbytes


def binary_matmul_call(rows: int, k: int, n: int) -> tuple[float, float]:
    """(ops, bytes) of one +-1 matmul: int8 activations (rows, k), packed
    1-bit weights (n, k), int32 result (rows, n)."""
    ops = 2 * rows * k * n
    nbytes = rows * k + n * (-(-k // 32)) * 4 + rows * n * 4
    return ops, nbytes

