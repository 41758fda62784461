"""bench/opcount.py against the program's analytic cost model at the same
shapes (a vocabulary that is a multiple of 256, where the program's padded
head and the algorithm's agree)."""

import pytest

from bench import opcount
from repro.configs import PrecisionPolicy, ShapeSpec, get_config
from repro.distributed import analytic_cost as ac

M = {"n_layers": 18, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
     "head_dim": 128, "d_ff": 12288, "vocab": 151808, "binary_ffn": True,
     "edge_blocks_float": 2}


def _cfg():
    return get_config("qwen3-8b").replace(
        n_layers=18, vocab=151808,
        policy=PrecisionPolicy(binary_ffn=True, edge_blocks_float=2,
                               binary_mode="int8"))


def test_decode_token_matches_analytic_cost():
    t, b = 1000, 16
    cost = ac.forward_cost(_cfg(), ShapeSpec("d", t, b, "decode"))
    bf16, binary = opcount.decode_ops(M, t)
    assert b * bf16 == pytest.approx(cost.flops_bf16, rel=1e-12)
    assert b * binary == pytest.approx(cost.flops_int8, rel=1e-12)


def test_prefill_matches_analytic_cost():
    s, b = 2048, 2
    cost = ac.forward_cost(_cfg(), ShapeSpec("p", s, b, "prefill"))
    bf16, binary = opcount.prefill_ops(M, s)
    assert b * bf16 == pytest.approx(cost.flops_bf16, rel=1e-12)
    assert b * binary == pytest.approx(cost.flops_int8, rel=1e-12)


def test_kernel_calls():
    ops, nbytes = opcount.flash_call(M, [1024, 512])
    assert ops == sum(2 * 32 * n * n * 128 * 2 * 0.5 for n in (1024, 512))
    assert nbytes == (1024 + 512) * 128 * 2 * (2 * 32 + 2 * 8)
    ops, nbytes = opcount.binary_matmul_call(8, 2560, 6912)
    assert ops == 2 * 8 * 2560 * 6912
    assert nbytes == 8 * 2560 + 6912 * 80 * 4 + 8 * 6912 * 4
