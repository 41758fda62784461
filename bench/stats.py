"""Helpers the metric readers share (bench/metrics/*.py)."""

from __future__ import annotations

import math

import numpy as np

from bench import opcount


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile (numpy's default); None if empty."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_window(run, t: float) -> bool:
    return run.t0 <= t <= run.t1


def window_work(run) -> tuple[float, float]:
    """(bf16 ops, binary ops) of the useful work done in the window: the
    prefill of every prompt admitted in it whose first token came in it,
    and every token decoded in it (free slots' rows are not counted)."""
    m, bf16, binary = run.model, 0.0, 0.0
    for r in run.reqs:
        t_admit = run.admits.get(r.rid)
        if (t_admit is not None and t_admit >= run.t0 and r.times
                and r.times[0] <= run.t1):
            a, b = opcount.prefill_ops(m, len(r.prompt))
            bf16, binary = bf16 + a, binary + b
        for j, t in enumerate(r.times[1:], start=1):
            if in_window(run, t):
                a, b = opcount.decode_ops(m, len(r.prompt) + j)
                bf16, binary = bf16 + a, binary + b
    return bf16, binary


def step_mfu(run) -> float | None:
    """Least time the chip's peaks allow for the window's useful work, as
    a share of the window (%)."""
    if not run.peaks:
        return None
    bf16, binary = window_work(run)
    t = (bf16 / run.peaks["bf16_flops_per_s"]
         + binary / run.peaks["int8_ops_per_s"])
    return 100.0 * t / run.window_s


def binary_ffn_bound_s(run) -> float:
    """Least time of every binary FFN matmul call in the window: per call
    the larger of its int8 compute bound and its HBM bound. A call's rows
    are the tokens the algorithm needs: a decode tick's active slots and a
    blocking prefill wave's true prompt lengths; the rows that buckets,
    groups and free slots pad are not work. Interleaved prefill slices
    are not counted: a cell that slices needs a reader that counts them."""
    m, pk = run.model, run.peaks
    rows = [n_active for t0, _, n_active in run.ticks
            if t0 >= run.t0 and n_active]
    rows += [sum(lens) for t0, _, _, lens in run.waves
             if t0 >= run.t0 and lens]
    nb = opcount.binary_layers(m)
    total = 0.0
    for r in rows:
        for k, n in ((m["d_model"], m["d_ff"]), (m["d_ff"], m["d_model"])):
            ops, nbytes = opcount.binary_matmul_call(r, k, n)
            total += nb * max(ops / pk["int8_ops_per_s"],
                              nbytes / pk["hbm_bytes_per_s"])
    return total


def flash_bound_s(run) -> float:
    """Least time of every prefill-attention call of the window's blocking
    prefill waves, per layer call the larger of compute and HBM bounds."""
    m, pk = run.model, run.peaks
    total = 0.0
    for t0, _, _, lens in run.waves:
        if t0 >= run.t0 and lens:
            ops, nbytes = opcount.flash_call(m, lens)
            total += m["n_layers"] * max(ops / pk["bf16_flops_per_s"],
                                         nbytes / pk["hbm_bytes_per_s"])
    return total


def kernel_roofline(run, pattern: str, bound_s: float) -> float | None:
    """bound_s over the device time of the ops matching ``pattern`` (%);
    None where the trace holds no such op."""
    from bench import tracefile
    if run.trace is None or not run.peaks:
        return None
    t, hits = tracefile.op_time(run.trace, pattern)
    if not hits or t <= 0 or bound_s <= 0:
        return None
    return 100.0 * bound_s / t


def finite(x: float) -> float:
    """JSON has no infinity: a request that failed stands in as 1e30 s."""
    return 1e30 if math.isinf(x) else x
