"""Idle gaps by innermost host span, device time by scope
(bench/attribution.py), on synthetic events and a recorded chip trace."""

import json
import os

import pytest

from bench import attribution, tracefile

H, D = "/host:CPU", "/device:TPU:0"
OPS = tracefile.DEVICE_OPS_LINE


def ev(plane, name, start, dur, scope="", module=""):
    line = OPS if plane.startswith("/device") else "python"
    return (plane, line, name, start, dur, scope, module)


def idle(events):
    return dict(attribution.reduce(events)["idle_gaps"])


def test_gap_goes_to_the_innermost_span():
    events = [ev(H, "bench.window", 0, 1000),
              ev(H, "bench.step", 0, 1000),
              ev(H, "serve.step", 0, 1000),
              ev(H, "serve.emit", 400, 200),
              ev(D, "a", 0, 400), ev(D, "b", 600, 400)]
    assert idle(events) == pytest.approx({"serve.emit": 200e-9})


def test_gap_straddling_two_children_is_split():
    events = [ev(H, "bench.window", 0, 1000),
              ev(H, "bench.step", 0, 1000),
              ev(H, "serve.sync", 300, 150),
              ev(H, "serve.emit", 450, 250),
              ev(D, "a", 0, 400), ev(D, "b", 600, 400)]
    # gap 400-600: 50 ns under serve.sync, 150 under serve.emit
    assert idle(events) == pytest.approx({"serve.sync": 50e-9,
                                          "serve.emit": 150e-9})


def test_parent_that_began_before_an_ended_child_is_found():
    events = [ev(H, "bench.window", 0, 1000),
              ev(H, "bench.step", 0, 1000),
              ev(H, "serve.admit", 100, 50),   # ended before the gap
              ev(D, "a", 0, 500), ev(D, "b", 700, 300)]
    assert idle(events) == pytest.approx({"bench.step": 200e-9})


def test_uncovered_gap_has_no_annotation():
    events = [ev(H, "bench.window", 0, 1000),
              ev(H, "bench.step", 0, 500),
              ev(D, "a", 0, 400), ev(D, "b", 800, 200)]
    assert idle(events) == pytest.approx({"bench.step": 100e-9,
                                          attribution.NO_SPAN: 300e-9})


def test_scope_seconds_add_up_and_unscoped_holds_the_rest():
    pre = "jit(serve_decode)/jit(main)/while/body/"
    events = [ev(H, "bench.window", 0, 1000),
              ev(D, "%copy.1 = bf16[28,8,64,4,8]{4,3,2,1,0} copy(x)", 0, 100,
                 module="jit_serve_decode"),
              ev(D, "%dus.2 = bf16[1,8,64,4,8]{4,3,2,1,0} fusion(x)", 100,
                 50, pre + "kv_pool/dynamic_update_slice",
                 "jit_serve_decode"),
              ev(D, "%fusion.3 = bf16[8,4,8]{2,1,0} fusion(x)", 150, 200,
                 pre + "attn/dot_general", "jit_serve_decode"),
              ev(D, "%fusion.4 = u32[64,4]{1,0} fusion(w)", 350, 300,
                 pre + "ffn_pack/convert_element_type", "jit_serve_decode"),
              ev(D, "%fusion.5 = bf16[8,64]{1,0} fusion(x)", 650, 100,
                 "jit(serve_prefill)/jit(main)/add", "jit_serve_prefill"),
              ev(D, "%while.6 = (s32[]) while(x)", 0, 700,
                 "jit(serve_decode)/while", "jit_serve_decode")]
    red = attribution.reduce(events)
    sc = red["scope_seconds"]
    assert sc == pytest.approx({"kv_pool": 50e-9, "attn": 200e-9,
                                "ffn_pack": 300e-9,
                                attribution.UNSCOPED: 200e-9})
    # control flow (the while) is left out, as in tracefile's op time
    assert sum(sc.values()) == pytest.approx(sum(red["op_seconds"].values()))
    assert red["module_seconds"] == pytest.approx(
        {"jit_serve_decode": 650e-9, "jit_serve_prefill": 100e-9})
    s = attribution.shares(red, pool_shapes=[(28, 8, 64, 4, 8)])
    # the kv_pool scope plus the unscoped copy of the stacked pool
    assert s["kv_pool_share"] == pytest.approx(100 * 150 / 750)
    assert s["pool_shape_share"] == pytest.approx(100 * 150 / 750)
    assert s["weight_pack_share"] == pytest.approx(100 * 300 / 750)
    assert s["unscoped_share"] == pytest.approx(100 * 200 / 750)


def test_five_tuples_reduce_without_scopes():
    red = attribution.reduce([(H, "python", "bench.window", 0, 100),
                              (D, OPS, "a", 0, 60)])
    assert red["scope_seconds"] == pytest.approx(
        {attribution.UNSCOPED: 60e-9})
    assert dict(red["idle_gaps"]) == pytest.approx(
        {attribution.NO_SPAN: 40e-9})


def test_recorded_chip_trace_reads_as_tracefile_does():
    """The 50 ms decode-batch fixture, recorded on a TPU v5e: busy time,
    idle share and per-op time are tracefile's, to the bit, and the
    values it gave before this module existed."""
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "trace_stablelm_decode_batch_50ms.json")
    with open(path) as f:
        events = [tuple(e) for e in json.load(f)]
    red = attribution.reduce(events)
    base = tracefile.reduce(events)
    assert red["busy_s"] == base["busy_s"] == 0.03992499
    assert red["idle_share"] == base["idle_share"] == 0.20150020000000002
    assert red["op_seconds"] == base["op_seconds"]
    assert len(red["op_seconds"]) == 210
    assert sum(red["op_seconds"].values()) == pytest.approx(0.039924342,
                                                            abs=1e-12)
    assert tracefile.op_time(red, r"^%int8_matmul_pallas\b") == \
        pytest.approx((0.003632068, 2))
    assert tracefile.op_time(red, r"^%_flash_call\b") == \
        pytest.approx((0.010972023, 2))
    # the idle time is all charged, now split by the innermost span
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(
        sum(v for _, v in base["idle_gaps"]))


def test_scopes_come_from_the_hlo_the_profiler_keeps(tmp_path):
    """A CPU trace of a jitted function with a named scope: the metadata
    plane's HLO names the scope on the function's instructions."""
    import jax
    import jax.numpy as jnp

    def serve_probe(x):
        with jax.named_scope("kv_pool"):
            y = jnp.sin(x) * 2
        return (y @ y.T).sum()

    f = jax.jit(serve_probe)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = attribution.hlo_op_names(tracefile.find_xplane(str(tmp_path)))
    (ops,) = [v for k, v in names.items() if k.startswith("jit_serve_probe")]
    scopes = {attribution.top_scope(v) for v in ops.values()}
    assert scopes == {"kv_pool", attribution.UNSCOPED}
