"""Trace reduction (bench/tracefile.py) on synthetic events."""

import pytest

from bench import tracefile

H, D = "/host:CPU", "/device:TPU:0"
OPS = tracefile.DEVICE_OPS_LINE


def ev(plane, name, start, dur, line=None):
    return (plane, line or (OPS if plane.startswith("/device") else "py"),
            name, start, dur)


def test_busy_union_idle_and_kernel_time():
    events = [
        ev(H, "bench.window", 0, 1000),
        ev(H, "bench.step", 0, 600),
        ev(H, "bench.sleep", 600, 400),
        ev(D, "fusion.1", 100, 180),          # overlaps the next op
        ev(D, "int8_kernel", 250, 150),
        ev(D, "int8_kernel", 500, 50),
        ev(D, "fusion.2", 990, 100),          # runs past the window
        ev(D, "XLA Modules", 0, 1000, line="XLA Modules"),  # ignored line
    ]
    red = tracefile.reduce(events)
    assert red["window_s"] == pytest.approx(1e-6)
    busy_ns = (400 - 100) + 50 + 10
    assert red["busy_s"] == pytest.approx(busy_ns * 1e-9)
    assert red["idle_share"] == pytest.approx(1 - busy_ns / 1000)
    t, hits = tracefile.op_time(red, r"int8")
    assert hits == 1 and t == pytest.approx(200e-9)
    idle = dict(red["idle_gaps"])
    # gaps 0-100 and 400-500 lie under bench.step; the gap 550-990 lies
    # mostly under bench.sleep and is charged to it whole
    assert idle["bench.step"] == pytest.approx(200e-9)
    assert idle["bench.sleep"] == pytest.approx(440e-9)
    assert red["device_ops"][0][0] == "int8_kernel"


def test_two_devices_are_averaged():
    events = [ev(H, "bench.window", 0, 100),
              ev(D, "a", 0, 100), ev("/device:TPU:1", "a", 0, 50)]
    red = tracefile.reduce(events)
    assert red["n_devices"] == 2
    assert red["busy_s"] == pytest.approx(75e-9)
    assert dict(red["idle_gaps"])["(no annotation)"] == pytest.approx(25e-9)


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        tracefile.reduce([ev(D, "a", 0, 1)])
    with pytest.raises(ValueError):
        tracefile.reduce([ev(H, "bench.window", 0, 10)])


def test_recorded_chip_trace():
    """50 ms of a stablelm-3b.decode-batch window on a TPU v5e (PR 12): a
    blocking 8x512 prefill wave in progress."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "trace_stablelm_decode_batch_50ms.json")
    with open(path) as f:
        red = tracefile.reduce([tuple(e) for e in json.load(f)])
    assert red["window_s"] == pytest.approx(0.05)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert 0.0 <= red["idle_share"] < 0.5
    assert sum(red["op_seconds"].values()) <= red["busy_s"] * 1.0001
    t, hits = tracefile.op_time(red, r"^%int8_matmul_pallas\b")
    assert hits >= 1 and t > 0
    t, hits = tracefile.op_time(red, r"^%_flash_call\b")
    assert hits >= 1 and t > 0
    assert not any(tracefile.CONTROL_FLOW.match(k) for k in red["op_seconds"])
    assert {k for k, _ in red["idle_gaps"]} <= {"bench.step",
                                                "bench.add_request",
                                                "(no annotation)"}
