"""The measuring loop end to end on the CPU (bench/harness.py), and the
refusals of the command line (bench/run.py)."""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from bench import harness, opcount, stats
from bench_tiny import tiny_spec
from repro.serving.engine import ServeEngine

ROOT = harness.ROOT
SEED = 2**31 + 99


@pytest.mark.parametrize("workload,trace", [
    ("stablelm-3b.decode-batch", False),
    ("qwen3-8b-18L.long-prompt", True),
])
def test_cpu_rehearsal_last_line(workload, trace):
    spec = tiny_spec(workload)
    out = harness.run_cell(spec, SEED, 1.0, trace, require_tpu=False)
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(out["device"])
    want = spec["per_layer"] if trace else spec["end_to_end"]
    # the CPU has no peaks and no device plane: those readers stay silent
    silent = {"step_mfu", "device_idle_share", "int8_ffn_roofline",
              "flash_attn_roofline", "peak_hbm_gib"}
    assert {e["name"] for e in want} - silent == set(out["metrics"])
    for v in out["metrics"].values():
        assert v["value"] > 0
    assert out["checks"]["gap"]["value"] <= out["checks"]["gap"]["limit"]


def _cli(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-3b.decode-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_refuses_a_host_with_no_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_cli_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_device_kind_is_refused(monkeypatch):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    with pytest.raises(harness.NoDevice, match="peaks"):
        harness.device_info(1, {"TPU v5 lite": {}}, True)
    assert harness.device_info(1, {"TPU v99": {}}, True)["kind"] == "TPU v99"
    with pytest.raises(harness.NoDevice, match="chips"):
        harness.device_info(4, {"TPU v99": {}}, True)


def test_a_wave_records_the_lengths_of_its_prompts():
    """The engine books a prefill wave, then admits its requests with the
    wave's start as their admission time; the record keeps both."""
    tm = harness._telemetry_class()()
    tm.request_added(0, 7, now=0.5)
    tm.request_added(1, 9, now=0.6)
    tm.prefill_wave(1.0, n_reqs=2, bucket=16, now=1.2)
    tm.request_admitted(0, slot=0, prefilled_tokens=7, now=1.0)
    tm.request_admitted(1, slot=1, prefilled_tokens=9, now=1.0)
    assert tm.waves == [(1.0, 1.2, 16, [7, 9])]
    assert tm.admits == {0: 1.0, 1: 1.0}
    assert tm.slots == {0: 0, 1: 1}


def _req(index, rid, n_tokens):
    r = harness.Req(index, 0.0, np.zeros((4,), np.int32), n_tokens)
    r.rid, r.tokens = rid, list(range(n_tokens))
    return r


def test_the_sample_holds_every_slot():
    """One request of 400 tokens holds more than min_tokens alone; the
    sample still takes a request from each of the other slots."""
    run = harness.Run(model={}, cell={}, peaks={}, max_batch=4)
    run.reqs = [_req(0, 10, 400)] + [_req(i, 10 + i, 20) for i in
                                     range(1, 9)]
    run.slots = {10 + i: i % 4 for i in range(9)}
    for seed in (1, SEED):
        got = harness.sample(run, seed, 300)
        assert got[0] is run.reqs[0]
        assert sorted(run.slots[r.rid] for r in got) == [0, 1, 2, 3]
    # short requests: filled up to min_tokens from the rest
    got = harness.sample(run, SEED, 480)
    assert sum(len(r.tokens) for r in got) >= 480


def test_int8_roofline_counts_only_needed_rows():
    """Free slots and the padding of buckets and groups are not work."""
    m = {"n_layers": 4, "d_model": 128, "d_ff": 256, "binary_ffn": True,
         "edge_blocks_float": 1}
    pk = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    run = harness.Run(model=m, cell={}, peaks=pk, max_batch=8)
    run.t0 = 1.0
    run.ticks = [(0.5, 0.6, 8), (1.0, 1.1, 3), (1.2, 1.3, 0)]
    run.waves = [(1.5, 1.7, 64, [17, 40])]

    def bound(rows):
        return 2 * sum(max(o / 1e12, b / 1e9) for r in rows for o, b in (
            opcount.binary_matmul_call(r, 128, 256),
            opcount.binary_matmul_call(r, 256, 128)))
    assert stats.binary_ffn_bound_s(run) == pytest.approx(bound([3, 57]))


def test_a_cell_is_added_with_files_and_entries_only(tmp_path, monkeypatch):
    """A new cell with engine settings that no cell has (a paged pool with
    the prefix cache), bursty arrivals and shared prefixes runs from new
    files and a new entry alone: no file of the benchmark is edited."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    name = "stablelm-3b.prefix-burst"
    (tmp_path / "bench" / "cells" / (name + ".json")).write_text(json.dumps(
        {"engine": {"max_batch": 2, "max_len": 64, "kv_block_size": 16,
                    "prefix_cache": True},
         "check": {"gap_limit": 0.06, "min_tokens": 30, "min_served": 1}}))
    (tmp_path / "bench" / "traffic" / "prefix-burst.json").write_text(
        json.dumps({
            "arrivals": {"kind": "poisson", "rate_rps": 4.0, "burst": 2},
            "shared_prefix": {"tokens": 16, "groups": 1},
            "prompt_tokens": {"dist": "lognormal", "median": 24,
                              "sigma": 0.3, "min": 17, "max": 32},
            "output_tokens": {"dist": "lognormal", "median": 12,
                              "sigma": 0.3, "min": 8, "max": 16}}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": "stablelm-3b",
                               "traffic": "prefix-burst", "chips": 1,
                               "why": "test"})
    for e in bench["end_to_end"] + bench["per_layer"]:
        if "stablelm-3b.decode-batch" in e.get("workloads", []):
            e["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    seen = {}
    orig = ServeEngine.__init__

    def spy(self, *a, **kw):
        seen.update(kw)
        orig(self, *a, **kw)

    monkeypatch.setattr(ServeEngine, "__init__", spy)
    spec = harness.load_cell(name, root=str(tmp_path))
    tiny = tiny_spec("stablelm-3b.decode-batch")
    spec["config"] = tiny["config"]
    out = harness.run_cell(spec, SEED, 1.0, False, require_tpu=False)
    assert seen["prefix_cache"] is True and seen["kv_block_size"] == 16
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and "output_tok_s" in out["metrics"]
