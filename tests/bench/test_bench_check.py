"""The comparison that decides ``correct``: it passes the program, fails
a token altered where it is produced, and fails the control."""

import importlib.util
import os

import numpy as np
import pytest

from bench import harness
from bench_tiny import tiny_spec
from repro.serving.engine import ServeEngine

SEED = 2**31 + 7


def test_a_token_altered_where_produced_is_not_correct(monkeypatch):
    spec = tiny_spec("stablelm-3b.decode-batch")
    vocab = spec["config"]["model"]["vocab"]
    orig = ServeEngine._sample

    def altered(self, logits, reqs):
        return (orig(self, logits, reqs) + 1) % vocab

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    out = harness.run_cell(spec, SEED, 1.0, False, require_tpu=False)
    assert out["correct"] is False
    gap = out["checks"]["gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", ["stablelm-3b.decode-batch",
                                      "qwen3-8b-18L.long-prompt"])
def test_the_control_fails_the_limit(workload):
    """At a size the CPU holds (8 layers, d_model 256, 4096 tokens), the
    float8 control (bench/control.py's reading) lies above the cell's
    gap_limit and the program below it, on the same 6 x 56 served
    tokens, run to completion so that the host's speed does not matter."""
    spec = tiny_spec(workload)
    m = dict(spec["config"]["model"], n_layers=8, d_model=256, head_dim=64,
             d_ff=512, vocab=4096, edge_blocks_float=2)
    spec["config"]["model"] = m
    spec["cell"] = dict(spec["cell"], engine={"max_batch": 4,
                                              "max_len": 160})
    params, eng, _, _ = harness.build(spec, SEED)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, m["vocab"], n, dtype=np.int32)
               for n in (33, 48, 64, 72, 88, 96)]
    rids = [eng.add_request(p, max_new=56) for p in prompts]
    out = eng.run()
    ref = harness.reference(harness.ROOT, spec["config"])
    prog = max(ref.served_gaps(params, m, p, out[r]).max()
               for p, r in zip(prompts, rids))
    ctrl = max(ref.control_gaps(params, m, p, out[r],
                                lowp=_control().LOWP).max()
               for p, r in zip(prompts, rids))
    limit = harness.load_cell(workload)["cell"]["check"]["gap_limit"]
    assert prog <= limit < ctrl, (prog, ctrl)


def _control():
    path = os.path.join(harness.ROOT, "bench", "control.py")
    spec = importlib.util.spec_from_file_location("bench_control", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
