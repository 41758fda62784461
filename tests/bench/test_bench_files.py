"""BENCHMARK.json and the files each cell is made of (bench/)."""

import json
import os
import re

import jax
import pytest

from bench import harness, weights
from repro.models import get_model

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_from_its_files(cell):
    spec = harness.load_cell(cell)
    assert spec["config"]["name"] == spec["workload"]["config"]
    assert {"engine", "check"} <= set(spec["cell"])
    assert {"max_batch", "max_len"} <= set(spec["cell"]["engine"])
    names = {e["name"] for e in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    for e in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(ROOT, e["name"]).read)
    # every per-layer metric moves an end-to-end metric this cell reports
    for e in spec["per_layer"]:
        assert e["moves"] in names


@pytest.mark.parametrize("cell", CELLS)
def test_engine_settings_are_serve_engine_keywords(cell):
    """A cell's engine settings go to ServeEngine as they stand; the
    harness gives the mesh and the telemetry itself."""
    import inspect
    from repro.serving.engine import ServeEngine
    keys = set(inspect.signature(ServeEngine).parameters) - {
        "api", "params", "mesh", "telemetry"}
    assert set(harness.load_cell(cell)["cell"]["engine"]) <= keys


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for e in metrics + BENCH["workloads"] + BENCH["configs"]:
        assert NAME.match(e["name"]), e["name"]
    assert len({e["name"] for e in metrics}) == len(metrics)
    for e in metrics:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", e["unit"])
        assert e["better"] in ("lower", "higher")
    for e in BENCH["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_weight_plan_matches_the_served_params(conf):
    """The reference's plan has every leaf of the program's params, with
    its shape and dtype, at the configuration's full size."""
    with open(os.path.join(ROOT, conf["file"])) as f:
        c = json.load(f)
    api = get_model(harness.program_config(c))
    have = {p: (tuple(a.shape), str(a.dtype)) for p, a in weights.flatten(
        jax.eval_shape(api.init, jax.random.PRNGKey(0))).items()}
    plan = harness.reference(ROOT, c).weight_plan(c["model"])
    assert {p: (tuple(s), d) for p, s, d, _ in plan} == have
    assert conf["reduced"] == c["reduced"]
