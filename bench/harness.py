"""One benchmark cell, run once: set-up, a measured window, the check.

Everything a cell is made of is a file found by name:

  BENCHMARK.json                 the cell: its configuration, traffic, chips
  bench/configs/<config>.json    sizes, the program's entry, the reference
  bench/references/<ref>.py      the plain reference and its weight plan
  bench/traffic/<traffic>.json   the traffic mix (bench/loadgen.py)
  bench/cells/<workload>.json    ServeEngine settings ("engine", passed
                                 through as keywords), the check's
                                 limit, optionally a "driver"
  bench/drivers/<driver>.py      what the window drives (default engine)
  bench/metrics/<metric>.py      one reader per metric: read(run) -> value
  bench/peaks.json               published peaks by device_kind

The ``engine`` driver drives ``ServeEngine.add_request`` and the
``ServeEngine.step`` loop, open loop on the wall clock: a request is
added when it falls due, and its stream callback stamps the host clock at
every token. Set-up makes the weights on the device from the seed in one
jitted call (sharded over a ``model`` mesh of the cell's chips when it
asks for more than one) and runs each prefill shape the traffic can
reach, and decode, once. After the window the program's state is freed,
and the reference checks a seeded sample of the served requests (see
``sample`` and ``check``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a device kind with no peaks."""


# ---------------------------------------------------------------------------
# loading a cell
# ---------------------------------------------------------------------------

def _json(path):
    with open(path) as f:
        return json.load(f)


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, read from its files."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    d = os.path.join(root, "bench")
    config = _json(os.path.join(d, "configs", w["config"] + ".json"))
    metrics = [e for e in bench["end_to_end"]
               if workload in e.get("workloads", [workload])]
    per_layer = [e for e in bench["per_layer"]
                 if workload in e.get("workloads", [workload])]
    return {"workload": w, "config": config,
            "traffic": _json(os.path.join(d, "traffic",
                                          w["traffic"] + ".json")),
            "cell": _json(os.path.join(d, "cells", workload + ".json")),
            "end_to_end": metrics, "per_layer": per_layer,
            "run_seconds": bench["run_seconds"], "root": root}


def reader(root: str, name: str):
    return _module(os.path.join(root, "bench", "metrics", name + ".py"),
                   "bench_metric_" + name.replace(".", "_"))


def reference(root: str, config: dict):
    return _module(os.path.join(root, "bench", "references",
                                config["reference"] + ".py"),
                   "bench_reference_" + config["reference"])


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    index: int
    due: float                  # absolute perf_counter seconds
    prompt: np.ndarray
    max_new: int
    rid: int = -1
    added: float = math.nan
    failed: bool = False
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: float = math.nan

    def stream(self, tok):
        if tok is None:
            self.done = time.perf_counter()
        else:
            self.times.append(time.perf_counter())
            self.tokens.append(int(tok))


@dataclasses.dataclass
class Run:
    """What the metric readers read; times are perf_counter seconds."""
    model: dict
    cell: dict
    peaks: dict
    max_batch: int
    t0: float = 0.0             # window opens
    t1: float = 0.0             # window closes
    setup_s: float = 0.0
    reqs: list = dataclasses.field(default_factory=list)
    telemetry: object = None    # the engine's Telemetry, reset at t0
    waves: list = dataclasses.field(default_factory=list)
    ticks: list = dataclasses.field(default_factory=list)
    admits: dict = dataclasses.field(default_factory=dict)  # rid -> time
    slots: dict = dataclasses.field(default_factory=dict)   # rid -> slot
    trace: dict | None = None   # tracefile.reduce of the window
    memory_peak_bytes: int = 0
    compiles: int = 0           # backend compiles inside the window

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def _telemetry_class():
    """A Telemetry that also keeps each prefill wave and decode tick as a
    record, and each request's admission time and slot (the harness's own
    view; the program's hooks are unchanged)."""
    from repro.serving.telemetry import Telemetry

    class Recording(Telemetry):
        def __init__(self):
            super().__init__()
            self.waves, self.ticks = [], []
            self.admits: dict = {}
            self.slots: dict = {}
            self._wave_lens: dict = {}

        def prefill_wave(self, t0, *, n_reqs, bucket, now=None):
            super().prefill_wave(t0, n_reqs=n_reqs, bucket=bucket, now=now)
            # the engine books the wave, then admits its requests with the
            # wave's start as their admission time
            lens = self._wave_lens[t0] = []
            self.waves.append((t0, now, bucket, lens))

        def request_admitted(self, rid, *, slot, prefilled_tokens,
                             cached_tokens=0, now=None):
            now = self.clock() if now is None else now
            super().request_admitted(rid, slot=slot,
                                     prefilled_tokens=prefilled_tokens,
                                     cached_tokens=cached_tokens, now=now)
            self.admits[rid] = now
            self.slots[rid] = slot
            if now in self._wave_lens:
                self._wave_lens[now].append(prefilled_tokens)

        def decode_tick(self, t0, *, n_active, now=None):
            super().decode_tick(t0, n_active=n_active, now=now)
            self.ticks.append((t0, now, n_active))

    return Recording


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def device_info(chips: int, peaks: dict, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoDevice(f"no TPU: JAX sees {devs[0].platform}")
        if len(devs) < chips:
            raise NoDevice(f"cell needs {chips} chips, JAX sees {len(devs)}")
        if kind not in peaks:
            raise NoDevice(f"device_kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind,
            "count": len(devs)}


def _compile_counter():
    import jax
    box = {"n": 0, "on": False}

    def listen(event, duration, **_):
        if box["on"] and event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


def program_config(config: dict):
    """The program's ModelConfig for a configuration file's sizes."""
    from repro.configs import PrecisionPolicy, get_config
    m = config["model"]
    return get_config(config["arch"]).replace(
        n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab=m["vocab"], qk_norm=m["qk_norm"], rope_base=m["rope_base"],
        policy=PrecisionPolicy(binary_ffn=m["binary_ffn"],
                               edge_blocks_float=m["edge_blocks_float"],
                               binary_mode=m["binary_mode"]))


def build(spec: dict, seed: int):
    """(weights, engine, telemetry, model sizes) for one cell. A cell on
    more than one chip serves over a ``model`` mesh of them, its weights
    made sharded."""
    import jax
    from repro.models import get_model
    from repro.serving.engine import ServeEngine
    from bench import weights as W
    m = spec["config"]["model"]
    api = get_model(program_config(spec["config"]))
    ref = reference(spec["root"], spec["config"])
    plan = ref.weight_plan(m)
    want = {p: (tuple(s), str(d)) for p, s, d, _ in plan}
    have = {p: (tuple(a.shape), str(a.dtype)) for p, a in
            W.flatten(jax.eval_shape(api.init, jax.random.PRNGKey(0))).items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))
        raise ValueError(f"weight plan differs from the program's params: "
                         f"{diff[:6]}")
    mesh, shardings = None, None
    chips = spec["workload"]["chips"]
    if chips > 1:
        from repro.launch import specs
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((chips,), ("model",), devices=jax.devices()[:chips])
        _, shardings = specs.param_shardings(
            api, mesh, specs.mesh_rules_for(api.cfg, mesh))
    params = W.make(plan, seed, shardings)
    tm = _telemetry_class()()
    eng = ServeEngine(api, params, mesh=mesh, telemetry=tm,
                      **spec["cell"]["engine"])
    return params, eng, tm, m


def warm_up(eng, spec: dict, seed: int):
    """Run every (bucket, group) prefill shape and the decode step that
    the traffic can reach, once each."""
    from repro.serving.scheduler import bucket_len, pad_group
    from bench import loadgen
    lo, hi = loadgen.length_range(spec["traffic"])
    buckets = sorted({bucket_len(n, eng.buckets) for n in (lo, hi)}
                     | {b for b in eng.buckets if lo <= b <= hi})
    buckets = [b for b in buckets if b >= bucket_len(lo, eng.buckets)]
    groups, g = [], 1
    while g <= pad_group(eng.max_batch):
        groups.append(min(g, eng.max_batch))
        g *= 2
    vocab = eng.api.cfg.vocab
    for b in buckets:
        for n in groups:
            for i in range(n):
                eng.add_request(loadgen.prompt_tokens(
                    seed ^ 0x5EED, i, min(b, hi), vocab), max_new=2)
            eng.run()


@contextlib.contextmanager
def annotate(name: str, on: bool):
    """A profiler span named ``name`` when tracing, else nothing."""
    if on:
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


def window(eng, spec: dict, seed: int, seconds: float, run: Run,
           trace: bool):
    """The measured window, by the cell's driver
    (``bench/drivers/<driver>.py``, ``engine`` unless the cell names
    another)."""
    name = spec["cell"].get("driver", "engine")
    _module(os.path.join(spec["root"], "bench", "drivers", name + ".py"),
            "bench_driver_" + name).window(eng, spec, seed, seconds, run,
                                           trace)


def record(run: Run, tm):
    """Move the telemetry's records of the window into ``run``."""
    run.telemetry = tm
    run.waves, run.ticks = tm.waves, tm.ticks
    run.admits, run.slots = dict(tm.admits), dict(tm.slots)


def sample(run: Run, seed: int, min_tokens: int) -> list:
    """The requests to check, all of their served tokens each: the one
    with the most tokens served in the window; one request from every
    other slot, drawn from the seed, so that each slot's rows are
    compared; then others in an order drawn from the seed until the
    sample holds ``min_tokens`` served tokens. A request still decoding
    when the window closed counts with the tokens it was served (a
    decode-heavy window finishes few)."""
    served = [r for r in run.reqs if r.tokens and not r.failed]
    if not served:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    first = min(served, key=lambda r: (-len(r.tokens), r.index))
    out = [first]
    by_slot: dict = {}
    for r in served:
        by_slot.setdefault(run.slots.get(r.rid, -1), []).append(r)
    by_slot.pop(run.slots.get(first.rid, -1), None)
    by_slot.pop(-1, None)
    for slot in sorted(by_slot):
        reqs = by_slot[slot]
        out.append(reqs[rng.integers(len(reqs))])
    rest = [r for r in served if all(r is not o for o in out)]
    rng.shuffle(rest)
    toks = sum(len(r.tokens) for r in out)
    for r in rest:
        if toks >= min_tokens:
            break
        out.append(r)
        toks += len(r.tokens)
    return out


def check(spec: dict, run: Run, seed: int, weights_fn) -> list:
    """The comparison that decides ``correct``: [(name, value, limit, ok)].

    The sample (``sample``) is run through the plain reference over
    prompt + served tokens; ``gap`` is the widest distance by which a
    served token's reference logit lies below the reference's best at
    the position that produced it. A finished request must also have been
    served exactly its max_new tokens."""
    ck = spec["cell"]["check"]
    served = [r for r in run.reqs if r.tokens and not r.failed]
    out = [("requests_served", len(served), ck["min_served"],
            len(served) >= ck["min_served"])]
    if not served:
        return out
    done = [r for r in served if not math.isnan(r.done)]
    short = sum(len(r.tokens) != r.max_new for r in done)
    out.append(("wrong_length", short, 0, short == 0))
    chosen = sample(run, seed, ck["min_tokens"])
    toks = sum(len(r.tokens) for r in chosen)
    ref = reference(spec["root"], spec["config"])
    w = weights_fn()
    gap = max(float(np.max(ref.served_gaps(w, run.model, r.prompt,
                                           r.tokens))) for r in chosen)
    out.append(("checked_tokens", toks, ck["min_tokens"],
                toks >= min(ck["min_tokens"],
                            sum(len(r.tokens) for r in served))))
    out.append(("gap", gap, ck["gap_limit"], gap <= ck["gap_limit"]))
    return out


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: float | None = None,
             log=sys.stderr) -> dict:
    """Set up, measure, check; returns the result line's dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    from bench import weights as W
    peaks = _json(os.path.join(spec["root"], "bench", "peaks.json"))
    device = device_info(spec["workload"]["chips"], peaks, require_tpu)
    compiles = _compile_counter()

    params, eng, tm, m = build(spec, seed)
    warm_up(eng, spec, seed)
    jax.block_until_ready(eng.caches)
    run = Run(model=m, cell=spec["cell"], max_batch=eng.max_batch,
              peaks=peaks.get(device["kind"], {}))
    run.setup_s = time.perf_counter() - t_start
    print(f"setup_s {run.setup_s:.3f}", file=log, flush=True)

    tm.reset()
    for rec in (tm.waves, tm.ticks, tm.admits, tm.slots,
                tm._wave_lens):
        rec.clear()
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(logdir)
    compiles["on"] = True
    try:
        with annotate("bench.window", trace):
            window(eng, spec, seed, seconds, run, trace)
    finally:
        compiles["on"] = False
        if trace:
            jax.profiler.stop_trace()
    run.compiles = compiles["n"]
    record(run, tm)
    print(f"compiles_in_window {run.compiles}", file=log, flush=True)
    # the peak on the fullest chip the cell uses
    run.memory_peak_bytes = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:spec["workload"]["chips"]])
    device["memory_peak_bytes"] = run.memory_peak_bytes
    if trace:
        from bench import tracefile
        try:
            run.trace = tracefile.reduce(
                tracefile.load(tracefile.find_xplane(logdir)))
        except ValueError:
            if require_tpu:
                raise
            # the CPU backend writes no device plane: nothing to reduce
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        if run.trace is not None:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]

    names = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for e in names:
        v = reader(spec["root"], e["name"]).read(run)
        if v is not None:
            metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}

    attempted = sum(1 for r in run.reqs if r.due < run.t0 + seconds)
    failed = sum(1 for r in run.reqs if r.failed)
    eng.caches = None
    del eng, params, tm
    gc.collect()
    t_check = time.perf_counter()
    checks = check(spec, run, seed, lambda: W.make(
        reference(spec["root"], spec["config"]).weight_plan(m), seed))
    print(f"check_s {time.perf_counter() - t_check:.3f}", file=log,
          flush=True)
    for name, value, limit, ok in checks:
        print(f"check {name} {value} limit {limit} {'ok' if ok else 'FAIL'}",
              file=log, flush=True)
    out = {"correct": all(ok for *_, ok in checks) and failed == 0,
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim, _ in checks}
    return out
