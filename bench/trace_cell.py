"""Run one cell's set-up and a traced window, and say where the device's
time and its idle gaps went, by the program's own spans and scopes.

    python bench/trace_cell.py --workload <name> --seed <n> --seconds <s>

From the root of a checkout, on the chips the cell asks for. The window
is the one ``bench/run.py --trace 1`` measures (same set-up, window loop and
annotations); nothing is checked against the reference. The last line
of standard output is one JSON object:

- ``metrics``: every end-to-end and per-layer metric of the cell, read
  from this window by its reader in ``bench/metrics/``;
- ``trace``: ``bench/attribution.py``'s reduction: ``idle_gaps`` by the
  innermost host span, ``scope_seconds``, ``module_seconds``, the top
  ``device_ops``; and its ``shares`` of busy time, with the KV pool's
  leaf shapes taken from the engine;
- ``tick_host_s``: the sum and count of ``serve_tick_host_seconds``, to
  set beside the idle charged to ``serve.*`` spans.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def trace_window(spec: dict, seed: int, seconds: float) -> dict:
    """Set up the cell, trace one window of it, and reduce the trace (the
    result line described above)."""
    import jax
    from bench import attribution, harness, tracefile
    peaks = harness._json(os.path.join(spec["root"], "bench", "peaks.json"))
    device = harness.device_info(spec["workload"]["chips"], peaks, True)
    _, eng, tm, m = harness.build(spec, seed)
    harness.warm_up(eng, spec, seed)
    jax.block_until_ready(eng.caches)
    run = harness.Run(model=m, cell=spec["cell"], max_batch=eng.max_batch,
                      peaks=peaks.get(device["kind"], {}))
    run.setup_s = time.perf_counter() - T_START
    tm.reset()
    for rec in (tm.waves, tm.ticks, tm.admits, tm.slots, tm._wave_lens):
        rec.clear()
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(logdir)
    try:
        with harness.annotate("bench.window", True):
            harness.window(eng, spec, seed, seconds, run, True)
    finally:
        jax.profiler.stop_trace()
    harness.record(run, tm)
    run.memory_peak_bytes = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:spec["workload"]["chips"]])
    try:
        path = tracefile.find_xplane(logdir)
        run.trace = tracefile.reduce(tracefile.load(path))
        events = attribution.load(path)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    metrics = {}
    for e in spec["end_to_end"] + spec["per_layer"]:
        v = harness.reader(spec["root"], e["name"]).read(run)
        if v is not None:
            metrics[e["name"]] = float(v)
    red = attribution.reduce(events)
    pools = {tuple(a.shape) for a in jax.tree.leaves(eng.caches)
             if a.ndim >= 4}
    h = getattr(tm, "tick_host_s", None)
    return {
        "metrics": metrics, "device": device,
        "trace": {k: red[k] for k in (
            "window_s", "busy_s", "idle_share", "idle_gaps",
            "scope_seconds", "module_seconds", "device_ops")},
        "shares": attribution.shares(red, sorted(pools)),
        "pool_shapes": sorted(pools),
        "ticks": len(run.ticks), "waves": len(run.waves),
        "tick_host_s": ({"sum": h.sum, "count": h.count}
                        if h is not None else None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    spec = harness.load_cell(args.workload)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = trace_window(spec, args.seed, args.seconds)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
