"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Prints progress and the checks (each number
compared, beside its limit) on standard error, and as the last line of
standard output one JSON object: correct, attempted, failed, metrics,
device, with --trace 1 also breakdown, and last the checks. With
--trace 0 the metrics are the cell's end-to-end metrics; with --trace 1
its per-layer metrics, read from a profiler trace of the window.

Exits non-zero, printing no result, when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that bench/peaks.json lacks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    spec = harness.load_cell(args.workload)
    # the checkout's .jax_cache/ (or $JAX_COMPILATION_CACHE_DIR), every
    # program kept, so that only a cell's first run there compiles
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = harness.run_cell(spec, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
