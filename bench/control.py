"""Readings that set a cell's ``gap_limit``: the program's and the
control's, on many seeds in one process.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed: the cell's weights and engine, the cell's traffic through
the timed path for ``--seconds`` (compiles are paid by the first seed;
later seeds load them), then, on the same sample of finished requests that a
benchmark run checks, three widest gaps in the reference's logits:

  program      the served tokens (what a run compares with gap_limit)
  control      the tokens that the reference computed in float8_e4m3fn
               (the precision below bf16) puts first, at the same
               positions
  control_all  the same at every position of prompt + served tokens

One JSON line per seed on standard output. The benchmark's own runs do
not run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

LOWP = "float8_e4m3fn"


def readings(spec, seed, seconds):
    import numpy as np
    from bench import harness
    from bench import weights as W
    params, eng, tm, m = harness.build(spec, seed)
    harness.warm_up(eng, spec, seed)
    run = harness.Run(model=m, cell=spec["cell"], peaks={},
                      max_batch=eng.max_batch)
    harness.window(eng, spec, seed, seconds, run, False)
    harness.record(run, tm)
    eng.caches = None
    del eng, params, tm
    gc.collect()
    ref = harness.reference(spec["root"], spec["config"])
    w = W.make(ref.weight_plan(m), seed)
    chosen = harness.sample(run, seed, spec["cell"]["check"]["min_tokens"])
    out = {"seed": seed, "requests": len(chosen),
           "tokens": sum(len(r.tokens) for r in chosen),
           "prompt_tokens": sum(len(r.prompt) for r in chosen)}
    for key, fn in (
            ("program", lambda r: ref.served_gaps(w, m, r.prompt, r.tokens)),
            ("control", lambda r: ref.control_gaps(
                w, m, r.prompt, r.tokens, lowp=LOWP)),
            ("control_all", lambda r: ref.control_gaps(
                w, m, r.prompt, r.tokens, lowp=LOWP, all_positions=True))):
        out[key] = max(float(np.max(fn(r))) for r in chosen)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = harness.load_cell(args.workload)
    for s in args.seeds.split(","):
        print(json.dumps(readings(spec, int(s), args.seconds)), flush=True)


if __name__ == "__main__":
    main()
