"""Find an online cell's knee: the highest Poisson rate it sustains.

    python bench/sweep.py --workload <name> --rates 0.2,0.4,0.6 \
        --seeds 1,2 --seconds 51

One process: the cell's weights and engine are built and warmed once;
then for each rate, in the order given, and each seed, the cell's traffic
at that rate runs for ``--seconds`` and the engine is drained. One JSON
line per rate reports the requests due, the queue left at each close,
and the TTFT percentiles over all of its seeds' requests (a request with
no first token by the close counts with its wait so far). As DistServe
(Zhong et al., OSDI 2024) scales an SLO from the latency the system
gives without load, a rate is sustained when its TTFT p50 is within
``--slo-scale`` (default 2) times the p50 at the first, lightest rate and
no queue at a close holds more than max_batch requests. The median, not
a tail: a light run holds few requests, and its tail depends on whether
the longest prompts fall in it. ``attainment`` is the share of requests
within scale x the lightest rate's p90, for reference. The cell's traffic
file then takes 0.8 x the highest sustained rate, written as a number.
The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--slo-scale", type=float, default=2.0)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    params, eng, tm, m = harness.build(spec, seeds[0])
    harness.warm_up(eng, spec, seeds[0])
    base = None
    for rate in (float(x) for x in args.rates.split(",")):
        s = dict(spec, traffic=dict(spec["traffic"], arrivals={
            "kind": "poisson", "rate_rps": rate}))
        ttft, left = [], []
        for seed in seeds:
            run = harness.Run(model=m, cell=spec["cell"], peaks={},
                              max_batch=eng.max_batch)
            harness.window(eng, s, seed, args.seconds, run, False)
            left.append(len(eng.queue))
            ttft += [r.times[0] - r.due if r.times and r.times[0] <= run.t1
                     else run.t1 - r.due for r in run.reqs]
            eng.run()
        p50, p90 = (float(np.percentile(ttft, q)) for q in (50, 90))
        base = (p50, p90) if base is None else base
        print(json.dumps({
            "rate_rps": rate, "due": len(ttft), "queue_at_close": left,
            "ttft_p50_s": p50, "ttft_p90_s": p90,
            "attainment": float(np.mean(
                np.asarray(ttft) <= args.slo_scale * base[1])),
            "sustained": p50 <= args.slo_scale * base[0]
            and max(left) <= eng.max_batch}), flush=True)


if __name__ == "__main__":
    main()
