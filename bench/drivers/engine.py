"""Driver ``engine``: the window drives ``ServeEngine.add_request`` and
the ``ServeEngine.step`` loop in this process, open loop on the wall
clock (the loop that the HTTP front door's engine thread runs). A cell
names its driver in ``bench/cells/<workload>.json`` (default this one);
another entry, such as the front door, is another file beside this one
with the same ``window``."""

from __future__ import annotations

import time

import numpy as np

from bench import loadgen
from bench.harness import Req, annotate


def window(eng, spec: dict, seed: int, seconds: float, run, trace: bool):
    """Drive for ``seconds``: add what falls due, else step."""
    from repro.serving.scheduler import AdmissionError
    mix = spec["traffic"]
    items = loadgen.schedule(mix, seed, seconds)
    arr = mix["arrivals"]
    depth = (arr.get("depth_per_slot", 0) * eng.max_batch
             if arr["kind"] == "backlog" else 0)
    vocab = eng.api.cfg.vocab
    i = 0
    t0 = time.perf_counter()
    run.t0 = t0
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        with annotate("bench.add_request", trace):
            while i < len(items) and (
                    (depth and len(eng.queue) < depth) or
                    (not depth and items[i].due <= now - t0)):
                it = items[i]
                r = Req(i, now if depth else t0 + it.due,
                        loadgen.request_prompt(mix, seed, i, it, vocab),
                        it.max_new)
                r.added = time.perf_counter()
                try:
                    r.rid = eng.add_request(r.prompt, max_new=r.max_new,
                                            stream=r.stream)
                except AdmissionError:
                    r.failed = True
                run.reqs.append(r)
                i += 1
            if depth and i == len(items):
                raise RuntimeError("backlog ran out: raise 'requests'")
        with annotate("bench.step", trace):
            busy = eng.step()
        if not busy:
            nxt = items[i].due if i < len(items) else seconds
            with annotate("bench.sleep", trace):
                time.sleep(max(0.0, min(nxt, seconds) - (time.perf_counter()
                                                          - t0)))
    run.t1 = time.perf_counter()
    # due in the window but never handed over (the last tick ran past
    # their due time): they count as attempted, with their wait so far
    for j in range(i, len(items)):
        if items[j].due is not None and items[j].due < seconds:
            run.reqs.append(Req(j, t0 + items[j].due,
                                np.zeros((0,), np.int32), items[j].max_new))
