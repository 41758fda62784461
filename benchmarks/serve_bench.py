"""Serving throughput: continuous-batching slot engine vs the seed
run-to-completion bucket engine on the same mixed-length workload.

The workload is a Poisson arrival stream (arrival unit = one decode step)
of requests with mixed prompt lengths and mixed max_new. The bucket engine
gets the *easier* job — every request enqueued up front — and still loses:
it only batches exact-equal prompt lengths, runs each group until its
slowest member finishes, and recompiles decode for every distinct group
size. The slot engine decodes the full fixed pool every step and swaps
finished requests for queued ones between steps.

    PYTHONPATH=src python benchmarks/serve_bench.py
    PYTHONPATH=src python benchmarks/serve_bench.py --requests 32 --max-batch 8
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import smoke_config
from repro.models import get_model
from repro.serving import BucketEngine, ServeEngine, Telemetry
from repro.serving.scheduler import poisson_workload, prefix_workload


def bench_bucket(api, params, workload, *, max_batch, max_len):
    eng = BucketEngine(api, params, max_batch=max_batch, max_len=max_len)
    for _, prompt, max_new in workload:           # best case: all up front
        eng.add_request(prompt, max_new=max_new)
    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    toks = sum(len(v) for v in results.values())
    return results, toks, dt, None


def bench_slot(api, params, workload, *, max_batch, max_len,
               telemetry=None, **eng_kw):
    eng = ServeEngine(api, params, max_batch=max_batch, max_len=max_len,
                      telemetry=telemetry, **eng_kw)
    results, toks, dt = _drive(eng, workload)
    return results, toks, dt, eng


def _pct_rows(prefix, telemetry):
    """p50/p99 TTFT + inter-token-latency rows read from the engine's own
    telemetry registry (serving/telemetry.py) — the identical histograms
    a production metrics scrape sees, so the bench can no longer drift
    from what the serving stack actually measures."""
    rows = []
    for metric, hist in (("ttft", telemetry.ttft), ("itl", telemetry.itl)):
        if not hist.count:
            continue
        for q in (50, 99):
            v = hist.percentile(q)
            rows.append((f"{prefix}_{metric}_p{q}", v * 1e6,
                         f"{v * 1e3:.1f} ms"))
    return rows


def run(quick: bool = True, *, requests: int | None = None,
        max_batch: int | None = None, rate: float = 1.0, seed: int = 0,
        trace_out: str | None = None):
    requests = requests if requests is not None else (24 if quick else 64)
    max_batch = max_batch if max_batch is not None else (4 if quick else 8)
    cfg = smoke_config("stablelm-3b")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    max_len = 64
    workload = poisson_workload(
        requests, rate=rate, prompt_lens=(5, 8, 12, 16), max_new=(4, 16),
        vocab=cfg.vocab, seed=seed)

    _, btoks, bdt, _ = bench_bucket(api, params, workload,
                                    max_batch=max_batch, max_len=max_len)
    # cold blocking drive — the measurement that exposed the head-of-line
    # bug: whole-wave prefills (plus their first-hit XLA compiles) block
    # every co-resident decode tick, so slot_blocking_itl_p99 sits orders
    # of magnitude above p50
    tm_b = Telemetry()
    bres, sbtoks, sbdt, _ = bench_slot(api, params, workload,
                                       max_batch=max_batch,
                                       max_len=max_len, telemetry=tm_b)
    assert btoks == sbtoks, (btoks, sbtoks)
    # the headline serve/slot_* rows: interleaved prefill (one slice per
    # tick beside the decode batch), warmed first so the percentiles price
    # steady-state serving, not compilation
    tm = Telemetry()
    eng = ServeEngine(api, params, max_batch=max_batch, max_len=max_len,
                      telemetry=tm, interleave=True, prefill_chunk=8)
    _warm_slot(eng, cfg, plens=(5, 12), seed=seed + 10 ** 6)
    tm.reset()                 # drop warmup latencies; measured drive only
    res, stoks, sdt = _drive(eng, workload)
    # counts must match exactly; token *values* on this random-init smoke
    # model sit inside fp-reorder noise between the monolithic and sliced
    # prefill lowerings — the trained-model token-identity bar lives in
    # tests/test_interleave.py
    assert btoks == stoks, (btoks, stoks)
    assert [len(v) for v in bres.values()] == [len(v) for v in
                                               res.values()]
    rows = [
        ("serve/bucket_tok_s", bdt / btoks * 1e6, f"{btoks / bdt:.1f} tok/s"),
        ("serve/slot_blocking_tok_s", sbdt / sbtoks * 1e6,
         f"{sbtoks / sbdt:.1f} tok/s (cold, blocking waves)"),
        ("serve/slot_tok_s", sdt / stoks * 1e6, f"{stoks / sdt:.1f} tok/s"),
        ("serve/slot_util", 0.0, f"{eng.utilization() * 100:.1f}%"),
        ("serve/speedup", 0.0, f"{bdt / sdt:.2f}x"),
        # memory column next to throughput: the KV codec trade is invisible
        # without it (see benchmarks/kvcache_bench.py for the codec sweep)
        ("serve/slot_gen_tokens", 0.0, f"{stoks} tokens"),
        ("serve/slot_kv_bytes", 0.0,
         f"{eng.stats['kv_bytes'] / 1024:.1f} KiB resident"),
    ]
    rows += _pct_rows("serve/slot_blocking", tm_b)
    rows += _pct_rows("serve/slot", tm)
    if trace_out:
        # the Perfetto artifact CI uploads next to BENCH_serve.json: the
        # measured run's request-lifecycle spans, straight from the tracer
        import json
        with open(trace_out, "w") as f:
            json.dump(tm.chrome_trace(), f)
        print(f"# wrote {trace_out}", file=sys.stderr)
    rows += _burst_rows(api, params, cfg, max_batch=max_batch, seed=seed,
                        quick=quick)
    rows += _mesh_rows(quick, requests=requests, max_batch=max_batch,
                       rate=rate, seed=seed)
    return rows


def _warm_slot(eng, cfg, *, plens, seed):
    """Deterministically compile every variant a measured drive can hit:
    each admission group size (1, 2, ..., max_batch) x each prompt bucket
    ``plens`` touches — prefill/slice/install/decode all trace here, so
    the timed section holds zero first-hit XLA compiles."""
    rng = np.random.default_rng(seed)
    g = 1
    while g <= eng.max_batch:
        for plen in plens:
            for _ in range(g):
                eng.add_request(
                    rng.integers(0, cfg.vocab, plen).astype(np.int32),
                    max_new=4)
            eng.run()
        g *= 2


def _burst_rows(api, params, cfg, *, max_batch, seed, quick=True):
    """Prefill-heavy adversarial workload: long prompts (up to 128 tokens,
    vs a 16-token decode-tick budget) keep arriving while short requests
    decode. Both engines run *warmed* chunked prefill (chunk=16), so the
    pair isolates scheduling alone: blocking runs all chunks of a wave
    back-to-back before the next decode tick; interleaved runs one chunk
    per tick beside the decode batch. The ITL p99 gap between the two rows
    is the head-of-line blocking the tentpole removes."""
    requests = 12 if quick else 32
    max_len = 192
    wl = poisson_workload(requests, rate=0.4, prompt_lens=(8, 96, 128),
                          max_new=(12, 24), vocab=cfg.vocab, seed=seed)
    rows, ref, p99s = [], None, []
    for name, kw in (("burst_blocking", dict(prefill_chunk=16)),
                     ("burst", dict(interleave=True, prefill_chunk=16))):
        tm = Telemetry()
        eng = ServeEngine(api, params, max_batch=max_batch,
                          max_len=max_len, telemetry=tm, **kw)
        _warm_slot(eng, cfg, plens=(8, 100, 128), seed=seed + 10 ** 6)
        tm.reset()
        res, toks, dt = _drive(eng, wl)
        if ref is None:
            ref = res
        else:
            # same caveat as run(): count parity here, token-identity on
            # the trained model in tests/test_interleave.py
            assert [len(v) for v in ref.values()] == \
                [len(v) for v in res.values()], "burst token counts diverged"
        p99 = tm.itl.percentile(99)
        p99s.append(p99)
        rows.append((f"serve/{name}_itl_p99", p99 * 1e6,
                     f"{p99 * 1e3:.2f} ms ({toks / dt:.1f} tok/s)"))
    rows.append(("serve/burst_itl_gain", 0.0,
                 f"{p99s[0] / max(p99s[1], 1e-9):.1f}x lower p99 ITL"))
    return rows


def _mesh_rows(quick: bool = True, *, requests, max_batch, rate, seed,
               mesh: int = 2):
    """Tensor-parallel slot engine vs single-device, same workload, in
    this process over the first ``mesh`` visible devices (on CPU launch
    with XLA_FLAGS=--xla_force_host_platform_device_count=N). Raises when
    fewer are visible. Token identity is asserted; wall-clock is recorded
    honestly — on a CPU host the mesh row prices the collectives (virtual
    devices serialize), while on real multi-chip hosts the identical code
    path is where the speedup comes from."""
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < mesh:
        raise RuntimeError(
            f"serve/mesh{mesh} needs {mesh} devices, {len(devices)} "
            "visible (on CPU set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={mesh} before launch)")
    # f32 compute: random-init bf16 argmax gaps (~1e-3) sit below sharded-
    # matmul reduction-reorder noise, and the point of the identity assert
    # is the engine, not tie-breaking luck (tests/test_engine_parity.py
    # holds the trained-model token bar)
    cfg = smoke_config("stablelm-3b").replace(compute_dtype="float32")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    workload = poisson_workload(requests, rate=rate,
                                prompt_lens=(5, 8, 12, 16), max_new=(4, 16),
                                vocab=cfg.vocab, seed=seed)
    warmup = poisson_workload(max(4, max_batch), rate=rate,
                              prompt_lens=(5, 8, 12, 16), max_new=(4, 16),
                              vocab=cfg.vocab, seed=seed + 10 ** 6)
    rows, ref = [], None
    for name, m in (("1dev", None),
                    (f"mesh{mesh}", make_mesh((mesh,), ("model",),
                                              devices=devices[:mesh]))):
        tm = Telemetry()
        eng = ServeEngine(api, params, max_batch=max_batch, max_len=64,
                          mesh=m, telemetry=tm)
        # compile every prefill bucket + the decode step outside the timed
        # drive: GSPMD partitioning makes the mesh engine's compiles much
        # slower, and compile time is not what this row prices
        _drive(eng, warmup)
        tm.reset()           # drop warmup latencies; measured drive only
        res, toks, dt = _drive(eng, workload)
        if ref is None:
            ref = res
        else:
            assert list(res.values()) == list(ref.values()), \
                "mesh outputs diverged from single-device"
        rows.append((f"serve/{name}_tok_s", dt / toks * 1e6,
                     f"{toks / dt:.1f} tok/s"))
        rows += _pct_rows(f"serve/{name}", tm)
        rows.append((f"serve/{name}_kv_bytes_per_dev", 0.0,
                     f"{eng.stats['kv_bytes_per_device'] / 1024:.1f} KiB"))
    return rows


def _trained_smoke_lm(steps: int = 200):
    """Briefly trained f32 smoke LM (same recipe as tests/test_kvcache.py):
    a random-init model's greedy argmax gaps sit below fp-reorder noise, so
    token-identity claims only mean something once the model predicts with
    decisive margins."""
    from repro.configs.base import PrecisionPolicy
    from repro.data.synthetic import SyntheticTokens
    from repro.optim import adamw_init
    from repro.train.step import make_train_step

    cfg = smoke_config("stablelm-3b").replace(
        policy=PrecisionPolicy(), compute_dtype="float32",
        param_dtype="float32")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(api, cfg, peak_lr=1e-3, warmup=20,
                                   total=steps))
    import jax.numpy as jnp
    for _, batch in zip(range(steps), SyntheticTokens(cfg.vocab, 32, 16,
                                                      seed=0)):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, _ = step(params, opt, batch)
    return cfg, api, params


def _drive(eng, workload):
    """Feed a workload into an existing engine (arrival clock = decode
    steps) and time it; returns (results for these rids, tokens, dt).

    This used to hand-roll TTFT/ITL capture by diffing slot state after
    every tick; that measurement now lives where the requests do — the
    engine's telemetry (serving/telemetry.py) stamps arrival at
    add_request and token emissions inside each tick, so benchmarks and
    production read one source of truth. Construct the engine with
    ``telemetry=Telemetry()`` and read the ``serve_ttft_seconds`` /
    ``serve_itl_seconds`` histograms back via ``_pct_rows``. Throughput
    alone hides scheduling pathologies — a bucket engine can post decent
    tok/s while late arrivals starve behind a draining group — so the
    percentile columns ride next to tok/s in every serve row."""
    pending = sorted(workload, key=lambda w: w[0])
    base = eng.step_count
    rids = []
    t0 = time.time()
    while pending or eng.queue or any(s is not None for s in eng.slots):
        while pending and pending[0][0] <= eng.step_count - base:
            _, prompt, max_new = pending.pop(0)
            rids.append(eng.add_request(prompt, max_new=max_new))
        stepped = eng.step()
        if not stepped and pending:
            eng.step_count = max(eng.step_count + 1,
                                 base + pending[0][0])
    dt = time.time() - t0
    results = {r: eng.results[r] for r in rids}
    return results, sum(len(v) for v in results.values()), dt


def run_prefix(quick: bool = True, *, requests: int | None = None,
               max_batch: int | None = None, header_len: int = 256,
               block_size: int = 64, seed: int = 0):
    """Prefix-heavy serving: N Poisson-arriving prompts sharing a
    ``header_len``-token header (shared system prompt), short unique
    suffixes. Baseline = the slot-contiguous engine (re-prefills every
    prompt in full); contender = paged pool + radix prefix cache (prefills
    the header once, then only suffixes). Greedy outputs are asserted
    token-identical for both the bf16 and int8 codecs.

    Both engines are warmed with a same-shaped workload under a *different*
    header first (compiles every prefill/decode variant; publishes nothing
    reusable), so the timed section measures steady-state serving, not
    XLA compilation."""
    requests = requests if requests is not None else (8 if quick else 24)
    max_batch = max_batch if max_batch is not None else 4
    cfg, api, params = _trained_smoke_lm()
    max_len = header_len + 16 + 16 + 8

    def markov(rng, n):
        # in-distribution tokens (the affine-Markov training map), so the
        # trained model decodes with multi-logit argmax margins
        x = int(rng.integers(0, cfg.vocab))
        out = []
        for _ in range(n):
            out.append(x)
            x = (x * 7 + 13) % cfg.vocab
        return np.asarray(out, np.int32)

    def make_workload(s):
        # short decodes + arrival-per-step keep prefill (what the cache
        # removes) a visible share of the wall clock on the smoke model
        return prefix_workload(
            requests, header_len=header_len, suffix_lens=(8, 12, 16),
            rate=1.0, max_new=(4, 8), vocab=cfg.vocab, seed=s,
            token_source=markov)

    def warm(eng):
        # deterministically compile every variant the measured phase can
        # hit: each admission group size x {full-header prefill, every
        # suffix bucket}. Fresh headers per burst, so nothing the measured
        # workload's header needs is pre-published.
        rng = np.random.default_rng(10 ** 6 + seed)
        g = 1
        while g <= max_batch:
            for slen in (8, 12):               # suffix buckets 8 and 16
                hdr = markov(rng, header_len)
                for phase in range(2):         # cold burst, then cached
                    for _ in range(g):
                        eng.add_request(
                            np.concatenate([hdr, markov(rng, slen)]),
                            max_new=4)
                    eng.run()
            g *= 2

    measured = make_workload(seed)
    rows = []
    for codec in ("bf16", "int8"):
        beng = ServeEngine(api, params, max_batch=max_batch,
                           max_len=max_len, kv_cache=codec)
        peng = ServeEngine(api, params, max_batch=max_batch,
                           max_len=max_len, kv_cache=codec,
                           kv_block_size=block_size, prefix_cache=True)
        warm(beng)
        warm(peng)
        pf0_b = beng.stats["prefilled_tokens"]
        pf0_p = peng.stats["prefilled_tokens"]
        ct0_p = peng.stats["cached_prompt_tokens"]
        rb, btoks, bdt = _drive(beng, measured)
        rp, ptoks, pdt = _drive(peng, measured)
        assert list(rb.values()) == list(rp.values()), \
            f"prefix-cached {codec} outputs diverged"
        base_pf = beng.stats["prefilled_tokens"] - pf0_b
        cached_pf = peng.stats["prefilled_tokens"] - pf0_p
        cached_hits = peng.stats["cached_prompt_tokens"] - ct0_p
        rows += [
            (f"prefix/{codec}_prefilled_tokens", 0.0,
             f"{base_pf} -> {cached_pf} ({base_pf / cached_pf:.2f}x fewer)"),
            (f"prefix/{codec}_cached_tokens", 0.0,
             f"{cached_hits} from radix tree"),
            (f"prefix/{codec}_base_tok_s", bdt / btoks * 1e6,
             f"{btoks / bdt:.1f} tok/s"),
            (f"prefix/{codec}_cached_tok_s", pdt / ptoks * 1e6,
             f"{ptoks / pdt:.1f} tok/s ({bdt / pdt:.2f}x)"),
        ]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrivals per decode step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix", action="store_true",
                    help="run the prefix-cache workload instead")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    fn = run_prefix if args.prefix else run
    for n, us, derived in fn(requests=args.requests,
                             max_batch=args.max_batch,
                             **({} if args.prefix else
                                {"rate": args.rate}),
                             seed=args.seed):
        print(f"{n},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
