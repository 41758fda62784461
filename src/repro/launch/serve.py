"""Serving launcher: loads (or inits) a model and runs a batch of requests
through the continuous-batching slot engine (or the legacy bucket engine).

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --smoke \
      --requests 8 --prompt-lens 8,12,16 --max-new 16

Tensor-parallel serving (N-way "model" mesh; on CPU force N host devices):

  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --smoke \
      --requests 8 --mesh 2 --prefill-chunk 16

Telemetry (serving/telemetry.py; slot engine only, host-side, zero extra
device work): ``--metrics-out metrics.json`` writes the metrics registry
(``.prom`` extension switches to Prometheus text exposition),
``--trace-out trace.json`` writes the request-lifecycle span trace as
Chrome trace-event JSON — open https://ui.perfetto.dev and drag the file
in to see queued -> prefill -> first-token -> decode/spec-wave per
request next to the engine's per-tick phase lane. ``--stats-every N``
logs a one-line summary every N ticks; ``--xla-profile DIR`` addition-
ally records a jax.profiler device trace (a profiler that fails to start
is an error):

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --smoke \
      --requests 8 --trace-out /tmp/serve_trace.json \
      --metrics-out /tmp/serve_metrics.json --stats-every 8

Run as a module, the launcher keeps XLA's persistent compilation cache in
``$JAX_COMPILATION_CACHE_DIR`` or, unset, in ``.jax_cache/`` at the root
of the checkout (launch/compile_cache.py).
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config, smoke_config
from repro.models import get_model
from repro.serving import BucketEngine, ServeEngine
from repro.train import checkpoint as C

log = logging.getLogger("repro.serve")


def _flush_telemetry(args, telemetry):
    """Write --metrics-out / --trace-out. Called from a finally so an
    interrupted or crashed run still leaves parseable files behind."""
    if telemetry is None:
        return
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            if args.metrics_out.endswith(".prom"):
                f.write(telemetry.metrics_prometheus())
            else:
                f.write(telemetry.metrics_json(indent=2) + "\n")
        log.info("wrote metrics to %s", args.metrics_out)
    if args.trace_out:
        import json
        with open(args.trace_out, "w") as f:
            json.dump(telemetry.chrome_trace(), f)
        log.info("wrote Perfetto-loadable trace to %s", args.trace_out)


def _serve_http(args, eng, telemetry):
    """--http mode: hand the engine to the SSE front door and block until
    Ctrl-C. Telemetry flushes on the way out like the batch drive."""
    from repro.launch.server import FrontDoor
    fd = FrontDoor(eng, host=args.http_host, port=args.http,
                   queue_limit=args.queue_limit)
    fd.start()
    log.info("serving on http://%s:%d (POST /v1/generate, GET /healthz, "
             "GET /metrics); Ctrl-C to stop", fd.host, fd.port)
    try:
        while True:
            time.sleep(1.0)
            if args.stats_every and telemetry is not None:
                log.info("%s", telemetry.summary_line())
    except KeyboardInterrupt:
        log.info("shutting down")
    finally:
        fd.close()
        _flush_telemetry(args, telemetry)
    return dict(eng.results)


def init_params(api, mesh):
    """Random init as one jitted program. At full width eager init
    dispatches every op of every layer separately; under a mesh the
    params land sharded, never whole on the first device."""
    if mesh is None:
        return jax.jit(api.init)(jax.random.PRNGKey(0))
    from repro.launch import specs
    _, p_sh = specs.param_shardings(api, mesh,
                                    specs.mesh_rules_for(api.cfg, mesh))
    return jax.jit(api.init, out_shardings=p_sh)(jax.random.PRNGKey(0))


def build(argv=None):
    """Parse the command line and construct the engine.

    Returns ``(args, cfg, eng, telemetry)``; ``drive`` serves with them.
    Split from ``main`` so a caller can inspect the engine it built (the
    chip smoke test reads per-device residency before serving)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="stablelm-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--engine", choices=["slot", "bucket"], default="slot")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", default="16",
                    help="comma list; each request draws one uniformly")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "xla_ref", "xla_blockwise",
                             "pallas_flash"],
                    help="attention backend override (see nn/attention.py)")
    ap.add_argument("--kv-cache", default=None,
                    choices=["auto", "bf16", "int8", "binary"],
                    help="KV-cache codec override (see serving/kvcache.py)")
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="paged KV pool block size in tokens (0 = slot-"
                         "contiguous pool; slot engine only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the paged pool "
                         "(requires --kv-block-size > 0)")
    ap.add_argument("--stop-tokens", default="",
                    help="comma list of token ids that end generation "
                         "early (EOS-style; slot engine only)")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per wave "
                         "through the binarized self-draft and verify "
                         "them in one float pass (0 = off; slot engine, "
                         "GQA archs only)")
    ap.add_argument("--spec-draft-impl", default=None,
                    choices=["auto", "xla_xnor", "int8_mxu", "pallas_xnor"],
                    help="packed-matmul lowering for the binary draft "
                         "(kernels/ops.py SPEC_DRAFT_IMPLS; auto = XLA "
                         "XNOR twin; pallas_xnor runs in interpret mode "
                         "only; int8_mxu = +-1 int8 dot_general). All "
                         "lowerings are exact-int32 twins: tokens never "
                         "change")
    ap.add_argument("--draft", default="binary",
                    choices=["binary", "none"],
                    help="speculative draft model: 'binary' = the served "
                         "weights with sign-packed absmean-scaled MLPs "
                         "(serving/spec.py); 'none' disables speculation "
                         "even with --spec-decode set")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine sampling seed (temperature > 0)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="tensor-parallel serving over an N-way 'model' "
                         "mesh: attention heads + MLP hidden + the KV "
                         "pool's head axis shard across N devices (0 = "
                         "single device). Needs N visible devices — on "
                         "CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before launch")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="C",
                    help="blockwise prefill: scan C-token chunks through "
                         "the verify path so long-context prefill holds "
                         "O(batch*C) activations (0 = monolithic; power "
                         "of two; slot engine, GQA archs only)")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write the telemetry metrics registry here at "
                         "exit: JSON by default, Prometheus text "
                         "exposition when PATH ends in .prom (slot "
                         "engine only)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write the request-lifecycle span trace here as "
                         "Chrome trace-event JSON (load in Perfetto; "
                         "slot engine only)")
    ap.add_argument("--xla-profile", default="", metavar="DIR",
                    help="also record a jax.profiler device trace into "
                         "DIR (TensorBoard/Perfetto readable); a "
                         "profiler that fails to start is an error")
    ap.add_argument("--stats-every", type=int, default=0, metavar="N",
                    help="log a one-line telemetry summary every N "
                         "engine ticks (0 = off; slot engine only)")
    ap.add_argument("--interleave", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="interleaved prefill: run one decode-tick-sized "
                         "prefill slice per tick beside the decode batch "
                         "instead of blocking whole waves (slot engine, "
                         "GQA archs; default on in --http mode)")
    ap.add_argument("--scheduler", default="fifo", choices=["fifo", "slo"],
                    help="admission policy: fifo, or slo (SLO-class-aware "
                         "with a hard starvation bound; classes: "
                         "interactive > standard > batch)")
    ap.add_argument("--http", type=int, default=0, metavar="PORT",
                    help="serve over HTTP/SSE instead of a synthetic "
                         "batch: POST /v1/generate (per-token streaming "
                         "with \"stream\": true), GET /healthz, GET "
                         "/metrics. Runs until Ctrl-C. Port 0 picks a "
                         "free port (logged at startup)")
    ap.add_argument("--http-host", default="127.0.0.1",
                    help="bind address for --http")
    ap.add_argument("--queue-limit", type=int, default=64,
                    help="bounded HTTP admission queue: beyond this many "
                         "waiting submissions, POSTs answer 429 + "
                         "Retry-After (backpressure, not buffering)")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("whisper", "vlm"):
        ap.error(f"--arch {args.arch}: {cfg.family} needs audio/image "
                 "inputs; this text-only launcher cannot serve it")
    api = get_model(cfg)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        if jax.device_count() < args.mesh:
            ap.error(f"--mesh {args.mesh} needs {args.mesh} devices but "
                     f"only {jax.device_count()} are visible (on CPU set "
                     "XLA_FLAGS=--xla_force_host_platform_device_count="
                     f"{args.mesh})")
        mesh = make_mesh((args.mesh,), ("model",),
                         devices=jax.devices()[:args.mesh])
    params = init_params(api, mesh)
    if args.ckpt_dir:
        last = C.latest_step(args.ckpt_dir)
        if last is not None:
            state, _ = C.restore(args.ckpt_dir, last, {"params": params})
            params = state["params"]
            log.info("loaded checkpoint step %d", last)

    plens = [int(x) for x in args.prompt_lens.split(",")]
    max_len = max(plens) + args.max_new + 8 + args.spec_decode
    cls = ServeEngine if args.engine == "slot" else BucketEngine
    if cls is ServeEngine and api.cache_insert is None:
        log.warning("family %r has no slot-indexed cache insert; "
                    "falling back to the bucket engine", cfg.family)
        cls = BucketEngine
    stop = frozenset(int(x) for x in args.stop_tokens.split(",") if x)
    spec_k = args.spec_decode if args.draft != "none" else 0
    telemetry = None
    if args.metrics_out or args.trace_out or args.stats_every \
            or args.xla_profile or args.http:
        from repro.serving.telemetry import Telemetry
        telemetry = Telemetry()
    # interleaved prefill defaults on behind the HTTP front door (ITL of
    # streaming clients is what it protects) and off for the synthetic
    # batch drive; archs without the slice seam fall back with a warning
    # unless the flag was explicit
    interleave = (bool(args.http) if args.interleave is None
                  else args.interleave)
    if interleave and api.prefill_slice is None \
            and args.interleave is None:
        log.warning("family %r has no prefill slice step; running "
                    "blocking prefill waves", cfg.family)
        interleave = False
    if cls is ServeEngine:
        eng = cls(api, params, max_batch=args.max_batch, max_len=max_len,
                  temperature=args.temperature, seed=args.seed,
                  attn_impl=args.attn_impl, kv_cache=args.kv_cache,
                  kv_block_size=args.kv_block_size,
                  prefix_cache=args.prefix_cache,
                  spec_k=spec_k, spec_draft="binary",
                  spec_draft_impl=args.spec_draft_impl, mesh=mesh,
                  prefill_chunk=args.prefill_chunk, telemetry=telemetry,
                  interleave=interleave, scheduler=args.scheduler)
    else:
        if args.kv_block_size or args.prefix_cache or stop or spec_k \
                or args.prefill_chunk or args.http or interleave \
                or args.scheduler != "fifo":
            ap.error("--kv-block-size/--prefix-cache/--stop-tokens/"
                     "--spec-decode/--prefill-chunk/--http/--interleave/"
                     "--scheduler slo need the slot engine")
        if telemetry is not None:
            ap.error("--metrics-out/--trace-out/--xla-profile/"
                     "--stats-every need the slot engine")
        eng = cls(api, params, max_batch=args.max_batch, max_len=max_len,
                  temperature=args.temperature, seed=args.seed,
                  attn_impl=args.attn_impl, kv_cache=args.kv_cache,
                  mesh=mesh)
    return args, cfg, eng, telemetry


def drive(args, cfg, eng, telemetry):
    """Serve over --http, or run the synthetic --requests batch to
    completion; returns {request id: generated tokens}."""
    if args.http:
        return _serve_http(args, eng, telemetry)
    plens = [int(x) for x in args.prompt_lens.split(",")]
    stop = frozenset(int(x) for x in args.stop_tokens.split(",") if x)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.choice(plens))
        prompt = rng.integers(0, cfg.vocab, plen)
        if isinstance(eng, ServeEngine):
            eng.add_request(prompt, max_new=args.max_new, stop_tokens=stop)
        else:
            eng.add_request(prompt, max_new=args.max_new)
    if args.xla_profile:
        from repro.serving.telemetry import start_xla_profiler
        start_xla_profiler(args.xla_profile)
    t0 = time.time()
    # the flush lives in a finally: a Ctrl-C (or a mid-run engine error)
    # must still leave parseable --metrics-out/--trace-out files behind —
    # a partial trace of a crashed run is exactly when you want the trace
    try:
        if args.stats_every:
            ticks = 0
            while eng.step():
                ticks += 1
                if ticks % args.stats_every == 0:
                    log.info("tick %d: %s", ticks,
                             telemetry.summary_line())
        else:
            eng.run()
    except KeyboardInterrupt:
        log.warning("interrupted; flushing telemetry for the partial run")
    finally:
        results = dict(eng.results)
        dt = time.time() - t0
        if args.xla_profile:
            from repro.serving.telemetry import stop_xla_profiler
            stop_xla_profiler()
            log.info("wrote jax.profiler device trace to %s",
                     args.xla_profile)
        _flush_telemetry(args, telemetry)
    toks = sum(len(v) for v in results.values())
    log.info("served %d requests, %d tokens in %.2fs (%.1f tok/s)",
             len(results), toks, dt, toks / max(dt, 1e-9))
    if isinstance(eng, ServeEngine):
        log.info("slot utilization %.1f%%, stats %s",
                 eng.utilization() * 100, eng.stats)
        if eng.spec_k:
            log.info("speculative: k=%d, acceptance %.1f%% "
                     "(%d/%d drafts), %d waves",
                     eng.spec_k, eng.acceptance_rate() * 100,
                     eng.stats["spec_accepted"], eng.stats["spec_drafted"],
                     eng.stats["spec_waves"])
    for rid in sorted(results)[:4]:
        log.info("request %d -> %s", rid, results[rid])
    return results


def main(argv=None):
    return drive(*build(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
