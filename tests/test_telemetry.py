"""Serving telemetry: instrument unit tests, the engine smoke path
(metrics JSON + Perfetto trace from a real run), the stats schema, and —
the load-bearing one — the overhead contract: telemetry on vs. off is
token-identical with an equal jitted-dispatch count."""

import json

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.serving import MetricsRegistry, ServeEngine, Telemetry, Tracer
from repro.serving import telemetry as T
from repro.serving.engine import STATS_SCHEMA


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config("stablelm-3b")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

def test_counter_monotonic():
    c = MetricsRegistry().counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_log_buckets_cover_range():
    bs = T.log_buckets(1e-3, 1.0)
    assert bs[0] == 1e-3
    assert bs[-1] >= 1.0
    assert all(b2 / b1 == pytest.approx(2.0) for b1, b2 in zip(bs, bs[1:]))
    with pytest.raises(ValueError):
        T.log_buckets(0.0, 1.0)


def test_histogram_percentile_matches_numpy():
    h = T.Histogram("h")
    rng = np.random.default_rng(0)
    xs = rng.exponential(0.01, size=257)
    for x in xs:
        h.observe(float(x))
    for q in (0, 25, 50, 99, 100):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q))
    assert h.mean() == pytest.approx(xs.mean())
    assert h.count == len(xs)


def test_histogram_empty_is_zero_not_crash():
    h = T.Histogram("h")
    assert h.percentile(50) == 0.0
    assert h.percentile(99) == 0.0
    assert h.mean() == 0.0


def test_histogram_bucket_counts_cumulative():
    h = T.Histogram("h", buckets=(0.1, 1.0, 10.0))
    for x in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(x)
    d = MetricsRegistry()
    d.histograms["h"] = h
    buckets = d.to_dict()["histograms"]["h"]["buckets"]
    assert buckets == {"0.1": 1, "1": 3, "10": 4, "+Inf": 5}


def test_registry_idempotent_and_reset():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    assert r.gauge("g") is r.gauge("g")
    assert r.histogram("h") is r.histogram("h")
    r.counter("a").inc(3)
    r.gauge("g").set(7)
    r.histogram("h").observe(0.5)
    h = r.histogram("h")       # handle taken before reset stays valid
    r.reset()
    assert r.counter("a").value == 0.0
    assert r.gauge("g").value == 0.0
    assert h.count == 0 and h.samples == [] and h.percentile(50) == 0.0


def test_prometheus_exposition_format():
    r = MetricsRegistry()
    r.counter("serve_requests_total", "requests").inc(4)
    r.gauge("kv_pool_bytes").set(1024)
    h = r.histogram("serve_ttft_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = r.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE serve_requests_total counter" in lines
    assert "serve_requests_total 4" in lines
    assert "kv_pool_bytes 1024" in lines
    assert 'serve_ttft_seconds_bucket{le="0.1"} 1' in lines
    assert 'serve_ttft_seconds_bucket{le="+Inf"} 2' in lines
    assert "serve_ttft_seconds_count 2" in lines
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_chrome_trace_schema():
    tr = Tracer(epoch=0.0)
    tr.name_request(3)
    tr.name_request(3)                       # idempotent
    tr.span("decode_tick", 1.0, 1.5, args={"n_active": 2})
    tr.instant("first_token", 1.2, tid=3)
    doc = tr.to_chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    # exactly one thread_name for rid 3 despite the double call
    assert sum(1 for e in evs if e["ph"] == "M"
               and e["name"] == "thread_name") == 1
    (span,) = [e for e in evs if e["ph"] == "X"]
    assert span["ts"] == pytest.approx(1.0e6)
    assert span["dur"] == pytest.approx(0.5e6)
    assert span["pid"] == T.ENGINE_PID
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert inst["pid"] == T.REQUEST_PID and inst["tid"] == 3
    json.loads(tr.to_json())                 # serializes cleanly
    tr.clear()                               # drops events, keeps metadata
    assert all(e["ph"] == "M" for e in tr.events)
    assert len(tr.events) == 3


def test_tokens_emitted_ttft_and_itl_convention():
    """First token closes TTFT; a k-token wave contributes k gaps of
    tick/k; extra tokens landing in the TTFT tick contribute 0.0 gaps —
    the exact convention of the bench capture the telemetry replaced."""
    tm = Telemetry()
    tm.request_added(0, prompt_len=4, now=10.0)
    tm.tokens_emitted(0, 3, now=10.5)        # first tick banks 3 tokens
    assert tm.ttft.samples == [pytest.approx(0.5)]
    assert tm.itl.samples == [0.0, 0.0]
    tm.tokens_emitted(0, 4, now=10.9)        # spec wave: 4 gaps of 0.1
    assert tm.itl.samples[2:] == [pytest.approx(0.1)] * 4
    assert tm.tokens.value == 7
    tm.tokens_emitted(0, 0, now=11.0)        # no-op
    tm.tokens_emitted(99, 1, now=11.0)       # unknown rid: no-op
    assert tm.tokens.value == 7
    tm.request_finished(0, "max_new", now=11.0)
    assert tm.finished.value == 1
    # lifecycle state dropped: a recycled rid starts a fresh TTFT
    assert 0 not in tm._arrive and 0 not in tm._emitted


def test_queue_wait_and_reset_keeps_inflight_state():
    tm = Telemetry()
    tm.request_added(5, prompt_len=8, now=1.0)
    tm.request_admitted(5, slot=0, prefilled_tokens=8, now=1.25)
    assert tm.queue_wait.samples == [pytest.approx(0.25)]
    tm.reset()
    assert tm.queue_wait.count == 0
    assert tm._arrive[5] == 1.0              # in-flight request survives
    tm.tokens_emitted(5, 1, now=2.0)
    assert tm.ttft.samples == [pytest.approx(1.0)]


# ---------------------------------------------------------------------------
# engine smoke: a real run produces a scrapeable registry + loadable trace
# ---------------------------------------------------------------------------

def test_engine_smoke_metrics_and_trace(model):
    cfg, api, params = model
    tm = Telemetry()
    eng = ServeEngine(api, params, max_batch=2, max_len=64,
                      kv_block_size=8, prefix_cache=True, telemetry=tm)
    # rid 0 and 1 fill both slots; rid 2 admits in a later wave and hits
    # the 8-token block the first wave published (shared 12-token prompt)
    rids = [eng.add_request(np.arange(12) % cfg.vocab, max_new=3),
            eng.add_request(np.arange(12) % cfg.vocab, max_new=6),
            eng.add_request(np.arange(12) % cfg.vocab, max_new=3)]
    results = eng.run()
    assert set(results) == set(rids)

    m = json.loads(tm.metrics_json())
    assert m["counters"]["serve_requests_total"] == 3
    assert m["counters"]["serve_finished_total"] == 3
    assert m["counters"]["serve_tokens_total"] == \
        eng.stats["generated_tokens"]
    assert m["histograms"]["serve_ttft_seconds"]["count"] == 3
    assert m["histograms"]["serve_queue_wait_seconds"]["count"] == 3
    # ITL gaps: every generated token after each request's first
    assert m["histograms"]["serve_itl_seconds"]["count"] == \
        eng.stats["generated_tokens"] - 3
    assert m["histograms"]["serve_decode_tick_seconds"]["count"] == \
        eng.stats["decode_steps"]
    assert m["histograms"]["serve_prefill_wave_seconds"]["count"] == \
        eng.stats["prefills"]
    assert m["gauges"]["kv_pool_bytes"] == eng.stats["kv_bytes"]
    assert m["gauges"]["kv_blocks_total"] == eng.n_blocks
    assert m["gauges"]["serve_slots_occupied"] == 0.0   # drained
    byte_roles = {k for k in m["gauges"] if k.startswith("kv_pool_")
                  and k.endswith("_bytes") and k != "kv_pool_bytes"}
    assert byte_roles >= {"kv_pool_values_bytes", "kv_pool_index_bytes"}
    # the two requests sharing a prompt hit the radix cache
    assert m["gauges"]["serve_prefix_hit_rate"] > 0.0
    tm.metrics_prometheus()                  # renders without crashing

    doc = tm.chrome_trace()
    json.dumps(doc)                          # Perfetto-loadable JSON
    evs = doc["traceEvents"]
    req_spans = [e for e in evs if e["ph"] == "X"
                 and e["pid"] == T.REQUEST_PID]
    assert {e["name"] for e in req_spans} == {"queued", "generate"}
    assert {e["tid"] for e in req_spans
            if e["name"] == "generate"} == set(rids)
    eng_spans = {e["name"] for e in evs if e["ph"] == "X"
                 and e["pid"] == T.ENGINE_PID}
    assert eng_spans == {"prefill_wave", "decode_tick"}
    firsts = [e for e in evs if e["ph"] == "i" and e["name"] == "first_token"]
    assert len(firsts) == 3
    assert all(e["dur"] >= 0.0 for e in req_spans)


def test_engine_smoke_interleave_slice_metrics(model):
    """Interleaved prefill books one prefill_slice span per slice (never a
    blocking prefill_wave) and exports the job gauge — the attribution
    surface the ITL audit hangs off."""
    cfg, api, params = model
    tm = Telemetry()
    eng = ServeEngine(api, params, max_batch=2, max_len=64,
                      interleave=True, prefill_chunk=4, telemetry=tm)
    eng.add_request(np.arange(12) % cfg.vocab, max_new=3)
    eng.add_request(np.arange(9) % cfg.vocab, max_new=3)
    eng.run()
    m = json.loads(tm.metrics_json())
    assert m["histograms"]["serve_prefill_wave_seconds"]["count"] == 0
    assert m["gauges"]["serve_prefill_jobs"] == 0.0       # drained
    evs = tm.chrome_trace()["traceEvents"]
    eng_spans = [e["name"] for e in evs if e["ph"] == "X"
                 and e["pid"] == T.ENGINE_PID]
    assert eng_spans.count("prefill_slice") == \
        eng.stats["prefill_slices"] > 0
    assert "prefill_wave" not in eng_spans


def test_engine_smoke_spec_wave_metrics(model):
    cfg, api, params = model
    tm = Telemetry()
    eng = ServeEngine(api, params, max_batch=2, max_len=64, spec_k=2,
                      telemetry=tm)
    eng.add_request(np.arange(6), max_new=6)
    eng.add_request(np.arange(6), max_new=6)
    eng.run()
    m = json.loads(tm.metrics_json())
    assert m["histograms"]["serve_spec_wave_seconds"]["count"] == \
        eng.stats["spec_waves"]
    assert m["histograms"]["serve_decode_tick_seconds"]["count"] == 0
    assert m["counters"]["serve_tokens_total"] == \
        eng.stats["generated_tokens"]
    assert m["gauges"]["serve_spec_acceptance"] == \
        pytest.approx(eng.acceptance_rate())
    waves = [e for e in tm.chrome_trace()["traceEvents"]
             if e["ph"] == "X" and e["name"] == "spec_wave"]
    assert len(waves) == eng.stats["spec_waves"]
    assert all(e["args"]["k"] == 2 for e in waves)


# ---------------------------------------------------------------------------
# engine phases on the profiler's clock, and named device scopes
# ---------------------------------------------------------------------------

PHASES = ("serve.admit", "serve.dispatch", "serve.sync", "serve.emit")


def _drive_small(eng, cfg):
    for p, mn in [(8, 5), (8, 7), (5, 3), (11, 4)]:
        eng.add_request(np.arange(p) % cfg.vocab, max_new=mn)


def test_engine_phases_nest_on_profiler_host_plane(model, tmp_path):
    """Under jax.profiler every tick is a serve.step span on the host
    plane, and the admit / dispatch / sync / emit phases lie inside one."""
    from jax.profiler import ProfileData

    cfg, api, params = model
    eng = ServeEngine(api, params, max_batch=2, max_len=64,
                      telemetry=Telemetry())
    _drive_small(eng, cfg)
    eng.step()                                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    steps = [(s, e) for n, s, e in spans if n == "serve.step"]
    assert len(steps) >= eng.stats["decode_steps"] - 1 > 0
    children = [(n, s, e) for n, s, e in spans if n != "serve.step"]
    assert {n for n, _, _ in children} == set(PHASES)
    for n, s, e in children:
        assert any(ps <= s and e <= pe for ps, pe in steps), n


def test_tick_host_seconds_counts_ticks_that_did_work(model):
    """serve_tick_host_seconds books one sample per tick that did work
    (step() returned True), each the tick less its serve.sync time."""
    cfg, api, params = model
    tm = Telemetry()
    eng = ServeEngine(api, params, max_batch=2, max_len=64, telemetry=tm)
    _drive_small(eng, cfg)
    worked = 0
    while eng.step():
        worked += 1
    assert eng.step() is False                 # an idle tick books nothing
    assert worked > 0
    assert tm.tick_host_s.count == worked
    assert all(x >= 0.0 for x in tm.tick_host_s.samples)
    m = json.loads(tm.metrics_json())
    assert m["histograms"]["serve_tick_host_seconds"]["count"] == worked


def test_decode_hlo_carries_device_scopes(model):
    """The decode step's op metadata names the KV pool, the attention and
    the weights' sign-and-pack, and its module is named serve_decode."""
    cfg, api, params = model
    assert cfg.policy.binary_ffn
    eng = ServeEngine(api, params, max_batch=2, max_len=64)
    text = eng._decode.lower(eng.params, eng.caches,
                             jax.numpy.asarray(eng.next_tok)).as_text(
                                 debug_info=True)
    assert "@jit_serve_decode" in text
    for scope in ("kv_pool", "attn", "ffn_pack"):
        assert f"/{scope}/" in text, scope


# ---------------------------------------------------------------------------
# the overhead contract: token identity + equal jitted-dispatch count
# ---------------------------------------------------------------------------

# every jitted callable the engine may hold; wrapping these counts exactly
# the device dispatches a tick performs (telemetry must add none).
# _job_init is host code that invokes the per-group-size jitted slice-cache
# allocator, so wrapping it counts those dispatches too.
_JITTED = ("_decode", "_prefill", "_insert", "_insert_pages",
           "_update_slots", "_gather_ctx", "_prefill_ctx", "_sample_rows",
           "_spec_wave", "_set_lens", "_slice", "_slice_finish",
           "_job_init")


def _count_dispatches(eng):
    counts = {}
    for name in _JITTED:
        fn = getattr(eng, name, None)
        if fn is None:
            continue
        counts[name] = 0

        def shim(*args, _fn=fn, _name=name):
            counts[_name] += 1
            return _fn(*args)

        setattr(eng, name, shim)
    return counts


@pytest.mark.parametrize("kw", [
    {},                                                  # contiguous
    {"kv_block_size": 8, "prefix_cache": True},          # paged + radix
    {"spec_k": 2},                                       # speculative
    {"interleave": True, "prefill_chunk": 4},            # sliced prefill
], ids=["contig", "paged_prefix", "spec", "interleave"])
def test_zero_sync_token_identity_and_dispatch_count(model, kw):
    """The acceptance criterion: with telemetry on, every request's tokens
    are identical to the telemetry-off run AND the engine launches exactly
    the same number of jitted calls — telemetry adds zero device work."""
    cfg, api, params = model

    def drive(telemetry):
        eng = ServeEngine(api, params, max_batch=2, max_len=64,
                          temperature=0.7, seed=11, telemetry=telemetry,
                          **kw)
        counts = _count_dispatches(eng)
        specs = [(8, 5), (8, 7), (5, 3), (11, 4)]
        rids = [eng.add_request(np.arange(p) % cfg.vocab, max_new=mn)
                for p, mn in specs]
        results = eng.run()
        return {rid: results[rid] for rid in rids}, counts

    toks_off, n_off = drive(None)
    toks_on, n_on = drive(Telemetry())
    assert toks_on == toks_off
    assert n_on == n_off
    assert sum(n_on.values()) > 0


# ---------------------------------------------------------------------------
# stats schema
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {},
    {"kv_block_size": 8, "prefix_cache": True},
    {"spec_k": 2},
    {"interleave": True, "prefill_chunk": 4},
], ids=["contig", "paged_prefix", "spec", "interleave"])
def test_stats_schema_exact(model, kw):
    """Every documented stats key exists with the documented type and no
    undocumented key ships — the schema is the contract dashboards and
    BENCH parsing hang off."""
    cfg, api, params = model
    eng = ServeEngine(api, params, max_batch=2, max_len=64, **kw)
    eng.add_request(np.arange(8), max_new=3)
    eng.run()
    assert set(eng.stats) == set(STATS_SCHEMA)
    for key, (typ, doc) in STATS_SCHEMA.items():
        assert isinstance(eng.stats[key], typ), \
            f"stats[{key!r}] = {eng.stats[key]!r} is not {typ.__name__}"
        assert doc                              # every key is documented
    # single device: the pool is unsharded
    assert eng.stats["kv_bytes_per_device"] == eng.stats["kv_bytes"]


@pytest.mark.slow
def test_kv_bytes_per_device_shards_on_mesh(run_forced_devices):
    """On an N-way model mesh the per-device stat must multiply back to
    the whole pool: kv_bytes_per_device * mesh_size == kv_bytes."""
    out = run_forced_devices("""
        import json

        import jax
        import numpy as np

        from repro.configs import smoke_config
        from repro.launch.mesh import make_mesh
        from repro.models import get_model
        from repro.serving import ServeEngine, Telemetry

        cfg = smoke_config("stablelm-3b")
        api = get_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        mesh = make_mesh((2,), ("model",))
        tm = Telemetry()
        eng = ServeEngine(api, params, max_batch=2, max_len=64, mesh=mesh,
                          telemetry=tm)
        eng.add_request(np.arange(8), max_new=2)
        eng.run()
        m = json.loads(tm.metrics_json())
        print("RESULT:" + json.dumps({
            "kv_bytes": eng.stats["kv_bytes"],
            "per_device": eng.stats["kv_bytes_per_device"],
            "gauge_total": m["gauges"]["kv_pool_bytes"],
            "gauge_per_device": m["gauges"]["kv_pool_bytes_per_device"],
            "devices": jax.device_count()}))
    """, n_devices=2)
    assert out["devices"] == 2
    assert out["per_device"] * 2 == out["kv_bytes"]
    assert out["gauge_per_device"] * 2 == out["gauge_total"]


# ---------------------------------------------------------------------------
# zero-division guards
# ---------------------------------------------------------------------------

def test_ratios_guarded_before_first_tick(model):
    """A metrics scrape (or stats read) on a fresh engine must read 0.0
    everywhere a ratio lives — never raise ZeroDivisionError."""
    cfg, api, params = model
    tm = Telemetry()
    eng = ServeEngine(api, params, max_batch=2, max_len=64, spec_k=2,
                      kv_block_size=8, prefix_cache=True, telemetry=tm)
    assert eng.acceptance_rate() == 0.0
    assert eng.utilization() == 0.0
    g = eng._telemetry_gauges()
    assert g["serve_slot_occupancy"] == 0.0
    assert g["serve_prefix_hit_rate"] == 0.0
    assert g["serve_spec_acceptance"] == 0.0
    m = json.loads(tm.metrics_json())        # scrape before any tick
    assert m["histograms"]["serve_ttft_seconds"]["p50"] == 0.0
    assert "ttft_p50=0.0ms" in tm.summary_line()


# ---------------------------------------------------------------------------
# device-profiler hook: an explicit request that fails is an error
# ---------------------------------------------------------------------------

def test_xla_profiler_warns_once_and_keeps_serving(monkeypatch):
    """--xla-profile was asked for: a profiler that cannot start raises
    (every time, not once) instead of warning and serving on without the
    device trace."""
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("no profiler on this backend")))
    for _ in range(2):
        with pytest.raises(RuntimeError, match="no profiler"):
            T.start_xla_profiler("/tmp/nowhere")
