"""Interleaved prefill: token parity with blocking waves, SLO-aware
admission, structured rejection, and the head-of-line latency bound.

The tentpole claim under test: slicing every prefill into decode-tick-
sized chunks and co-scheduling one slice per tick with the decode batch
changes WHEN admission work runs, never WHAT any request decodes. The
parity matrix holds the interleaved engine token-identical to the
blocking engine across {bf16, int8} x {contiguous, paged+prefix} x
{plain, speculative} on the session-trained smoke LM (greedy margins of
several logits — see tests/conftest.py).
"""

import time

import numpy as np
import pytest

from repro.serving import ServeEngine, Telemetry
from repro.serving.scheduler import (AdmissionError, FifoScheduler, Request,
                                     SloScheduler, make_buckets)


def _markov(start, n, vocab):
    out, x = [], start
    for _ in range(n):
        out.append(x)
        x = (x * 7 + 13) % vocab
    return np.asarray(out, np.int32)


def _outputs(api, params, prompts, *, temperature=0.0, **kw):
    eng = ServeEngine(api, params, max_batch=2, max_len=64,
                      temperature=temperature, seed=11, **kw)
    rids = [eng.add_request(p, max_new=8) for p in prompts]
    res = eng.run()
    return [res[r] for r in rids]


@pytest.fixture(scope="module")
def prompts(trained_lm):
    cfg, _, _ = trained_lm
    # mixed lengths force padded buckets, multi-slice jobs (bucket 16 at
    # chunk 4 = 4 slices), and multi-wave admission through max_batch=2
    return [_markov(3 + i, 7 + (i % 4), cfg.vocab) for i in range(5)]


@pytest.mark.parametrize("spec", [0, 3], ids=["plain", "spec"])
@pytest.mark.parametrize("pool", ["contiguous", "paged"])
@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_interleave_parity_matrix(trained_lm, prompts, codec, pool, spec):
    """Interleaved == blocking, token for token, against the *monolithic*
    blocking engine (so the comparison spans both the slicing and the
    chunked lowering)."""
    cfg, api, params = trained_lm
    kw = dict(kv_cache=codec, spec_k=spec,
              kv_block_size=8 if pool == "paged" else 0,
              prefix_cache=pool == "paged")
    ref = _outputs(api, params, prompts, **kw)
    got = _outputs(api, params, prompts, interleave=True, prefill_chunk=4,
                   **kw)
    assert got == ref, (codec, pool, spec)


def test_interleave_sampled_parity(trained_lm, prompts):
    """Per-request RNG streams make sampled outputs a function of
    (params, prompt, seed, rid) only — co-scheduling must not shift them."""
    cfg, api, params = trained_lm
    ref = _outputs(api, params, prompts, temperature=0.8)
    got = _outputs(api, params, prompts, temperature=0.8, interleave=True,
                   prefill_chunk=4)
    assert got == ref


def test_slo_scheduler_degenerates_to_fifo(trained_lm, prompts):
    """With every request in one class the SLO scheduler anchors on the
    queue head and fills in queue order — FifoScheduler exactly, so the
    parity matrix stays valid under scheduler='slo' defaults."""
    cfg, api, params = trained_lm
    ref = _outputs(api, params, prompts)
    got = _outputs(api, params, prompts, interleave=True, prefill_chunk=4,
                   scheduler="slo")
    assert got == ref


# -- structured admission rejection -----------------------------------------

def test_overlong_prompt_rejected_not_fatal(trained_lm):
    """An over-long prompt used to detonate ``bucket_len`` inside the tick
    loop, taking every co-resident request down with it. Now it raises a
    structured AdmissionError at add_request and the engine keeps
    serving."""
    cfg, api, params = trained_lm
    eng = ServeEngine(api, params, max_batch=2, max_len=64)
    with pytest.raises(AdmissionError) as ei:
        eng.add_request(_markov(3, 65, cfg.vocab), max_new=4)
    assert ei.value.code == "prompt_too_long"
    body = ei.value.to_dict()["error"]
    assert body["code"] == "prompt_too_long"
    assert body["detail"]["limit"] == 64
    # a ValueError subclass: pre-existing call sites keep passing
    assert isinstance(ei.value, ValueError)
    # the engine survives the rejection and serves the next request
    rid = eng.add_request(_markov(3, 8, cfg.vocab), max_new=4)
    assert len(eng.run()[rid]) == 4


@pytest.mark.parametrize("kwargs,code", [
    (dict(prompt_len=0, max_new=4), "empty_prompt"),
    (dict(prompt_len=8, max_new=0), "bad_max_new"),
    (dict(prompt_len=8, max_new=4, slo="platinum"), "bad_slo"),
    (dict(prompt_len=80, max_new=4), "prompt_too_long"),
    (dict(prompt_len=60, max_new=16), "too_long"),
])
def test_check_request_codes(trained_lm, kwargs, code):
    cfg, api, params = trained_lm
    eng = ServeEngine(api, params, max_batch=2, max_len=64)
    with pytest.raises(AdmissionError) as ei:
        eng.check_request(**kwargs)
    assert ei.value.code == code


def test_spec_headroom_in_admission(trained_lm):
    """spec_k scratch K/V tightens the length budget; the error says so."""
    cfg, api, params = trained_lm
    eng = ServeEngine(api, params, max_batch=2, max_len=64, spec_k=3)
    eng.check_request(40, 20)                 # 40+20+3 <= 64? no: 63 <= 64
    with pytest.raises(AdmissionError) as ei:
        eng.check_request(42, 20)             # 42+20+3 = 65 > 64
    assert ei.value.code == "too_long"
    assert ei.value.detail["spec_k"] == 3


# -- SLO scheduler policy (pure python) --------------------------------------

def _req(rid, plen, slo, arrival):
    return Request(rid, np.zeros(plen, np.int32), 4, slo=slo,
                   arrival=arrival)


def test_slo_priority_order():
    buckets = make_buckets(64)
    s = SloScheduler(buckets)
    q = [_req(0, 8, "batch", 0), _req(1, 8, "standard", 1),
         _req(2, 8, "interactive", 2)]
    group = s.select(q, n_free=2, clock=3)
    assert [r.rid for r in group] == [2, 1]


def test_slo_starvation_bound():
    """Once the queue head has waited past starvation_limit ticks it
    anchors the group no matter its class — absolute, not probabilistic."""
    buckets = make_buckets(64)
    s = SloScheduler(buckets, starvation_limit=4)
    q = [_req(0, 8, "batch", 0)] + \
        [_req(i, 8, "interactive", i) for i in range(1, 6)]
    # inside the limit: interactive jumps the batch head
    assert s.select(q, 1, clock=4)[0].rid == 1
    # past the limit: the starved head anchors and survives truncation
    assert s.select(q, 1, clock=5)[0].rid == 0


def test_slo_fifo_equivalence_single_class():
    buckets = make_buckets(64)
    fifo, slo = FifoScheduler(buckets), SloScheduler(buckets)
    rng = np.random.default_rng(0)
    for trial in range(20):
        q = [_req(i, int(rng.choice([5, 8, 12, 16])), "standard", i)
             for i in range(8)]
        n = int(rng.integers(1, 9))
        assert ([r.rid for r in slo.select(q, n, clock=trial)]
                == [r.rid for r in fifo.select(q, n)])


def test_slo_scheduler_validation(trained_lm):
    cfg, api, params = trained_lm
    with pytest.raises(ValueError, match="starvation_limit"):
        ServeEngine(api, params, max_batch=2, max_len=64, scheduler="slo",
                    starvation_limit=0)
    with pytest.raises(ValueError, match="unknown scheduler"):
        ServeEngine(api, params, max_batch=2, max_len=64, scheduler="edf")


# -- head-of-line bound under a prefill-heavy workload -----------------------

def _victim_gaps(api, params, cfg, *, interleave):
    """One short request decodes while 96-token prompts keep arriving;
    returns (max observed inter-token wall gap of the victim, engine,
    telemetry). Both modes run warmed chunked prefill (chunk=16) so the
    pair isolates scheduling, not compilation or chunking."""
    tm = Telemetry()
    eng = ServeEngine(api, params, max_batch=2, max_len=160,
                      prefill_chunk=16, interleave=interleave,
                      telemetry=tm)
    for plen in (8, 96):                       # compile both buckets
        eng.add_request(_markov(5, plen, cfg.vocab), max_new=2)
        eng.run()
    stamps = []
    eng.add_request(
        _markov(3, 8, cfg.vocab), max_new=30,
        stream=lambda t: stamps.append(time.perf_counter())
        if t is not None else None)
    for _ in range(4):                         # victim admitted + decoding
        eng.step()
    for k in range(3):                         # adversarial long arrivals
        eng.add_request(_markov(7 + k, 96, cfg.vocab), max_new=2)
        for _ in range(6):
            eng.step()
    eng.run()
    assert len(stamps) == 30
    return float(np.max(np.diff(stamps))), eng, tm


def _slice_spans(tm):
    return sum(1 for e in tm.chrome_trace()["traceEvents"]
               if e["ph"] == "X" and e["name"] == "prefill_slice")


def test_interleave_bounds_decode_gaps(trained_lm):
    """The bug: a blocking 96-token wave (bucket 128 — eight 16-token
    chunks, back to back) lands whole inside one of the victim's
    inter-token gaps. Interleaved, each gap absorbs at most one 16-token
    slice, so the victim's worst gap
    must come out strictly smaller — and the engines' telemetry shows the
    structural difference: the interleaved run books prefill_slice spans
    and not one blocking prefill_wave."""
    cfg, api, params = trained_lm
    gap_b, eng_b, tm_b = _victim_gaps(api, params, cfg, interleave=False)
    gap_i, eng_i, tm_i = _victim_gaps(api, params, cfg, interleave=True)
    assert gap_i < gap_b, (gap_i, gap_b)
    assert tm_b.prefill_s.count > 0 and _slice_spans(tm_b) == 0
    assert tm_i.prefill_s.count == 0
    assert _slice_spans(tm_i) == eng_i.stats["prefill_slices"] > 0
    assert eng_i.stats["prefill_jobs"] > 0
    assert eng_b.stats["prefill_jobs"] == eng_b.stats["prefill_slices"] == 0


def test_decode_never_skipped_while_slicing(trained_lm):
    """Starvation-freedom the other way: on every tick that advanced a
    prefill slice, the co-resident decoding request still gained a token
    — co-scheduling, not alternation."""
    cfg, api, params = trained_lm
    eng = ServeEngine(api, params, max_batch=2, max_len=160,
                      prefill_chunk=16, interleave=True)
    vid = eng.add_request(_markov(3, 8, cfg.vocab), max_new=40)
    for _ in range(4):
        eng.step()
    eng.add_request(_markov(9, 96, cfg.vocab), max_new=2)
    victim = next(r for r in eng.slots if r is not None and r.rid == vid)
    while eng._jobs or eng.queue:
        before_toks = len(victim.out)
        before_slices = eng.stats["prefill_slices"]
        eng.step()
        if eng.stats["prefill_slices"] > before_slices:
            assert len(victim.out) == before_toks + 1
    assert eng.stats["prefill_slices"] >= 96 // 16
    res = eng.run()
    assert len(res[vid]) == 40


def test_interleave_requires_slice_seam(trained_lm):
    cfg, api, params = trained_lm
    gutted = api._replace(prefill_slice=None)
    with pytest.raises(ValueError, match="prefill slice"):
        ServeEngine(gutted, params, max_batch=2, max_len=64,
                    interleave=True)
    with pytest.raises(ValueError, match="slices_per_tick"):
        ServeEngine(api, params, max_batch=2, max_len=64, interleave=True,
                    slices_per_tick=0)
