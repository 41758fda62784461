"""Continuous-batching slot engine.

A fixed pool of ``max_batch`` decode slots. The decode step is a single
jitted call over the *whole* pool every tick — its shape never changes, so
it compiles exactly once — and requests flow through three states:

  queued -> admitted (prefill into a free slot) -> evicted (max_new / stop)

Admission happens *between decode steps*: finished requests free their slot
at the end of a tick and the scheduler immediately prefills queued work into
the gaps, so slots never idle while the queue is non-empty. Prefill batches
are padded to power-of-two length buckets and group sizes (bounding compile
variants to O(#buckets * log max_batch)); ``seq_lens`` makes the padded
prefill bit-identical to an exact-length one (see models/transformer.py),
so greedy outputs match the run-to-completion BucketEngine exactly.

Free slots still ride through the decode step — their rows are computed and
ignored. That is the BEANNA trade expressed at the serving layer: a fixed
systolic-array-shaped batch with full occupancy beats perfectly-sized but
ragged launches, because the hot loop never recompiles and eviction /
admission cost only a cache scatter.

Two cache backends (``kv_block_size``):

  0 (default)   slot-contiguous: each slot owns a private (max_len, ...)
                KV region — the historical layout, bit-compatible.
  > 0           paged: one shared block pool + per-slot block tables
                (serving/kvcache.py). With ``prefix_cache=True`` a radix
                tree over token blocks (serving/prefix.py) lets requests
                sharing a prompt prefix share the prefix's physical blocks
                and prefill only their un-cached suffix — O(unique suffix)
                instead of O(prompt) prefill under multi-user traffic.

Sampling (``temperature > 0``) uses per-request RNG streams: request
``rid``'s token t is drawn from fold_in(fold_in(seed_key, rid), t), so a
request's sampled output is a function of (params, prompt, seed, rid) only
— independent of pool size, co-resident traffic, and admission batching.

Speculative decoding (``spec_k > 0``) swaps the one-token tick for a
draft/verify wave — the BEANNA fp/binary mode mux running the serving hot
loop. A binarized self-draft (serving/spec.py: the served weights with
sign-packed + absmean-scaled MLPs, everything else aliased) proposes
``spec_k`` tokens through the *target's own cache*; one multi-token verify
pass (ModelApi.verify) re-scores every position with exact float K/V; the
engine keeps the longest prefix whose tokens match what the request's own
RNG stream would have emitted from the target logits, plus one correction
/ bonus token. Outputs are token-identical to the non-speculative engine
by construction — each emitted token is drawn from target logits at its
own (rid, step) stream — and cache rollback is a per-slot length reset:
rejected positions sit past ``len``, invisible to every masked read, and
are overwritten by later waves.

The whole wave runs as ONE jitted launch (serving/spec.make_spec_wave):
the k draft decodes are a ``lax.scan`` with on-device token picks, the
rewind, the verify pass, and candidate selection fused behind it — two
dispatches per wave (wave + accept-driven length reset) where PR 5 paid
2k+3 with a host sample round-trip between every draft step.
``spec_draft_impl`` picks the packed-matmul lowering inside the draft
("auto" | "xla_xnor" | "int8_mxu" | "pallas_xnor" — exact-int32 twins,
see kernels/ops.py), threaded through ``ModelConfig`` like ``attn_impl``.

Telemetry (``telemetry=``, serving/telemetry.py) threads a metrics
registry + lifecycle tracer through every path above: request spans
(queued -> admitted -> first token -> generate -> finished), per-phase
tick histograms (prefill wave / decode tick / spec wave), queue-wait,
TTFT/ITL, and cache-pressure gauges. Each tick's phases (``serve.step``
around ``serve.admit`` / ``serve.dispatch`` / ``serve.sync`` /
``serve.emit``) are profiler spans on the device trace's clock, and each
jitted entry has a stable name (``serve_decode``, ``serve_prefill``,
``serve_insert``, ``serve_spec_wave``, ``serve_prefill_slice``) that
names its module in the trace. The contract is **zero extra device
work**: every hook reads host clocks and host integers the engine already
holds, so telemetry on vs. off is token-identical with an equal
jitted-dispatch count (tests/test_telemetry.py asserts both). ``stats``
stays the cheap always-on dict; ``STATS_SCHEMA`` documents its keys.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

import logging

from repro.serving import kvcache as kvc
from repro.serving.kvcache import kv_pool_bytes
from repro.serving.prefix import PrefixPool
from repro.serving.scheduler import (AdmissionError, FifoScheduler, Request,
                                     SloScheduler, accept_wave, bucket_len,
                                     make_buckets, pad_group, slo_rank)

log = logging.getLogger("repro.serving.engine")

# the phase span of an engine without telemetry: none at all
_NO_PHASE = contextlib.nullcontext()


def _entry(name: str, fn):
    """``fn`` under a stable name: jit names its module after it, so the
    device trace says which engine entry ran."""
    @wraps(fn)
    def entry(*args):
        return fn(*args)
    entry.__name__ = entry.__qualname__ = name
    return entry


# every ServeEngine.stats key, its type, and what it counts — the schema
# tests/test_telemetry.py holds the dict to (ad-hoc keys don't ship)
STATS_SCHEMA = {
    "decode_steps": (int, "engine ticks (decode steps or spec waves)"),
    "occupied_slot_steps": (int, "sum over ticks of occupied slots"),
    "prefills": (int, "admission prefill installs (blocking waves or "
                      "completed interleaved jobs)"),
    "prefill_jobs": (int, "interleaved prefill jobs started "
                          "(0 with interleave off)"),
    "prefill_slices": (int, "interleaved prefill slices run alongside "
                            "decode ticks"),
    "admitted": (int, "requests admitted into a slot"),
    "evictions": (int, "requests finished and evicted"),
    "generated_tokens": (int, "tokens emitted across all requests"),
    "prefilled_tokens": (int, "tokens run through prefill attention"),
    "cached_prompt_tokens": (int, "prompt tokens served from the radix "
                                  "prefix cache instead of prefill"),
    "spec_waves": (int, "speculative draft/verify waves run"),
    "spec_drafted": (int, "draft tokens proposed"),
    "spec_accepted": (int, "draft tokens accepted by verify"),
    "spec_draft_launches": (int, "device launches spent drafting"),
    "kv_bytes": (int, "resident bytes of the preallocated KV pool"),
    "kv_bytes_per_device": (int, "per-device shard of kv_bytes "
                                 "(== kv_bytes / mesh size)"),
}


@dataclasses.dataclass
class _PagedSlot:
    """Host-side block accounting for one occupied slot (paged mode)."""
    plen: int                    # prompt tokens
    row: np.ndarray              # (n_pages,) physical ids, holes = sentinel
    chain: list                  # radix nodes covering leading full blocks
    private: list                # physical blocks owned by this request


@dataclasses.dataclass
class _PrefillJob:
    """One admitted group prefilling a slice per tick (interleave mode).

    Slots are *committed* (counted against free capacity) when the job is
    created but only assigned at install time, after the last slice; until
    then the prompt's K/V accumulates in a transient cache the size of one
    prefill batch — the main pool is untouched, so in-flight decode slots
    never see a partial prefill (and the contiguous pool's span-write
    clamp never meets a garbage row)."""
    admitted: list               # [(Request, chain, blocks)]; contiguous
    #                              mode uses empty chain/blocks
    toks: np.ndarray             # (gp, blen) right-padded suffix tokens
    lens: np.ndarray             # (gp,) true suffix lengths
    blen: int                    # padded bucket length
    gp: int                      # padded group size
    monolithic: bool             # True: one blocking call at dequeue (the
    #                              cached-prefix path can't slice through
    #                              gathered context)
    arrays: dict | None          # paged group arrays (_paged_arrays)
    todo: int = 0                # slice coverage target (ceil(max lens/c)*c)
    pos: int = 0                 # prompt tokens already sliced
    caches: object = None        # transient per-job prefill cache
    h_last: object = None        # (gp, 1, d) captured last hidden states
    arrival: int = 0             # min member arrival (job aging)
    rank: int = 0                # min member SLO rank (job priority)
    t_start: float = 0.0         # admission-decision clock (telemetry)


class ServeEngine:
    def __init__(self, api, params, *, max_batch: int = 8,
                 max_len: int = 512, temperature: float = 0.0, seed: int = 0,
                 min_bucket: int = 8, attn_impl: str | None = None,
                 kv_cache: str | None = None, kv_block_size: int = 0,
                 prefix_cache: bool = False, n_blocks: int | None = None,
                 spec_k: int = 0, spec_draft: str = "binary",
                 spec_draft_impl: str | None = None, mesh=None,
                 prefill_chunk: int = 0, telemetry=None,
                 interleave: bool = False, slices_per_tick: int = 1,
                 scheduler: str = "fifo", starvation_limit: int = 64):
        overrides = {}
        if attn_impl is not None:
            overrides["attn_impl"] = attn_impl
        if kv_cache is not None:
            overrides["kv_cache"] = kv_cache
        if spec_draft_impl is not None:
            from repro.kernels.ops import SPEC_DRAFT_IMPLS
            if spec_draft_impl not in SPEC_DRAFT_IMPLS:
                raise ValueError(
                    f"unknown spec_draft_impl {spec_draft_impl!r}: "
                    f"expected one of {SPEC_DRAFT_IMPLS}")
            overrides["spec_draft_impl"] = spec_draft_impl
        if overrides:
            # rebind every model fn to the requested attention backend /
            # cache codec (api closures capture cfg, so a fresh api is the
            # only seam)
            from repro.models import get_model
            api = get_model(api.cfg.replace(**overrides))
        if api.cache_insert is None:
            raise ValueError(
                f"model family {api.cfg.family!r} has no slot-indexed cache "
                "insert; use repro.serving.bucket.BucketEngine instead")
        if prefix_cache and not kv_block_size:
            raise ValueError("prefix_cache requires kv_block_size > 0 "
                             "(the radix cache shares paged blocks)")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k and spec_draft != "binary":
            raise ValueError(
                f"unknown speculative draft {spec_draft!r}: 'binary' (the "
                "sign-packed self-draft) is the only draft; spec_k=0 "
                "disables speculation")
        if spec_k and api.verify is None:
            raise ValueError(
                f"model {api.cfg.name!r} has no multi-token verify step "
                "(MLA/SSM caches decode one token at a time); speculative "
                "decoding requires a GQA KV pool (spec_k=0)")
        if kv_block_size and api.init_paged_cache is None:
            raise ValueError(
                f"model {api.cfg.name!r} has no paged cache layout "
                "(MLA/SSM caches are not paged); use kv_block_size=0")
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0 or (
                self.prefill_chunk and
                self.prefill_chunk & (self.prefill_chunk - 1)):
            raise ValueError(
                f"prefill_chunk must be 0 or a power of two (buckets are "
                f"powers of two), got {prefill_chunk}")
        if self.prefill_chunk and api.prefill_chunked is None:
            raise ValueError(
                f"model {api.cfg.name!r} has no chunked prefill (GQA "
                "families only); use prefill_chunk=0")
        self.interleave = bool(interleave)
        self.slices_per_tick = int(slices_per_tick)
        if self.interleave and self.slices_per_tick < 1:
            raise ValueError(
                f"slices_per_tick must be >= 1, got {slices_per_tick}")
        if self.interleave and api.prefill_slice is None:
            raise ValueError(
                f"model {api.cfg.name!r} has no prefill slice step (GQA "
                "families only — the verify path); use interleave=False")
        if scheduler not in ("fifo", "slo"):
            raise ValueError(
                f"unknown scheduler {scheduler!r}: expected 'fifo' or 'slo'")
        # -- tensor-parallel serving: a `model`-axis mesh shards attention
        # heads + MLP hidden (the param logical-axis rules) and the KV
        # pool's head axis (cache_partition_specs), so per-device cache
        # residency shrinks ~1/model and decode matmuls split across
        # devices. Rules activate only around this engine's jitted calls
        # (see _meshed), so mesh and plain engines coexist in-process.
        self.mesh = mesh
        if mesh is not None:
            from repro.launch import specs as _specs
            self._mesh_rules = _specs.mesh_rules_for(api.cfg, mesh)
            _, p_sh = _specs.param_shardings(api, mesh, self._mesh_rules)
            params = jax.device_put(params, p_sh)
        self.api, self.params = api, params
        self.max_batch, self.max_len = max_batch, max_len
        self.temperature = temperature
        self._seed_key = jax.random.PRNGKey(seed)
        self._next_rid = 0
        self.queue: list[Request] = []
        self.results: dict[int, list[int]] = {}
        # host-side observer; None = the exact pre-telemetry engine (tests
        # assert telemetry on/off is token-identical with equal dispatches)
        self.tm = telemetry
        _reg = telemetry.registry if telemetry is not None else None
        self.buckets = make_buckets(max_len, min_bucket=min_bucket)
        if scheduler == "slo":
            self.sched = SloScheduler(self.buckets, metrics=_reg,
                                      starvation_limit=starvation_limit)
        else:
            self.sched = FifoScheduler(self.buckets, metrics=_reg)
        # interleaved-prefill state: in-flight jobs + slots promised to
        # them (committed slots are subtracted from free capacity so two
        # jobs can't both target the same future vacancy)
        self._jobs: list[_PrefillJob] = []
        self._committed = 0
        # slice width: the prefill_chunk knob when set, else the smallest
        # bucket — "decode-tick-sized" is the contract, and both choices
        # are powers of two, so slices always tile the padded bucket
        self.slice_chunk = self.prefill_chunk or self.buckets[0]
        # slot table: per-slot request (None = free), next token to feed
        self.slots: list[Request | None] = [None] * max_batch
        self.next_tok = np.zeros((max_batch, 1), np.int32)

        self.block_size = int(kv_block_size)
        self.paged = self.block_size > 0
        self.prefix_on = bool(prefix_cache)
        if self.paged:
            bs = self.block_size
            self.n_pages = -(-max_len // bs)
            self.pool_len = self.n_pages * bs
            # default pool capacity == the slot-contiguous pool's: sharing
            # then only ever *frees* blocks, so admission can always
            # succeed once refcount-0 tree blocks are evicted
            self.n_blocks = (n_blocks if n_blocks is not None
                             else max_batch * self.n_pages)
            init_pool = partial(api.init_paged_cache, self.n_blocks, bs,
                                max_batch, self.n_pages)
            self.pool = PrefixPool(self.n_blocks, bs, metrics=_reg)
            self._pstate: dict[int, _PagedSlot] = {}
            self._codec = kvc.get_codec(api.cfg.kv_cache)
            self._hole_row = np.full((self.n_pages,), self.n_blocks,
                                     np.int32)
        else:
            self.pool_len = max_len
            init_pool = partial(api.init_cache, max_batch, max_len)
        if mesh is None:
            self.caches = init_pool()
        else:
            # the pool itself and every transient prefill cache carry
            # NamedShardings with the head axis on "model": the pool is
            # created sharded (never whole on one device), and every jit
            # that returns a pool below pins out_shardings — cache blocks
            # never gather to one device
            self._cache_sh = kvc.cache_shardings(
                jax.eval_shape(init_pool), mesh, self._mesh_rules)
            self.caches = jax.jit(init_pool,
                                  out_shardings=self._cache_sh)()
            self._prefill_sh = kvc.cache_shardings(
                jax.eval_shape(
                    lambda: api.init_cache(max_batch, self.pool_len)),
                mesh, self._mesh_rules)
            from jax.sharding import NamedSharding, PartitionSpec
            self._repl = NamedSharding(mesh, PartitionSpec())
        # public virtual clock (decode steps elapsed): callers scheduling
        # arrivals by step may also fast-forward it across idle gaps, as
        # benchmarks/serve_bench.py does
        self.step_count = 0
        # kv_bytes: resident bytes of the preallocated cache pool — fixed
        # at init (the pool never grows), so the codec trade is visible
        # next to the throughput numbers. prefilled_tokens counts tokens
        # actually run through prefill attention; cached_prompt_tokens
        # counts prompt tokens served from the radix cache instead.
        # spec_*: speculative-decoding counters (spec_k > 0): waves run,
        # draft tokens proposed, draft tokens accepted by verify —
        # acceptance_rate() = spec_accepted / spec_drafted
        self.stats = {"decode_steps": 0, "occupied_slot_steps": 0,
                      "prefills": 0, "prefill_jobs": 0, "prefill_slices": 0,
                      "admitted": 0, "evictions": 0,
                      "generated_tokens": 0, "prefilled_tokens": 0,
                      "cached_prompt_tokens": 0,
                      "spec_waves": 0, "spec_drafted": 0, "spec_accepted": 0,
                      # device launches spent drafting: 1 per wave with the
                      # fused draft scan (PR 5 spent k per wave) — the
                      # dispatch-count reduction benchmarks assert on
                      "spec_draft_launches": 0,
                      "kv_bytes": kv_pool_bytes(self.caches),
                      # per-device shard of the pool: == kv_bytes on one
                      # device, ~kv_bytes/model on a model-axis mesh
                      "kv_bytes_per_device":
                          kvc.kv_pool_bytes_per_device(self.caches)}
        if self.tm is not None:
            self.tm.engine_started(
                kv_bytes=self.stats["kv_bytes"],
                kv_bytes_per_device=self.stats["kv_bytes_per_device"],
                max_batch=max_batch,
                n_blocks=self.n_blocks if self.paged else None,
                byte_breakdown=kvc.kv_pool_byte_breakdown(self.caches))

        def outs(*sh):
            # pin pool-returning jits' output shardings under a mesh so the
            # persistent pool provably stays sharded through every donated
            # update; {} when no mesh (the exact historical jits)
            if mesh is None:
                return {}
            return {"out_shardings": sh[0] if len(sh) == 1 else sh}

        # the pool cache is donated: step/admit immediately rebind
        # self.caches, so XLA can update the (layers, B, T, ...) buffers in
        # place instead of copying the whole pool every tick
        self._decode = self._meshed(jax.jit(
            _entry("serve_decode", api.decode), donate_argnums=1,
            **outs(self._repl, self._cache_sh) if mesh is not None
            else {}))
        prefill_fn = api.prefill_chunked if self.prefill_chunk else \
            api.prefill
        prefill_kw = ({"chunk": self.prefill_chunk} if self.prefill_chunk
                      else {})
        self._prefill = self._meshed(jax.jit(
            _entry("serve_prefill",
                   lambda p, toks, sl: prefill_fn(p, {"tokens": toks},
                                                  max_len=self.pool_len,
                                                  seq_lens=sl,
                                                  **prefill_kw)),
            **outs(self._repl, self._prefill_sh) if mesh is not None
            else {}))
        if self.paged:
            self._insert_pages = self._meshed(jax.jit(
                _entry("serve_insert", kvc.paged_insert_prefill),
                donate_argnums=0,
                **outs(self._cache_sh) if mesh is not None else {}))
            self._update_slots = self._meshed(jax.jit(
                kvc.paged_update_slots, donate_argnums=0,
                **outs(self._cache_sh) if mesh is not None else {}))
            codec, hd = self._codec, api.cfg.kv_head_dim()
            self._gather_ctx = self._meshed(jax.jit(
                lambda caches, pages: kvc.gather_prefix_context(
                    caches, pages, codec, hd)))
            self._prefill_ctx = self._meshed(jax.jit(
                lambda p, toks, sl, ctx, cl: api.prefill_ctx(
                    p, {"tokens": toks}, ctx, cl, max_len=self.pool_len,
                    seq_lens=sl)))
        else:
            self._insert = self._meshed(jax.jit(
                _entry("serve_insert", api.cache_insert), donate_argnums=0,
                **outs(self._cache_sh) if mesh is not None else {}))
        if self.interleave:
            # one slice per tick: exact K/V appends into the job's
            # transient cache (donated — updated in place across slices),
            # last-token hidden capture, head matmul deferred to finish
            self._slice = self._meshed(jax.jit(
                _entry("serve_prefill_slice", api.prefill_slice),
                donate_argnums=(1, 3),
                **outs(self._repl, self._prefill_sh) if mesh is not None
                else {}))
            self._slice_finish = self._meshed(jax.jit(
                api.prefill_slice_finish, donate_argnums=1,
                **outs(self._repl, self._prefill_sh) if mesh is not None
                else {}))
            # per-group-size jitted zero-state builders (the zeros are
            # created on device, not transferred): O(log max_batch) entries
            self._slice_inits: dict[int, object] = {}
        seed_key = self._seed_key

        def sample_rows(rids, steps, logits, t):
            # per-request streams derived inside the jit: one dispatch per
            # tick, not O(max_batch) host-side fold_in calls
            def one(rid, step, row):
                k = jax.random.fold_in(jax.random.fold_in(seed_key, rid),
                                       step)
                return jax.random.categorical(k, row / t)

            return jax.vmap(one)(rids, steps, logits).astype(jnp.int32)

        self._sample_rows = jax.jit(sample_rows)

        self.spec_k = int(spec_k)
        if self.spec_k:
            from repro.serving.spec import binarize_draft_params, \
                make_spec_wave
            # the draft aliases every non-FFN target array; only the
            # packed sign bits + absmean scales are new residency
            self.draft_params = binarize_draft_params(params, api.cfg)
            if mesh is not None:
                # aliased float leaves already landed sharded via the
                # device_put above; the packed sign-bit + scale leaves are
                # tiny and new, so replicate anything not yet on the mesh
                from jax.sharding import NamedSharding as _NS
                self.draft_params = jax.tree.map(
                    lambda x: x if isinstance(getattr(x, "sharding", None),
                                              _NS)
                    else jax.device_put(x, self._repl),
                    self.draft_params)
            # the whole wave — k scanned draft decodes, rewind, float
            # verify, candidate selection — is ONE jitted launch (PR 5
            # dispatched each draft step separately with a host sample
            # round-trip in between: 2k+3 dispatches per wave, and the
            # dispatch overhead is what kept hybrid at 0.4x wall-clock)
            self._spec_wave = self._meshed(jax.jit(
                _entry("serve_spec_wave",
                       make_spec_wave(api, k=self.spec_k,
                                      temperature=float(temperature),
                                      seed_key=self._seed_key)),
                donate_argnums=2,
                **outs(self._repl, self._repl, self._cache_sh)
                if mesh is not None else {}))
            self._set_lens = self._meshed(jax.jit(
                kvc.set_cache_lengths, donate_argnums=0,
                **outs(self._cache_sh) if mesh is not None else {}))

    def _meshed(self, fn):
        """Run ``fn`` with this engine's mesh + logical rules active.

        Rules are process-global (with_logical_constraint and the
        cache-update "auto" policy read them at trace time), so they are
        flipped on only for the duration of each jitted call and restored
        afterwards — a mesh engine and a plain engine can interleave steps
        in one process without trampling each other's lowering decisions.
        No-op without a mesh.
        """
        if self.mesh is None:
            return fn
        from repro.distributed import sharding as shd
        from repro.launch.mesh import set_mesh
        mesh, rules = self.mesh, self._mesh_rules

        def call(*args):
            prev = shd.get_logical_rules()
            shd.set_logical_rules(mesh, rules)
            try:
                with set_mesh(mesh):
                    return fn(*args)
            finally:
                shd.set_logical_rules(*prev)
        return call

    def _phase(self, name: str):
        """The telemetry phase span ``name`` (``Telemetry.phase``); none
        without telemetry."""
        return self.tm.phase(name) if self.tm is not None else _NO_PHASE

    def check_request(self, prompt_len: int, max_new: int,
                      slo: str = "standard") -> None:
        """Admission validation, as one pure read-only gate.

        Raises AdmissionError (a ValueError subclass) with a structured
        code/detail — the per-request rejection the HTTP front door maps
        to a 400. Every limit that could otherwise detonate inside the
        tick loop (``bucket_len`` on an over-long prompt would kill the
        engine mid-tick for every co-resident request) is checked here,
        against immutable engine config only, so the front door may call
        it from its HTTP threads before enqueueing."""
        if prompt_len <= 0:
            raise AdmissionError(
                "empty_prompt", "prompt must contain at least one token",
                prompt_len=int(prompt_len))
        if max_new < 1:
            raise AdmissionError(
                "bad_max_new", f"max_new must be >= 1, got {max_new}",
                max_new=int(max_new))
        slo_rank(slo)                      # raises AdmissionError(bad_slo)
        if prompt_len > self.buckets[-1]:
            raise AdmissionError(
                "prompt_too_long",
                f"prompt length {prompt_len} exceeds the largest prefill "
                f"bucket ({self.buckets[-1]})",
                prompt_len=int(prompt_len), limit=int(self.buckets[-1]))
        if prompt_len + max_new + self.spec_k > self.max_len:
            extra = (f" + spec_k ({self.spec_k})" if self.spec_k else "")
            raise AdmissionError(
                "too_long",
                f"prompt ({prompt_len}) + max_new ({max_new}){extra} "
                f"exceeds max_len ({self.max_len})"
                + (": speculative waves write up to spec_k tokens of "
                   "scratch K/V past the last kept position"
                   if self.spec_k else ""),
                prompt_len=int(prompt_len), max_new=int(max_new),
                spec_k=int(self.spec_k), max_len=int(self.max_len))

    def add_request(self, prompt, max_new: int = 16,
                    stop_tokens=(), slo: str = "standard",
                    stream=None) -> int:
        prompt = np.asarray(prompt, np.int32)
        self.check_request(len(prompt), max_new, slo)
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, prompt, max_new,
                                  stop_tokens=frozenset(
                                      int(t) for t in stop_tokens),
                                  slo=slo, arrival=self.step_count,
                                  stream=stream))
        if self.tm is not None:
            self.tm.request_added(rid, len(prompt))
        return rid

    # -- sampling -----------------------------------------------------------

    def _sample(self, logits, reqs):
        """reqs: one Request (or None for free/dummy rows) per logits row.

        Greedy is a pure argmax. Stochastic sampling draws row r from the
        request's own stream — fold_in(fold_in(seed, rid), len(out)) — so
        tokens don't depend on which other rows happen to share the call.
        Free/dummy rows draw from (rid 0, step 0); their tokens are never
        read. (Speculative waves sample inside the fused launch —
        serving/spec.make_spec_wave — with the same per-row streams.)
        """
        if self.temperature <= 0:
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            rids = np.asarray([r.rid if r is not None else 0 for r in reqs],
                              np.int32)
            steps = np.asarray([len(r.out) if r is not None else 0
                                for r in reqs], np.int32)
            toks = self._sample_rows(jnp.asarray(rids), jnp.asarray(steps),
                                     logits, float(self.temperature))
        with self._phase("serve.sync"):
            return np.asarray(toks)

    # -- slot lifecycle -----------------------------------------------------

    def _notify(self, r: Request, tok):
        """Deliver one stream event (token id, or None = finished) to the
        request's observer; observer failures must never reach the tick
        loop (a broken SSE client is that client's problem)."""
        if r.stream is None:
            return
        try:
            r.stream(tok)
        except Exception:  # noqa: BLE001 - observer code is untrusted
            log.exception("stream callback failed for rid %d", r.rid)
            r.stream = None

    def _finish(self, slot: int):
        r = self.slots[slot]
        self.results[r.rid] = r.out
        self.slots[slot] = None
        self.stats["evictions"] += 1
        self._notify(r, None)
        if self.tm is not None:
            reason = ("stop" if r.out and r.out[-1] in r.stop_tokens
                      and len(r.out) < r.max_new else "max_new")
            self.tm.request_finished(r.rid, reason)
        if self.paged:
            st = self._pstate.pop(slot)
            self.pool.release(st.chain)
            self.pool.free_blocks(st.private)
            # neutralize the slot's device table/len *now*: the next decode
            # tick must not write through a stale row into freed (possibly
            # reallocated) blocks
            self.caches = self._update_slots(
                self.caches, jnp.asarray(self._hole_row[None]),
                jnp.zeros((1,), jnp.int32),
                jnp.asarray([slot], jnp.int32))

    def _append_token(self, slot: int, tok: int) -> bool:
        """Record one generated token; returns True if the request ended
        (max_new or stop token) and the slot was freed."""
        r = self.slots[slot]
        r.out.append(tok)
        self.next_tok[slot, 0] = tok
        self.stats["generated_tokens"] += 1
        self._notify(r, tok)
        if len(r.out) >= r.max_new or tok in r.stop_tokens:
            self._finish(slot)
            return True
        return False

    def _group_arrays(self, group):
        """Bucket-padded token/length arrays for one contiguous group."""
        blen = bucket_len(max(len(r.prompt) for r in group), self.buckets)
        gp = pad_group(len(group))
        toks = np.zeros((gp, blen), np.int32)
        lens = np.ones((gp,), np.int32)          # dummy rows: 1-token prompt
        for j, r in enumerate(group):
            toks[j, :len(r.prompt)] = r.prompt
            lens[j] = len(r.prompt)
        return toks, lens, blen, gp

    def _install_contig(self, group, blen, gp, logits, new, *,
                        wave_t0=None, t_admit=0.0):
        """Sample first tokens and scatter one prefilled group's caches
        into free slots — the install tail shared verbatim by the blocking
        wave and the interleaved job, so their tokens match by
        construction. ``wave_t0`` set = a blocking wave happened (book the
        prefill_wave span); ``t_admit`` stamps queue-wait's end (the
        admission decision / prefill start, NOT the wave end)."""
        free = [i for i, r in enumerate(self.slots) if r is None]
        rows = list(group) + [None] * (gp - len(group))
        nxt = self._sample(logits, rows)
        # dummy rows aim past the pool and are dropped by the scatter
        idx = np.full((gp,), self.max_batch, np.int32)
        idx[:len(group)] = free[:len(group)]
        with self._phase("serve.dispatch"):
            self.caches = self._insert(self.caches, new, jnp.asarray(idx))
        self.stats["prefills"] += 1
        now = 0.0
        if self.tm is not None:
            now = self.tm.clock()
            if wave_t0 is not None:
                self.tm.prefill_wave(wave_t0, n_reqs=len(group),
                                     bucket=blen, now=now)
        with self._phase("serve.emit"):
            for j, r in enumerate(group):
                slot = int(idx[j])
                self.slots[slot] = r
                self.stats["admitted"] += 1
                self.stats["prefilled_tokens"] += len(r.prompt)
                if self.tm is not None:
                    self.tm.request_admitted(
                        r.rid, slot=slot, prefilled_tokens=len(r.prompt),
                        now=t_admit)
                    self.tm.tokens_emitted(r.rid, 1, now=now)
                self._append_token(slot, int(nxt[j]))

    def _admit(self):
        """Prefill queued requests into free slots (one group per bucket)."""
        if self.paged:
            self._admit_paged()
            return
        free = [i for i, r in enumerate(self.slots) if r is None]
        while free and self.queue:
            group = self.sched.select(self.queue, len(free),
                                      clock=self.step_count)
            if not group:
                break
            for r in group:
                self.queue.remove(r)
            toks, lens, blen, gp = self._group_arrays(group)
            t0 = self.tm.clock() if self.tm is not None else 0.0
            with self._phase("serve.dispatch"):
                logits, new = self._prefill(self.params, jnp.asarray(toks),
                                            jnp.asarray(lens))
            self._install_contig(group, blen, gp, logits, new,
                                 wave_t0=t0, t_admit=t0)
            free = [i for i, r in enumerate(self.slots) if r is None]

    # -- paged admission (radix prefix cache) --------------------------------

    def _select_paged(self, n_free: int):
        """Pick one paged admission group and allocate its blocks.

        Returns [(Request, chain, blocks)] with the requests already
        dequeued (possibly empty on pool exhaustion); matched prefix
        chains come pinned."""
        bs = self.block_size
        # longest cached block-prefix per queued request, under the
        # tree as of *this wave* (earlier waves may have published)
        chains = {}
        for r in self.queue:
            chains[r.rid] = (self.pool.match(r.prompt,
                                             clock=self.step_count)
                             if self.prefix_on else [])

        def suffix_len(r):
            return len(r.prompt) - len(chains[r.rid]) * bs

        group = self.sched.select(self.queue, n_free,
                                  length_of=suffix_len,
                                  clock=self.step_count)
        if not group:
            return []
        # pin every candidate's matched chain BEFORE any allocation:
        # alloc-driven LRU eviction only sees refcount-0 nodes, so a
        # group member's (or the request's own) matched chain can
        # never be reclaimed out from under the wave
        for r in group:
            self.pool.acquire(chains[r.rid])
        admitted, deferred = [], list(group)
        while deferred:
            r = deferred[0]
            chain = chains[r.rid]
            ctx_pages = len(chain)
            # +spec_k: verify waves write draft-scratch K/V up to
            # spec_k positions past the last kept token
            need = (-(-(len(r.prompt) + r.max_new - 1 + self.spec_k)
                      // bs) - ctx_pages)
            blocks = self.pool.alloc(need, clock=self.step_count)
            if blocks is None:
                break                      # pool exhausted this wave
            deferred.pop(0)
            admitted.append((r, chain, blocks))
        for r in deferred:                 # not admitted: unpin
            self.pool.release(chains[r.rid])
        for r, _, _ in admitted:
            self.queue.remove(r)
        return admitted

    def _admit_paged(self):
        free = [i for i, r in enumerate(self.slots) if r is None]
        while free and self.queue:
            admitted = self._select_paged(len(free))
            if not admitted:
                break
            a = self._paged_arrays(admitted)
            t0 = self.tm.clock() if self.tm is not None else 0.0
            logits, new = self._paged_prefill_call(a)
            self._install_paged(admitted, a, logits, new,
                                wave_t0=t0, t_admit=t0)
            free = [i for i, r in enumerate(self.slots) if r is None]

    def _paged_arrays(self, admitted) -> dict:
        """Host-side arrays for one paged group's suffix prefill."""
        bs = self.block_size
        blen = bucket_len(max(len(r.prompt) - len(c) * bs
                              for r, c, _ in admitted), self.buckets)
        gp = pad_group(len(admitted))
        toks = np.zeros((gp, blen), np.int32)
        lens = np.ones((gp,), np.int32)
        plens = np.zeros((gp,), np.int32)
        ctx_lens = np.zeros((gp,), np.int32)
        rows = np.tile(self._hole_row, (gp, 1))          # (gp, n_pages)
        dest = np.tile(self._hole_row, (gp, 1))
        max_ctx_pages = max(len(c) for _, c, _ in admitted)
        for j, (r, chain, blocks) in enumerate(admitted):
            ctx_pages = len(chain)
            suffix = r.prompt[ctx_pages * bs:]
            toks[j, :len(suffix)] = suffix
            lens[j] = len(suffix)
            plens[j] = len(r.prompt)
            ctx_lens[j] = ctx_pages * bs
            rows[j, :ctx_pages] = [n.block for n in chain]
            rows[j, ctx_pages:ctx_pages + len(blocks)] = blocks
            # suffix-cache page i lands in the slot's page ctx_pages + i
            n_suffix_pages = self.n_pages - ctx_pages
            dest[j, :n_suffix_pages] = rows[j, ctx_pages:]
        ctx_tab = None
        if max_ctx_pages:
            # pad the gathered context to a power-of-two page bucket so
            # compile variants stay O(buckets), not O(distinct lengths)
            pb = 1
            while pb < max_ctx_pages:
                pb *= 2
            ctx_tab = np.zeros((gp, pb), np.int32)
            for j, (_, chain, _) in enumerate(admitted):
                ctx_tab[j, :len(chain)] = [n.block for n in chain]
        return {"toks": toks, "lens": lens, "plens": plens,
                "ctx_lens": ctx_lens, "rows": rows, "dest": dest,
                "blen": blen, "gp": gp, "max_ctx_pages": max_ctx_pages,
                "ctx_tab": ctx_tab}

    def _paged_prefill_call(self, a: dict):
        """One blocking suffix prefill (plain, or against gathered ctx)."""
        with self._phase("serve.dispatch"):
            if a["max_ctx_pages"] == 0:
                return self._prefill(self.params, jnp.asarray(a["toks"]),
                                     jnp.asarray(a["lens"]))
            ctx = self._gather_ctx(self.caches, jnp.asarray(a["ctx_tab"]))
            return self._prefill_ctx(self.params, jnp.asarray(a["toks"]),
                                     jnp.asarray(a["lens"]), ctx,
                                     jnp.asarray(a["ctx_lens"]))

    def _install_paged(self, admitted, a: dict, logits, new, *,
                       wave_t0=None, t_admit=0.0):
        """Scatter one prefilled paged group into its blocks + free slots
        — shared verbatim by the blocking wave and the interleaved job
        (token parity by construction). ``wave_t0`` set = blocking wave
        (book the prefill_wave span); ``t_admit`` stamps queue-wait's end
        (the admission decision / prefill start, NOT the wave end)."""
        bs = self.block_size
        free = [i for i, r in enumerate(self.slots) if r is None]
        group = [r for r, _, _ in admitted]
        slots = free[:len(group)]
        gp = a["gp"]
        row_reqs = list(group) + [None] * (gp - len(group))
        nxt = self._sample(logits, row_reqs)
        # padded to the group's power-of-two size like every other
        # admission op (one compile per log group size, not per size);
        # dummy rows aim past the pool and drop
        slot_idx = np.full((gp,), self.max_batch, np.int32)
        slot_idx[:len(group)] = slots
        with self._phase("serve.dispatch"):
            self.caches = self._insert_pages(self.caches, new,
                                             jnp.asarray(a["dest"]))
            self.caches = self._update_slots(self.caches,
                                             jnp.asarray(a["rows"]),
                                             jnp.asarray(a["plens"]),
                                             jnp.asarray(slot_idx))
        self.stats["prefills"] += 1
        now = 0.0
        if self.tm is not None:
            now = self.tm.clock()
            if wave_t0 is not None:
                self.tm.prefill_wave(wave_t0, n_reqs=len(group),
                                     bucket=a["blen"], now=now)
        with self._phase("serve.emit"):
            for j, (r, chain, blocks) in enumerate(admitted):
                slot = slots[j]
                self.slots[slot] = r
                st = _PagedSlot(plen=len(r.prompt), row=a["rows"][j],
                                chain=chain, private=list(blocks))
                self._pstate[slot] = st
                self.stats["admitted"] += 1
                self.stats["prefilled_tokens"] += int(a["lens"][j])
                self.stats["cached_prompt_tokens"] += int(a["ctx_lens"][j])
                if self.tm is not None:
                    self.tm.request_admitted(
                        r.rid, slot=slot,
                        prefilled_tokens=int(a["lens"][j]),
                        cached_tokens=int(a["ctx_lens"][j]), now=t_admit)
                    self.tm.tokens_emitted(r.rid, 1, now=now)
                self.pool.record_hit(chain)
                if self.prefix_on:
                    # publish the prompt's full blocks beyond the matched
                    # prefix, so requests admitted from the next wave on
                    # share them (same-wave requests prefilled
                    # independently)
                    for pi in range(len(chain), len(r.prompt) // bs):
                        self._publish_block(st, pi, r)
                self._append_token(slot, int(nxt[j]))

    def _publish_block(self, st: _PagedSlot, pi: int, r: Request):
        """Hang slot page pi (now full and immutable) on the radix tree."""
        seq = r.prompt if pi * self.block_size + self.block_size <= st.plen \
            else np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        tokens = seq[pi * self.block_size:(pi + 1) * self.block_size]
        parent = st.chain[-1] if st.chain else None
        node, owned = self.pool.publish(parent, tokens, int(st.row[pi]),
                                        clock=self.step_count)
        if owned:
            st.private.remove(int(st.row[pi]))
        st.chain.append(node)

    # -- interleaved prefill (one slice per tick) ---------------------------

    def _job_init(self, gp: int):
        """Fresh transient (caches, h_last) for a gp-row job; the zeros are
        built on device by a per-group-size jit (O(log max_batch) compiles,
        no host->device transfer of a pool-sized buffer)."""
        fn = self._slice_inits.get(gp)
        if fn is None:
            api, pool_len = self.api, self.pool_len
            fn = self._meshed(jax.jit(
                lambda: api.prefill_slice_init(gp, pool_len),
                **({"out_shardings": (self._prefill_sh, self._repl)}
                   if self.mesh is not None else {})))
            self._slice_inits[gp] = fn
        return fn()

    def _start_jobs(self):
        """Dequeue admissible work into new prefill jobs. Slots are
        committed (deducted from capacity) here so two jobs never target
        the same future vacancy, but assigned only at install."""
        free = sum(1 for r in self.slots if r is None) - self._committed
        while free > 0 and self.queue:
            if self.paged:
                admitted = self._select_paged(free)
                if not admitted:
                    break
                a = self._paged_arrays(admitted)
                # a cached-prefix group can't slice: its attention reads
                # gathered context, so it runs as one blocking call —
                # still scheduled alongside decode like any other job
                job = _PrefillJob(admitted=admitted, toks=a["toks"],
                                  lens=a["lens"], blen=a["blen"],
                                  gp=a["gp"],
                                  monolithic=a["max_ctx_pages"] > 0,
                                  arrays=a)
            else:
                group = self.sched.select(self.queue, free,
                                          clock=self.step_count)
                if not group:
                    break
                for r in group:
                    self.queue.remove(r)
                toks, lens, blen, gp = self._group_arrays(group)
                job = _PrefillJob(admitted=[(r, [], []) for r in group],
                                  toks=toks, lens=lens, blen=blen, gp=gp,
                                  monolithic=False, arrays=None)
            reqs = [r for r, _, _ in job.admitted]
            c = min(self.slice_chunk, job.blen)
            job.todo = -(-int(job.lens.max()) // c) * c
            job.rank = min(slo_rank(r.slo) for r in reqs)
            job.arrival = min(r.arrival for r in reqs)
            job.t_start = self.tm.clock() if self.tm is not None else 0.0
            self._jobs.append(job)
            self._committed += len(reqs)
            self.stats["prefill_jobs"] += 1
            free = sum(1 for r in self.slots if r is None) - self._committed

    def _job_key(self, job: _PrefillJob):
        """Job service order: starved-first, then (SLO rank, arrival)."""
        limit = getattr(self.sched, "starvation_limit", None)
        starved = (limit is not None
                   and self.step_count - job.arrival > limit)
        return (0 if starved else 1, job.rank, job.arrival)

    def _advance_job(self, job: _PrefillJob) -> bool:
        """One unit of prefill work; True = job finished and installed."""
        if job.monolithic:
            logits, new = self._paged_prefill_call(job.arrays)
            self._install_paged(job.admitted, job.arrays, logits, new,
                                wave_t0=None, t_admit=job.t_start)
            return True
        t0 = self.tm.clock() if self.tm is not None else 0.0
        c = min(self.slice_chunk, job.blen)
        with self._phase("serve.dispatch"):
            if job.caches is None:
                job.caches, job.h_last = self._job_init(job.gp)
            job.h_last, job.caches = self._slice(
                self.params, job.caches,
                jnp.asarray(job.toks[:, job.pos:job.pos + c]), job.h_last,
                jnp.asarray(job.lens), jnp.asarray(job.pos, jnp.int32))
        job.pos += c
        self.stats["prefill_slices"] += 1
        if self.tm is not None:
            self.tm.prefill_slice(t0, n_reqs=len(job.admitted),
                                  tokens=c * job.gp, bucket=job.blen)
        if job.pos < job.todo:
            return False
        with self._phase("serve.dispatch"):
            logits, new = self._slice_finish(self.params, job.caches,
                                             job.h_last,
                                             jnp.asarray(job.lens))
        if self.paged:
            self._install_paged(job.admitted, job.arrays, logits, new,
                                wave_t0=None, t_admit=job.t_start)
        else:
            self._install_contig([r for r, _, _ in job.admitted],
                                 job.blen, job.gp, logits, new,
                                 wave_t0=None, t_admit=job.t_start)
        return True

    def _prefill_tick(self):
        """Interleave-mode admission: start jobs for queued work, then run
        up to ``slices_per_tick`` units of prefill beside this tick's
        decode batch. With no slot decoding there is nothing to starve, so
        the backlog drains freely until an install re-arms the decode
        loop."""
        self._start_jobs()
        idle = all(r is None for r in self.slots)
        n = self.slices_per_tick
        while self._jobs and (n > 0 or idle):
            job = min(self._jobs, key=self._job_key)
            n -= 1
            if self._advance_job(job):
                self._jobs.remove(job)
                self._committed -= len(job.admitted)
                # an install can finish instantly (max_new=1) and re-free
                # its slots — let newly-admissible work start now
                self._start_jobs()
                idle = all(r is None for r in self.slots)

    # -- engine ticks -------------------------------------------------------

    def step(self) -> bool:
        """One tick: admit into free slots, then one batched decode step over
        the full pool (or one draft/verify wave with spec_k > 0). Returns
        False once no slot is occupied (idle)."""
        if self.tm is None:
            return self._tick()
        with self.tm.phase("serve.step"):
            busy = self._tick()
        self.tm.tick_done(busy)
        return busy

    def _tick(self) -> bool:
        """step() without its serve.step span."""
        with self._phase("serve.admit"):
            if self.interleave:
                self._prefill_tick()
            else:
                self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            if self._jobs:
                # prefill still in flight (this tick did slice work, or an
                # install finished instantly): keep the clock moving and
                # report busy so callers keep ticking
                self.step_count += 1
                return True
            return False
        if self.spec_k:
            self._step_spec(active)
        else:
            self._step_decode(active)
        return True

    def _step_decode(self, active: list):
        """One batched decode step over the full pool."""
        t0 = self.tm.clock() if self.tm is not None else 0.0
        with self._phase("serve.dispatch"):
            logits, self.caches = self._decode(self.params, self.caches,
                                               jnp.asarray(self.next_tok))
        nxt = self._sample(logits, list(self.slots))
        self.step_count += 1
        self.stats["decode_steps"] += 1
        self.stats["occupied_slot_steps"] += len(active)
        with self._phase("serve.emit"):
            now = 0.0
            if self.tm is not None:
                now = self.tm.clock()
                self.tm.decode_tick(t0, n_active=len(active), now=now)
            for i in active:
                r = self.slots[i]
                if self.paged and self.prefix_on:
                    # the decode just inserted K/V at position
                    # plen+len(out)-1; publish the block it completed, if
                    # any
                    st = self._pstate[i]
                    cur = st.plen + len(r.out)   # cache len after this tick
                    if cur % self.block_size == 0:
                        self._publish_block(st, cur // self.block_size - 1,
                                            r)
                if self.tm is not None:
                    self.tm.tokens_emitted(r.rid, 1, now=now)
                self._append_token(i, int(nxt[i]))
            if self.tm is not None:
                self.tm.update_gauges(self._telemetry_gauges())

    def _step_spec(self, active: list):
        """One speculative wave: draft spec_k tokens through the binarized
        self-draft (sharing the target cache), verify all of them plus the
        pending token in one float pass, and keep the longest matching
        prefix + one correction/bonus token per slot.

        Token-identity with the plain engine holds by construction: the
        wave's j-th emission is drawn from *target* logits conditioned on
        an all-accepted history, using the request's own (rid, step)
        stream — the draft only decides how many of those emissions one
        wave can bank (1..spec_k+1 per slot)."""
        k = self.spec_k
        # pre-wave cache length per slot (invariant: plen + len(out) - 1;
        # next_tok's K/V is not yet inserted). Free slots pin to 0 so
        # their draft-scratch writes stay invisible and bounded; their
        # rid/step pins are arbitrary (their tokens are never read).
        base_len = np.zeros((self.max_batch,), np.int32)
        rids = np.zeros((self.max_batch,), np.int32)
        base_steps = np.zeros((self.max_batch,), np.int32)
        for i in active:
            r = self.slots[i]
            base_len[i] = len(r.prompt) + len(r.out) - 1
            rids[i] = r.rid
            base_steps[i] = len(r.out)

        # -- one fused launch: k scanned draft decodes (approximate K/V
        # appended past base_len), rewind, one float verify scoring k+1
        # positions with exact K/V, candidate selection from each
        # request's own (rid, step) stream
        t0 = self.tm.clock() if self.tm is not None else 0.0
        with self._phase("serve.dispatch"):
            tok_mat, cand, self.caches = self._spec_wave(
                self.params, self.draft_params, self.caches,
                jnp.asarray(self.next_tok), jnp.asarray(rids),
                jnp.asarray(base_steps), jnp.asarray(base_len))
        with self._phase("serve.sync"):
            tok_mat = np.asarray(tok_mat)               # (B, k+1)
            cand = np.asarray(cand)                     # (B, k+1)
        self.stats["spec_draft_launches"] += 1

        # -- accept/reject (host): longest draft prefix matching the
        # request's own-stream emissions, then one correction/bonus token
        wave: dict[int, list[int]] = {}
        new_lens = np.zeros((self.max_batch,), np.int32)
        for i in active:
            emitted = accept_wave(cand[i], tok_mat[i, 1:])
            wave[i] = emitted
            self.stats["spec_drafted"] += k
            self.stats["spec_accepted"] += len(emitted) - 1
            new_lens[i] = base_len[i] + len(emitted)
        # roll back before any bookkeeping: rejected positions fall past
        # len (and free slots to 0); paged _finish, which re-zeros its
        # slot, runs after this
        with self._phase("serve.dispatch"):
            self.caches = self._set_lens(self.caches, jnp.asarray(new_lens))
        self.step_count += 1
        self.stats["decode_steps"] += 1
        self.stats["spec_waves"] += 1
        self.stats["occupied_slot_steps"] += len(active)
        with self._phase("serve.emit"):
            self._emit_wave(active, wave, t0)

    def _emit_wave(self, active: list, wave: dict, t0: float):
        """Book one speculative wave's accepted tokens, slot by slot."""
        now = 0.0
        if self.tm is not None:
            now = self.tm.clock()
            self.tm.spec_wave(
                t0, n_active=len(active), k=self.spec_k,
                accepted=sum(len(w) - 1 for w in wave.values()), now=now)
        for i in active:
            r = self.slots[i]
            if self.tm is not None:
                # tokens actually emitted this wave: the accept rule's
                # output, cut at max_new or the first stop token — the
                # same rule the _append_token loop below applies
                n_emit, room = 0, r.max_new - len(r.out)
                for tok in wave[i]:
                    n_emit += 1
                    if n_emit >= room or int(tok) in r.stop_tokens:
                        break
                self.tm.tokens_emitted(r.rid, n_emit, now=now)
            for tok in wave[i]:
                if self.paged and self.prefix_on:
                    # same crossing rule as the plain tick: the wave's
                    # verify pass completed the block covering positions
                    # [cur - bs, cur) with exact K/V
                    st = self._pstate[i]
                    cur_len = st.plen + len(r.out)
                    if cur_len % self.block_size == 0:
                        self._publish_block(st,
                                            cur_len // self.block_size - 1,
                                            r)
                if self._append_token(i, int(tok)):
                    # finished (max_new / stop token): the rest of the
                    # wave is discarded — neither emitted nor counted
                    break
        if self.tm is not None:
            self.tm.update_gauges(self._telemetry_gauges())

    def _telemetry_gauges(self) -> dict:
        """Instantaneous cache-pressure / occupancy values, all host-side
        (slot table, queue, the paged pool's free list — never a device
        array). Every ratio is zero-division-guarded: a scrape before the
        first tick reads 0.0, not a crash."""
        occ = sum(1 for s in self.slots if s is not None)
        g = {"serve_slots_occupied": occ,
             "serve_queue_depth": len(self.queue),
             "serve_slot_occupancy": occ / self.max_batch
             if self.max_batch else 0.0}
        if self.interleave:
            g["serve_prefill_jobs"] = len(self._jobs)
        if self.paged:
            free = len(self.pool.free)
            g["kv_blocks_free"] = free
            g["kv_pool_occupancy"] = (1.0 - free / self.n_blocks
                                      if self.n_blocks else 0.0)
        else:
            g["kv_pool_occupancy"] = g["serve_slot_occupancy"]
        if self.prefix_on:
            hit = self.stats["cached_prompt_tokens"]
            tot = hit + self.stats["prefilled_tokens"]
            g["serve_prefix_hit_rate"] = hit / tot if tot else 0.0
        if self.spec_k:
            g["serve_spec_acceptance"] = self.acceptance_rate()
        return g

    def acceptance_rate(self) -> float:
        """Fraction of draft tokens the verify pass accepted."""
        d = self.stats["spec_drafted"]
        return self.stats["spec_accepted"] / d if d else 0.0

    def run(self) -> dict[int, list[int]]:
        """Drain queue and slots; returns rid -> generated ids (cumulative
        over the engine's lifetime, so arrivals between run() calls work)."""
        while self.step():
            pass
        return dict(self.results)

    def prefill_logits(self, tokens, seq_lens) -> jax.Array:
        """Last-token logits of one blocking prefill of right-padded
        ``tokens`` (B, S) through this engine's own jitted (and, under a
        mesh, sharded) prefill step. Pool, slots and queue are untouched:
        a parity probe across engines, not an admission."""
        logits, _ = self._prefill(self.params, jnp.asarray(tokens),
                                  jnp.asarray(seq_lens))
        return logits

    def utilization(self) -> float:
        """Mean fraction of occupied slots per decode step."""
        steps = self.stats["decode_steps"]
        if steps == 0:
            return 0.0
        return self.stats["occupied_slot_steps"] / (steps * self.max_batch)
