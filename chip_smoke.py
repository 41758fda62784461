"""Chip smoke test: serve stablelm-3b at its full published width on a TPU.

One process, no fallback. Run from the root of a checkout:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: --mesh 4 vs one device
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke   # CPU rehearsal

One chip: serves 8 greedy requests (prompts of 128/256/512 tokens, 32 new
tokens each) through ``repro.launch.serve.main`` at 32 layers x d_model
2560 with random weights from a fixed seed, checks the main-path Pallas
kernels on the chip at the model's widths (the packed int8 FFN matmul is
exactly equal in int32 to its XLA twin; the flash prefill kernel at B > 1
agrees with the score-materializing reference within FLASH_ATOL), and
checks that the lowered prefill step holds ``tpu_custom_call``.

``--four-chips`` runs only the tensor-parallel phase: the same requests
with ``--mesh 4``, per-device residency of the params and KV pool, and
prefill logits of a 4-way engine against a single-device engine in the
same process (on the float-FFN twin of the model; see ``four_chips``).

``--smoke`` swaps in the smoke config and allows the CPU (Pallas kernels
then run in interpret mode); it rehearses control flow only. Without it,
a process that sees no TPU exits non-zero before any phase. Any failed
check exits non-zero; the last stdout line, a JSON object naming the
device, is printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "stablelm-3b"
PROMPT_LENS = (128, 256, 512)
REQUESTS = 8
MAX_BATCH = 8
MAX_NEW = 32
# bf16 flash kernel vs the xla_ref oracle, max |difference| of outputs
# whose entries are O(1): both round the softmax weights to bf16 before
# the PV matmul, but the kernel rescales block by block, so roundings
# differ by a few bf16 ulps
FLASH_ATOL = 3e-2
# --mesh 4 vs one device, ||logits_mesh - logits_1|| / ||logits_1|| over
# a bf16 prefill batch of the float-FFN twin (four_chips says why not the
# served binary model); sharded matmuls reduce in another order. 0.021 on
# 4 virtual CPU devices at 32 layers
MESH_LOGITS_RTOL = 5e-2
# per-device share of params + KV pool under --mesh 4: 1/4 plus the
# replicated leaves (norm scales, per-output binary scales)
MESH_SHARE_MAX = 0.3
# max / min peak HBM over the four devices after the --mesh 4 serve
MESH_PEAK_SKEW = 1.5


def log(msg: str):
    print(msg, flush=True)


def serve_argv(smoke: bool, *extra: str) -> list[str]:
    return (["--arch", ARCH, "--requests", str(REQUESTS),
             "--prompt-lens", ",".join(map(str, PROMPT_LENS)),
             "--max-new", str(MAX_NEW), "--max-batch", str(MAX_BATCH),
             "--temperature", "0"]
            + (["--smoke"] if smoke else []) + list(extra))


def check_results(results: dict, vocab: int):
    assert len(results) == REQUESTS, f"{len(results)} of {REQUESTS} served"
    for rid, toks in sorted(results.items()):
        assert len(toks) == MAX_NEW, f"request {rid}: {len(toks)} tokens"
        assert all(0 <= t < vocab for t in toks), \
            f"request {rid}: token outside [0, {vocab}): {toks}"
    log(f"serve: {len(results)} requests x {MAX_NEW} tokens, all in vocab "
        f"{vocab}")


def compile_seconds_counter() -> list[float]:
    """Running sum of XLA backend compile time in this process."""
    total = [0.0]

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


def probe_batch(vocab: int, seed: int = 1):
    """(MAX_BATCH, max prompt) right-padded prompts and their lengths."""
    rng = np.random.default_rng(seed)
    lens = np.array([PROMPT_LENS[i % len(PROMPT_LENS)]
                     for i in range(MAX_BATCH)], np.int32)
    toks = np.zeros((MAX_BATCH, max(PROMPT_LENS)), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, vocab, n)
    return toks, lens


def check_int8_kernel(cfg, interpret: bool):
    """pallas_int8 == binary_matmul_int8, exact int32, FFN in and out."""
    from repro.core.binarize import packed_len
    from repro.kernels.binary_matmul import binary_matmul_int8
    from repro.kernels.int8_matmul import int8_matmul_pallas
    for m in (8, 256):
        for k, n in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
            ka, kw = jax.random.split(jax.random.PRNGKey(m + k))
            a = jnp.where(jax.random.bernoulli(ka, 0.5, (m, k)), 1,
                          -1).astype(jnp.int8)
            pw = jax.random.bits(kw, (n, packed_len(k)), jnp.uint32)
            got = np.asarray(int8_matmul_pallas(a, pw, interpret=interpret))
            want = np.asarray(binary_matmul_int8(a, pw, k=k))
            assert got.dtype == want.dtype == np.int32
            assert np.array_equal(got, want), \
                f"int8 M={m} K={k} N={n}: {np.sum(got != want)} differ"
            log(f"kernel pallas_int8 M={m} K={k} N={n}: equal int32")


def check_flash_kernel(cfg, interpret: bool):
    """pallas_flash vs xla_ref at B=4 with ragged kv_len, bf16."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.nn.attention import prefill_attention
    b, s, h, d = 4, 256, cfg.n_heads, cfg.kv_head_dim()
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
               for kk in jax.random.split(jax.random.PRNGKey(2), 3))
    kv_len = jnp.array([256, 200, 129, 17], jnp.int32)
    got = flash_attention_pallas(q, k, v, causal=True, kv_len=kv_len,
                                 interpret=interpret)
    want = prefill_attention(q, k, v, causal=True, kv_len=kv_len,
                             impl="xla_ref")
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    log(f"kernel pallas_flash B={b} S={s} H={h} D={d}: max|diff| vs xla_ref "
        f"{err} (limit {FLASH_ATOL})")
    assert err <= FLASH_ATOL, err


def check_prefill_lowering(api, p_abs, max_len: int):
    """The engine's prefill step, lowered for this backend, calls Pallas
    kernels (flash attention, packed int8 FFN), not references."""
    step = jax.jit(lambda p, toks, sl: api.prefill(
        p, {"tokens": toks}, max_len=max_len, seq_lens=sl))
    text = step.lower(
        p_abs, jax.ShapeDtypeStruct((MAX_BATCH, PROMPT_LENS[0]), jnp.int32),
        jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32)).as_text()
    n = text.count("tpu_custom_call")
    assert n, "no tpu_custom_call in the lowered prefill step"
    log(f"prefill step lowering: {n} tpu_custom_call sites")


def param_bytes(p_abs) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(p_abs))


def peak_bytes(dev):
    stats = dev.memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def one_chip(smoke: bool, on_tpu: bool, compile_s: list[float]):
    from repro.configs import get_config, smoke_config
    from repro.launch import serve
    from repro.models import get_model

    cfg = smoke_config(ARCH) if smoke else get_config(ARCH)
    api = get_model(cfg)
    p_abs = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    log(f"config {cfg.name}: layers {cfg.n_layers} d_model {cfg.d_model} "
        f"heads {cfg.n_heads} d_ff {cfg.d_ff} vocab {cfg.vocab}; "
        f"param bytes {param_bytes(p_abs)}")

    t0 = time.perf_counter()
    results = serve.main(serve_argv(smoke))
    wall = time.perf_counter() - t0
    check_results(results, cfg.vocab)
    log(f"serve wall seconds (init + compile + requests, informational): "
        f"{wall}")

    interpret = not on_tpu
    check_int8_kernel(cfg, interpret)
    check_flash_kernel(cfg, interpret)
    if on_tpu:
        max_len = max(PROMPT_LENS) + MAX_NEW + 8
        check_prefill_lowering(api, p_abs, max_len)
    else:
        log("prefill step lowering: not checked off the chip (interpret "
            "mode lowers kernels to plain ops)")
    log(f"compile seconds: {compile_s[0]}")
    log(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


def shard_bytes_per_device(tree) -> dict:
    """Bytes each device holds of the arrays in ``tree``, from their
    shardings (touching ``.addressable_shards[i].data`` would itself
    create live per-shard arrays)."""
    out: dict = {}
    for x in jax.tree.leaves(tree):
        n = math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for d in x.sharding.addressable_devices:
            out[d] = out.get(d, 0) + n
    return out


def four_chips(smoke: bool, on_tpu: bool, compile_s: list[float]):
    from repro.configs.base import PrecisionPolicy
    from repro.launch import serve
    from repro.models import get_model
    from repro.serving import ServeEngine

    devs = jax.devices()
    assert len(devs) >= 4, f"--four-chips needs 4 devices, {len(devs)} seen"
    args, cfg, eng, tm = serve.build(serve_argv(smoke, "--mesh", "4"))
    held = (eng.params, eng.caches)
    total = sum(x.nbytes for x in jax.tree.leaves(held))
    per_dev = shard_bytes_per_device(held)
    live = shard_bytes_per_device(jax.live_arrays())
    for d in devs[:4]:
        share = per_dev.get(d, 0) / total
        log(f"mesh4 device {d.id}: params + KV pool shard bytes "
            f"{per_dev.get(d, 0)} of {total} ({share:.4f}); all live "
            f"arrays {live.get(d, 0)}; bytes_in_use "
            f"{(d.memory_stats() or {}).get('bytes_in_use')}; peak "
            f"{peak_bytes(d)}")
        assert 0.25 <= share <= MESH_SHARE_MAX, (d.id, share)

    check_results(serve.drive(args, cfg, eng, tm), cfg.vocab)
    peaks = [peak_bytes(d) for d in devs[:4]]
    log(f"peak_bytes_in_use per device after the --mesh 4 serve: {peaks}")
    if on_tpu:
        # nothing — init, pool, prefill, decode — ever lands whole on
        # one device: peaks stay within MESH_PEAK_SKEW of each other
        assert max(peaks) <= MESH_PEAK_SKEW * min(peaks), peaks
    mesh = eng.mesh
    del eng, held

    # Logits: with random weights the served model is chaotic under
    # reduction reordering — one near-zero activation that changes sign
    # in a binary FFN moves every later layer — so two correct lowerings
    # of it disagree at the logits (relative L2 0.97 at 32 layers on 4
    # virtual CPU devices). Tensor parallelism is therefore compared on
    # the float-FFN twin at the same widths, a well-conditioned function.
    twin = get_model(cfg.replace(policy=PrecisionPolicy()))
    params = serve.init_params(twin, mesh)
    toks, lens = probe_batch(cfg.vocab)
    max_len = max(PROMPT_LENS) + MAX_NEW + 8
    logits_mesh = np.asarray(ServeEngine(
        twin, params, max_batch=MAX_BATCH, max_len=max_len,
        mesh=mesh).prefill_logits(toks, lens), np.float32)
    logits_one = np.asarray(ServeEngine(
        twin, jax.device_put(params, devs[0]), max_batch=MAX_BATCH,
        max_len=max_len).prefill_logits(toks, lens), np.float32)
    rel = float(np.linalg.norm(logits_mesh - logits_one)
                / np.linalg.norm(logits_one))
    agree = float(np.mean(logits_mesh.argmax(-1) == logits_one.argmax(-1)))
    log(f"mesh4 vs one device, float-FFN twin prefill logits: relative L2 "
        f"{rel} (limit {MESH_LOGITS_RTOL}); argmax agreement {agree}")
    assert rel <= MESH_LOGITS_RTOL, rel
    log(f"compile seconds: {compile_s[0]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smoke config; allows the CPU (rehearsal only)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --mesh 4 phase (needs 4 devices)")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.smoke:
        sys.exit(f"chip_smoke: no TPU visible (platform {dev.platform!r}); "
                 "only --smoke runs off the chip")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    compile_s = compile_seconds_counter()
    if args.four_chips:
        four_chips(args.smoke, on_tpu, compile_s)
    else:
        one_chip(args.smoke, on_tpu, compile_s)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
