"""chip_smoke.py off the chip: ``--smoke`` rehearses every phase on the CPU
(smoke config, interpret-mode kernels); without it the script refuses to
run anywhere but on a TPU. Also the compile-cache helper the entry
points share."""

import json
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(tmp_path, *args, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)


def _last_json(proc):
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1])


def test_smoke_runs_end_to_end_on_cpu(tmp_path):
    proc = _run(tmp_path, "--smoke")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert _last_json(proc) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert "serve: 8 requests x 32 tokens" in proc.stdout
    assert "equal int32" in proc.stdout


def test_four_chip_phase_on_virtual_devices(tmp_path):
    proc = _run(tmp_path, "--smoke", "--four-chips", devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert _last_json(proc)["device"]["count"] == 4
    assert "float-FFN twin prefill logits" in proc.stdout


def test_without_smoke_refuses_the_cpu(tmp_path):
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU visible" in proc.stderr


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The environment's directory wins and nothing else is set; unset,
    the cache goes to .jax_cache/ at the root of the checkout."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
