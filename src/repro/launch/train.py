"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt [--resume]

On this CPU container it runs real steps on reduced configs (--smoke) or
full configs at your peril; on a TPU pod the same entry point picks up the
production mesh. Composes: data pipeline -> sharded train step ->
fault-tolerance supervisor (SIGTERM drain, retries, straggler watchdog) ->
async checkpointing with elastic restore. Run as a module, it keeps
XLA's persistent compilation cache where launch/compile_cache.py says.
"""

from __future__ import annotations

import argparse
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, smoke_config
from repro.data.synthetic import SyntheticTokens
from repro.distributed.sharding import set_logical_rules
from repro.launch import specs as S
from repro.launch.mesh import make_mesh
from repro.models import get_model
from repro.optim import adamw_init
from repro.train import checkpoint as C
from repro.train.fault_tolerance import TrainSupervisor
from repro.train.step import make_train_step

log = logging.getLogger("repro.train")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS + ["beanna-mnist"],
                    default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="e.g. '2x2:data,model' (default: single device)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = get_model(cfg)

    mesh = None
    if args.mesh:
        shape_s, axes_s = args.mesh.split(":")
        mesh = make_mesh([int(x) for x in shape_s.split("x")],
                         axes_s.split(","))

    key = jax.random.PRNGKey(0)
    params = api.init(key)
    opt = adamw_init(params, moment_dtype=jnp.dtype(cfg.opt_moment_dtype))
    data = SyntheticTokens(cfg.vocab, args.seq, args.batch, seed=0)

    start_step = 0
    if args.resume and args.ckpt_dir:
        last = C.latest_step(args.ckpt_dir)
        if last is not None:
            state, meta = C.restore(args.ckpt_dir, last,
                                    {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            data.restore(meta["data_state"])
            start_step = meta["step"]
            log.info("resumed from step %d", start_step)

    step_fn = make_train_step(api, cfg, peak_lr=args.lr,
                              warmup=max(args.steps // 20, 1),
                              total=args.steps)
    if mesh is not None:
        from repro.configs.base import ShapeSpec
        sh = ShapeSpec("cli", args.seq, args.batch, "train")
        rules = S.mesh_rules_for(cfg, mesh, sh)
        set_logical_rules(mesh, rules)
        p_abs, p_sh = S.param_shardings(api, mesh, rules)
        o_abs, o_sh = S.opt_shardings(api, cfg, p_abs, p_sh, mesh)
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(opt, o_sh)
        step_fn = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    def checkpoint_fn(state, i):
        if not args.ckpt_dir:
            return
        params, opt = state
        C.save_async(args.ckpt_dir, start_step + max(i, 0),
                     {"params": params, "opt": opt},
                     meta={"data_state": data.state()})

    def wrapped_step(params, opt, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return step_fn(params, opt, batch)

    sup = TrainSupervisor(wrapped_step, checkpoint_fn=checkpoint_fn)
    sup.install_signals()

    t0 = time.time()
    (params, opt), history = sup.run(
        (params, opt), data, n_steps=args.steps,
        ckpt_every=args.ckpt_every)
    dt = time.time() - t0
    if history:
        for i in range(0, len(history), args.log_every):
            log.info("step %d loss %.4f", start_step + i,
                     history[i]["loss"])
        log.info("final loss %.4f  (%d steps in %.1fs, %.2f s/step, "
                 "stragglers=%d)", history[-1]["loss"], len(history), dt,
                 dt / len(history), sup.watchdog.straggler_steps)
    if args.ckpt_dir:
        checkpoint_fn((params, opt), len(history))
        C.wait_for_saves()                     # join async writers before exit
    return history


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
