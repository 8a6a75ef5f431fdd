"""End-to-end training driver with checkpoint/restart fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 200 --batch 8 --seq 128 --reduced --ckpt-dir /tmp/ckpt

Fault tolerance: periodic async checkpoints; on start, resumes from the
latest step if a checkpoint exists (synthetic data is a pure function of
step, so the stream resumes exactly). A step-time watchdog flags straggler
steps (> straggler_factor x rolling median) — on real multi-host deploys
that signal feeds the controller's replace-node policy; here it logs.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import SyntheticLM
from repro.launch import cache as compile_cache
from repro.models import decoder, encdec
from repro.models.decoder import RunFlags
from repro.optim import adamw
from repro.train.step import TrainConfig, train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-blocking", action="store_true",
                    help="synchronous saves (deterministic tests)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--die-at-step", type=int, default=None,
                    help="failure injection: hard-exit at this step")
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    ocfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=20,
                             total_steps=args.steps)
    tcfg = TrainConfig(optimizer=ocfg, microbatches=args.microbatches,
                       flags=RunFlags(remat="none"))

    key = jax.random.PRNGKey(0)
    api = encdec if cfg.family == "encdec" else decoder
    params = api.init(key, cfg)
    opt_state = adamw.init(params, ocfg)

    data = SyntheticLM(
        cfg.vocab, args.seq, args.batch,
        frames_dim=cfg.d_model if cfg.family == "encdec" else None,
        embeds_len=args.seq // 4 if cfg.input_mode == "vl" else 0,
        embeds_dim=cfg.d_model if cfg.input_mode == "vl" else None)

    start_step = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr is not None and mgr.latest_step() is not None:
        start_step = mgr.latest_step()
        state = mgr.restore(start_step,
                            {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        print(f"[train] resumed from step {start_step}")

    step_fn = jax.jit(
        lambda p, o, b: train_step(p, o, b, cfg, tcfg),
        donate_argnums=(0, 1))

    times = []
    losses = []
    it = data.iterator(start_step)
    for step in range(start_step, args.steps):
        batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.family == "encdec":
            batch["frames"] = batch["frames"].astype(jnp.bfloat16)
        if "embeds" in batch:
            batch["embeds"] = batch["embeds"].astype(jnp.bfloat16)
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        times.append(dt)
        losses.append(loss)
        if len(times) > 8:
            med = float(np.median(times[-32:]))
            if dt > args.straggler_factor * med and step > start_step + 3:
                print(f"[watchdog] straggler step {step}: {dt:.3f}s "
                      f"(median {med:.3f}s)")
        if step % args.log_every == 0:
            print(f"[train] step {step} loss {loss:.4f} "
                  f"({dt:.3f}s/step)", flush=True)
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state},
                     blocking=args.ckpt_blocking)
        if args.die_at_step is not None and step == args.die_at_step:
            print(f"[train] injected failure at step {step}", flush=True)
            import os
            os._exit(42)

    if mgr is not None:
        mgr.save(args.steps, {"params": params, "opt": opt_state},
                 blocking=True)
        mgr.wait()
    print(f"[train] done. first loss {losses[0]:.4f} "
          f"last loss {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
