"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train \
      --arch gemma-2b --reduced --steps 200 --batch 8 --seq 64

Runs the full production loop — data pipeline, jit'd train step,
checkpoint/restart, preemption guard, straggler watchdog — at whatever
scale the current devices allow (reduced configs on CPU; full configs
on a pod with the same code path).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np
import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.models import model as M
from repro.data import SyntheticTokens
from repro.train import (TrainConfig, make_train_step, make_optimizer,
                         CheckpointManager, PreemptionGuard, StepWatchdog)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    tc = TrainConfig(optimizer=args.optimizer, learning_rate=args.lr,
                     warmup_steps=max(args.steps // 20, 5),
                     total_steps=args.steps, microbatch=args.microbatch)
    opt = make_optimizer(tc)

    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    opt_state = opt.init(params)
    n_par = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {cfg.name} ({'reduced' if args.reduced else 'full'}): "
          f"{n_par/1e6:.1f}M params, {len(jax.devices())} device(s)")

    data = SyntheticTokens(cfg, batch=args.batch, seq=args.seq)
    step_fn = jax.jit(make_train_step(cfg, tc, opt=opt), donate_argnums=(0, 1))

    mgr = CheckpointManager(Path(args.ckpt_dir) / cfg.name, keep=3)
    start = 0
    if args.resume:
        latest = mgr.latest()
        if latest is not None:
            (params, opt_state), _ = mgr.restore(
                latest, (params, opt_state))
            start = latest
            print(f"[train] resumed from step {latest}")

    guard = PreemptionGuard()
    watchdog = StepWatchdog()
    log = []
    t_start = time.time()
    for step in range(start, args.steps):
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state,
                                             data.batch_at(step))
        dt = time.time() - t0
        watchdog.record(step, dt)
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            tokens_s = args.batch * args.seq / dt
            print(f"[train] step {step:5d} loss {m['loss']:.4f} "
                  f"nll {m['nll']:.4f} gnorm {m['grad_norm']:.3f} "
                  f"lr {m['lr']:.2e} {tokens_s:,.0f} tok/s")
            log.append({"step": step, **m, "tokens_per_s": tokens_s})
        if (step + 1) % args.save_every == 0 or guard.should_stop:
            mgr.save(step + 1, (params, opt_state))
            if guard.should_stop:
                print("[train] preemption requested: checkpointed, exiting")
                break

    mgr.save(args.steps, (params, opt_state))
    out = {"config": cfg.name, "steps": args.steps,
           "wall_s": time.time() - t_start, "log": log,
           "stragglers": watchdog.straggler_steps}
    Path("experiments").mkdir(exist_ok=True)
    with open(f"experiments/train_{cfg.name}.json", "w") as f:
        json.dump(out, f, indent=1)
    print(f"[train] done in {out['wall_s']:.1f}s; "
          f"final loss {log[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
