"""Serving driver: loads (or inits) a model and serves batched requests
through the ServeEngine (prefill + jit'd decode loop).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.models import model as M
from repro.serve import ServeEngine, GenerationConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if not cfg.has_decoder:
        raise SystemExit(f"{cfg.name} has no decoder")
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab,
                           (args.batch, args.prompt_len)).astype(np.int32)
    kw = {}
    if cfg.family == "encdec":
        kw["enc_frames"] = rng.standard_normal(
            (args.batch, cfg.enc_seq, cfg.d_model)).astype(np.float32)
    if cfg.family == "vlm":
        kw["extra_embeds"] = rng.standard_normal(
            (args.batch, cfg.vis_seq, cfg.d_model)).astype(np.float32)

    engine = ServeEngine(cfg, params,
                         max_len=args.prompt_len + args.max_new + 8)
    gen = GenerationConfig(max_new_tokens=args.max_new,
                           temperature=args.temperature)
    t0 = time.time()
    out = engine.generate(prompts, gen, **kw)
    dt = time.time() - t0
    n_tok = out.size
    print(f"[serve] {cfg.name}: generated {n_tok} tokens for "
          f"{args.batch} requests in {dt:.2f}s ({n_tok/dt:.1f} tok/s)")
    print("[serve] first request tokens:", out[0][:16].tolist())


if __name__ == "__main__":
    main()
