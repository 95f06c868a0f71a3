"""Serving launcher: batched generation with the Engine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
      --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_config
    from repro.data.pipeline import extra_inputs
    from repro.models import model as M
    from repro.serve.engine import Engine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = M.init(cfg, key)
    max_seq = args.max_seq or (args.prompt_len + args.gen + 8)
    eng = Engine(cfg, params, max_batch=args.batch, max_seq=max_seq,
                 temperature=args.temperature)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 2,
                                 cfg.vocab_size)
    extra = extra_inputs(cfg, args.batch, args.seed)
    t0 = time.time()
    out = eng.generate(prompts, args.gen, extra or None)
    dt = time.time() - t0
    tput = args.batch * args.gen / dt
    print(f"generated {out.shape} in {dt:.2f}s ({tput:.1f} tok/s)")
    for row in out[: min(2, args.batch)]:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
