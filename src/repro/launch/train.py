"""Training launcher.

Single-host CPU (smoke/e2e):
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume auto

Multi-host TPU deployment (per host, under your cluster runner):
  python -m repro.launch.train --arch mistral-large-123b --shape train_4k \
      --coordinator <addr> --num-hosts 64 --host-id $HOST_ID

The multi-host path calls jax.distributed.initialize and builds the
production mesh; data loading is (seed, step)-deterministic per host
(no data service on the hot path).  XLA overlap flags for TPU are set
unless already present (compute/collective overlap).
"""
from __future__ import annotations

import argparse
import logging
import os


TPU_OVERLAP_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", choices=("auto", "none"), default="auto")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # multi-host deployment
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    args = ap.parse_args()

    from repro.launch import enable_compile_cache
    enable_compile_cache()
    if args.coordinator:
        os.environ.setdefault("XLA_FLAGS", TPU_OVERLAP_FLAGS)
        import jax
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_hosts,
                                   process_id=args.host_id)

    logging.basicConfig(level=logging.INFO)
    from repro.configs import get_config
    from repro.optim.adamw import AdamWConfig
    from repro.train.loop import TrainConfig, train

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(
        accum=args.accum, compress_grads=args.compress_grads,
        optim=AdamWConfig(lr=args.lr, total_steps=args.steps))
    if args.resume == "none" and args.ckpt_dir:
        import shutil
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    res = train(cfg, steps=args.steps, batch_size=args.batch,
                seq_len=args.seq, tcfg=tcfg, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, seed=args.seed)
    last = res["history"][-1]
    print(f"done: step {last['step']} loss {last['loss']:.4f} "
          f"restarts {res['restarts']} stragglers {len(res['watchdog'])}")


if __name__ == "__main__":
    main()
