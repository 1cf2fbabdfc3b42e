"""Training driver (host-scale run of the production stack).

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --reduced \
      --steps 100 --batch 8 --seq 256 --ckpt /tmp/ckpt

On the real pod this module is launched per-host with jax.distributed;
here it runs the same code on the host device set (see examples/train_lm.py
for the ~100M-parameter end-to-end run).
"""
from __future__ import annotations

import argparse

from repro.configs.base import get_arch
from repro.data.pipeline import Batcher, DataConfig
from repro.launch.compile_cache import place_compile_cache
from repro.models.model import build_model
from repro.train.loop import LoopConfig, run_training
from repro.train.step import TrainHParams


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (host runs)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--micro-steps", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    place_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    hp = TrainHParams(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps, micro_steps=args.micro_steps)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    loop = LoopConfig(total_steps=args.steps, checkpoint_dir=args.ckpt,
                      checkpoint_every=args.ckpt_every)
    out = run_training(model, hp, loop, iter(Batcher(data_cfg)))
    final = out["history"][-1] if out["history"] else {}
    print(f"[train] done: {final}")


if __name__ == "__main__":
    main()
