"""Training launcher.

On real TPU hardware this drives the full production configs through the
pjit train step with the DESIGN.md §4 sharding; on CPU (this container) use
``--reduced`` for smoke-scale runs.  Example:

  PYTHONPATH=src python -m repro.launch.train --arch nlg-350m-moe128 \
      --reduced --steps 100 --batch 16 --seq 128
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.checkpoint import ckpt
from repro.configs.registry import get_config, make_reduced
from repro.data.pipeline import data_stream
from repro.launch.runtime import device_label, enable_compile_cache
from repro.training.trainer import TrainConfig, train_loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="2-layer tiny variant (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--vocab", type=int, default=0, help="override vocab (synthetic data)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--moe-impl", default=None, choices=[None, "einsum", "dense", "ep"])
    args = ap.parse_args()
    enable_compile_cache()
    print(f"device: {device_label()}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    if args.vocab:
        cfg = cfg.replace(vocab_size=args.vocab)
    if args.moe_impl:
        cfg = cfg.replace(moe_impl=args.moe_impl)

    tc = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1), decay_steps=args.steps)
    it = data_stream(cfg.vocab_size, args.batch, args.seq, seed=args.seed)
    params, opt_state, history = train_loop(cfg, tc, it, args.steps, seed=args.seed)

    if args.ckpt_dir:
        ckpt.save(os.path.join(args.ckpt_dir, "params"), params, step=args.steps)
        with open(os.path.join(args.ckpt_dir, "history.json"), "w") as f:
            json.dump(history, f, indent=1)
        print(f"saved checkpoint to {args.ckpt_dir}")
    print(f"final loss: {history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
