"""End-to-end LM training example with checkpoint/restart + GSE-SEM
gradient compression.

Defaults to a fast CPU-sized model; ``--model-100m`` trains a ~100M-param
granite-family config for a few hundred steps (slow on CPU, the shape a
TPU pod would run via launch/train.py).

  PYTHONPATH=src python examples/train_lm.py --steps 60
"""
import argparse
import dataclasses
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp

from repro import compile_cache, configs
from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.train import build
from repro.models.config import ModelConfig


def model_100m() -> ModelConfig:
    return ModelConfig(
        name="lm_100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32000,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--model-100m", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    args = ap.parse_args()

    cfg = model_100m() if args.model_100m else configs.get_config(
        "granite_3_2b", smoke=True)
    n_params = None

    state, step_fn = build(cfg, args.steps, lr=1e-3,
                           grad_compress=args.grad_compress)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"model {cfg.name}: {n_params/1e6:.1f}M params "
          f"(grad_compress={args.grad_compress})")

    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                    global_batch=8, seed=0,
                                    d_model=cfg.d_model))
    with tempfile.TemporaryDirectory() as ckdir:
        losses = []
        for step in range(args.steps):
            state, m = step_fn(state, pipe.batch_at(step))
            losses.append(float(m["loss"]))
            if step % 10 == 0:
                print(f"step {step:4d} loss {losses[-1]:.4f}")
            if (step + 1) % 25 == 0:
                ckpt.save_async(ckdir, state, step + 1)
        ckpt.wait_pending(ckdir)
        first, last = losses[0], sum(losses[-5:]) / 5
        print(f"\nloss: {first:.4f} -> {last:.4f} "
              f"({'LEARNING' if last < first else 'NOT LEARNING'})")
        saved = ckpt.latest_step(ckdir)
        print(f"latest checkpoint step: {saved}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
