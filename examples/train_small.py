"""Train a ~100M-param dense LM for a few hundred steps on CPU with the
full production train_step (AdamW + ZeRO-1 specs + remat + checkpointing),
demonstrating fault-tolerant restart.

    PYTHONPATH=src python examples/train_small.py [--steps 200]
"""

import argparse
import dataclasses
import os
import shutil
import time

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.training import optimizer as opt
from repro.training.data import SyntheticTokens
from repro.training.train_step import make_train_step

CKPT_DIR = "/tmp/repro_train_small"


def small_config():
    return ModelConfig(
        name="demo-100m", family="dense", n_layers=6, d_model=512,
        n_heads=8, n_kv_heads=4, d_ff=2048, vocab=8192, dtype="float32")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--fresh", action="store_true")
    args = ap.parse_args()

    if args.fresh and os.path.exists(CKPT_DIR):
        shutil.rmtree(CKPT_DIR)

    cfg = small_config()
    model = build_model(cfg)
    print(f"params: {model.bytes()/4/1e6:.1f}M")
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init_state(params)

    ckpt = CheckpointManager(CKPT_DIR, keep=2)
    start = 0
    if ckpt.latest_step() is not None:
        (params, state), manifest = ckpt.restore((params, state))
        start = manifest["step"]
        print(f"restored checkpoint at step {start} (fault-tolerant resume)")

    step_fn = jax.jit(make_train_step(
        model, opt.AdamWConfig(lr=1e-3, warmup_steps=20,
                               total_steps=args.steps),
        remat="none", grad_dtype=None))
    data = iter(SyntheticTokens(cfg, args.batch, args.seq, seed=1))

    t0 = time.time()
    for step in range(start, args.steps):
        batch = next(data)
        params, state, metrics = step_fn(params, state, batch)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0):.1f}s)")
        if step and step % 50 == 0:
            path = ckpt.save(step, (params, state))
            print(f"  checkpoint -> {path}")
    ckpt.save(args.steps, (params, state))
    print("done; rerun without --fresh to resume from the last checkpoint")


if __name__ == "__main__":
    main()
