"""Training launcher: any ``--arch`` (full or --reduced), synthetic LM
data, AdamW (+WSD where the arch prescribes it), async fault-tolerant
checkpointing with automatic resume.

CPU example (minutes):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.1-8b --reduced \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
It trains on one device.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.data.lm_data import SyntheticLM
from repro.models import init_params
from repro.training.checkpoint import (AsyncCheckpointer, latest_step,
                                       restore_checkpoint)
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.1-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg, layers_per_stage=2, d_model=128, d_ff=256,
                            vocab=512)
    print(f"arch={cfg.name} params~{cfg.param_count() / 1e6:.1f}M "
          f"schedule={cfg.lr_schedule}")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 5),
                          total_steps=args.steps,
                          schedule=("wsd" if cfg.lr_schedule == "wsd"
                                    else "cosine"))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, ce_chunk=64))
    data = SyntheticLM(cfg.vocab_size, seed=0)

    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        start = latest_step(args.ckpt_dir)
        state = restore_checkpoint(args.ckpt_dir, start)
        params, opt = state["params"], state["opt"]
        params = jax.tree.map(jnp.asarray, params)
        opt = jax.tree.map(jnp.asarray, opt)
        print(f"resumed from step {start}")
    else:
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        opt = init_opt_state(params)

    ckpt = AsyncCheckpointer()
    t0 = time.time()
    losses = []
    for step in range(start, args.steps):
        toks, labels, mask = data.batch(step, args.batch, args.seq)
        params, opt, metrics = step_fn(params, opt, jnp.asarray(toks),
                                       jnp.asarray(labels),
                                       jnp.asarray(mask))
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            print(f"step {step + 1:5d} loss={losses[-1]:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time() - t0) / (step - start + 1):.2f}s/step)")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1,
                      {"params": params, "opt": opt})
    ckpt.wait()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}); "
          f"uniform-entropy baseline {np.log(cfg.vocab_size):.3f}")
    return losses


if __name__ == "__main__":
    main()
