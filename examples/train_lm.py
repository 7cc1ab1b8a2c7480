"""End-to-end driver: train a ~100M-parameter decoder LM for a few hundred
steps on the synthetic pipeline and watch the loss drop.

By default this runs a genuinely ~100M-param qwen3-family model for 200
steps (CPU: expect ~20-40 min).  ``--fast`` drops to the reduced config +
60 steps for a quick check.

    PYTHONPATH=src python examples/train_lm.py --fast
"""
import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs.base import get_config
from repro.data import SyntheticLM, make_train_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.optim import cosine_schedule


def hundred_m_config():
    base = get_config("qwen3-1.7b")
    return dataclasses.replace(
        base, name="qwen3-100m", n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=4, head_dim=64, d_ff=1536, vocab=8192,
        dtype="float32", param_dtype="float32")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)

    if args.fast:
        cfg = get_config("qwen3-1.7b").reduced()
        steps = args.steps or 60
        seq = 64
    else:
        cfg = hundred_m_config()
        steps = args.steps or 200
        seq = args.seq

    model = Model(cfg)
    print(f"arch={cfg.name} params={model.param_count():,} steps={steps}")
    state = model.init_train_state(jax.random.key(0))
    sched = lambda s: cosine_schedule(s, peak_lr=args.lr, warmup_steps=20,
                                      total_steps=steps)
    step_fn = jax.jit(lambda s, b: model.train_step(s, b, lr_schedule=sched),
                      donate_argnums=(0,))
    it = make_train_iterator(SyntheticLM(cfg.vocab, seq, seed=0), args.batch)

    losses = []
    t0 = time.time()
    for step in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % 10 == 0 or step == steps - 1:
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"({(time.time() - t0) / (step + 1):.2f} s/step)")
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, steps, state.params)
        print(f"saved checkpoint to {args.ckpt_dir}")
    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"loss: {first:.4f} -> {last:.4f}")
    assert last < first, "training must reduce loss"
    print("train_lm OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
