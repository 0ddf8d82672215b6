"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
checkpoint/restart fault tolerance (deliverable b).

Default is a short CPU-sized run; pass --steps 300 --d-model 512 for the
full ~100M-parameter exercise (slow on 1 CPU core, linear in steps).

  PYTHONPATH=src python examples/train_lm.py --steps 40
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.data import TokenPipeline, make_batch_fn
from repro.configs.base import RunShape
from repro.models import build_model
from repro.train.fault import StepMonitor, run_resumable
from repro.train.train_step import init_state, make_train_step
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = get_arch("olmo-1b").scaled(
        n_layers=args.layers, d_model=args.d_model, d_ff=4 * args.d_model,
        n_heads=args.d_model // 64, n_kv_heads=args.d_model // 64,
        vocab=8192, vocab_pad_mult=128, head_dim=64)
    api = build_model(cfg, remat="block")
    state = init_state(api, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"model: {cfg.name}-scaled  params={n/1e6:.1f}M  "
          f"steps={args.steps}  ckpt={args.ckpt}")

    step = jax.jit(make_train_step(api, lr_fn=lambda s: 3e-4))
    shape = RunShape("ex", args.seq, args.batch, "train")
    raw = make_batch_fn(cfg, shape)
    batch_fn = lambda s: {k: jnp.asarray(v) for k, v in raw(s).items()}

    mon = StepMonitor()
    t0 = time.perf_counter()
    state, last = run_resumable(step, state, batch_fn, steps=args.steps,
                                ckpt_dir=args.ckpt, ckpt_every=20,
                                monitor=mon)
    dt = time.perf_counter() - t0
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"done: step={last}  {tok_s:.0f} tok/s  "
          f"stragglers flagged={len(mon.stragglers)}")
    print("re-run the same command to resume from the checkpoint.")


if __name__ == "__main__":
    main()
