"""Training driver.

Two modes:
  --smoke      reduced config, real training on CPU (examples use this)
  (default)    full config on the production mesh — requires hardware.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch yi_34b --smoke \
      --steps 200 --ckpt-dir /tmp/ck --io tam
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager, HostCollectiveIO
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.launch.steps import make_optimizer, make_train_step
from repro.models import transformer as T
from repro.models.config import reduced
from repro.models.sharding import unsharded
from repro.optim import warmup_cosine
from repro.runtime import HeartbeatMonitor, TrainLoop, TrainLoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_34b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--io", default="tam", choices=["tam", "twophase"])
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (e.g. ~100M-param example)")
    ap.add_argument("--n-layers", type=int, default=None)
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.smoke:
        over = {}
        if args.d_model:
            over.update(d_model=args.d_model,
                        head_dim=max(args.d_model // 8, 16), n_heads=8,
                        n_kv_heads=min(
                            4, cfg.n_kv_heads) if cfg.n_kv_heads else 0,
                        d_ff=4 * args.d_model if cfg.d_ff else 0,
                        vocab=8192)
        if args.n_layers:
            per = cfg.block_period
            over["n_layers"] = -(-args.n_layers // per) * per
        cfg = reduced(cfg, **over)
    plan = unsharded()
    opt = make_optimizer(args.arch)

    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    opt_state = opt.init(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"steps={args.steps}")

    lr_fn = warmup_cosine(args.lr, warmup=20, total=args.steps)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(T.loss_fn)(
            params, cfg, batch, plan)
        params, opt_state = opt.update(grads, opt_state, params,
                                       lr_fn(opt_state["step"]))
        return params, opt_state, loss

    train_step = jax.jit(train_step, donate_argnums=(0, 1))

    data = SyntheticTokenPipeline(DataConfig(
        vocab=cfg.vocab, seq=args.seq, global_batch=args.batch))
    io = HostCollectiveIO(n_ranks=8, n_nodes=2, stripe_size=1 << 20,
                          stripe_count=4)
    ckpt = CheckpointManager(args.ckpt_dir, io, method=args.io)
    loop = TrainLoop(
        TrainLoopConfig(total_steps=args.steps,
                        checkpoint_every=args.ckpt_every),
        train_step, data, ckpt)

    t0 = time.time()
    first_loss = None

    def on_step(step, loss):
        nonlocal first_loss
        if first_loss is None:
            first_loss = loss
        if step % 20 == 0:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"({(time.time()-t0)/step:.2f}s/step)")

    params, opt_state, step = loop.run(params, opt_state, on_step=on_step)
    print(f"done: loss {first_loss:.4f} -> {loop.losses[-1]:.4f} "
          f"in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
