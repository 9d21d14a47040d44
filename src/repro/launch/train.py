"""End-to-end training driver.

Wires together every substrate: config registry, synthetic data with
async prefetch (host task runtime), sharded train step (auto mode or
manual grad-sync schedules), asynchronous checkpointing bound to external
events, preemption handling, and step-granular restart.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --scale smoke --steps 200 --batch 16 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \
      --scale smoke --steps 50 --grad-sync bucketed --mesh 4x2
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import jax
import numpy as np

from .. import configs, optim, checkpoint as ckpt
from ..data import SyntheticLMData, Prefetcher
from ..models import inputs as model_inputs
from ..runtime import steps
from ..runtime.sharding import ShardingPolicy, batch_shardings
from . import mesh as meshlib
from .cache import enable_compile_cache


def parse_mesh(spec: Optional[str]):
    if not spec:
        return meshlib.local_mesh()
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("data", "model")[:len(dims)] if len(dims) <= 2 else \
        ("pod", "data", "model")
    return meshlib.make_mesh(dims, axes)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="granite-3-2b")
    p.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--mesh", default=None, help="e.g. 4x2 (data x model)")
    p.add_argument("--grad-sync", default="auto",
                   choices=["auto", "fused", "bucketed", "sentinel"])
    p.add_argument("--remat", default=None, choices=[None, "full", "dots"])
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", default=None)
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = configs.smoke(args.arch) if args.scale == "smoke" \
        else configs.get(args.arch)
    if args.dtype:
        cfg = cfg.scaled(dtype=args.dtype)
    opt_cfg = optim.OptimConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                                total_steps=args.steps)
    mesh = parse_mesh(args.mesh)
    print(f"[train] arch={cfg.name} scale={args.scale} mesh={dict(mesh.shape)}"
          f" devices={mesh.devices.size}")

    manual = args.grad_sync != "auto"
    policy = ShardingPolicy(
        fsdp=not manual, tp=not manual, sp=not manual, remat=args.remat,
        grad_sync=args.grad_sync)

    key = jax.random.PRNGKey(args.seed)
    state = steps.init_train_state(cfg, opt_cfg, key)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    print(f"[train] params: {n_params/1e6:.2f}M")

    data = SyntheticLMData(cfg, batch=args.batch, seq=args.seq,
                           seed=args.seed)
    abatch = jax.eval_shape(lambda: data.batch_at(0))

    start_step = 0
    saver = None
    if args.ckpt_dir:
        saver = ckpt.AsyncCheckpointer(args.ckpt_dir)
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            a_state = jax.eval_shape(lambda: state)
            state, start_step = ckpt.restore_checkpoint(
                args.ckpt_dir, a_state)
            print(f"[train] restored checkpoint at step {start_step}")

    with mesh:
        if manual:
            make = steps.build_train_step_manual(cfg, mesh, policy, opt_cfg)
            step_fn = make(jax.eval_shape(lambda: state), abatch)
            state_shard = None
        else:
            step_fn, sshard = steps.build_train_step(
                cfg, mesh, policy, opt_cfg, abstract_batch=abatch,
                donate=False)
            state = jax.device_put(state, sshard)
            state_shard = sshard

        bshard = batch_shardings(mesh, abatch)
        prefetch = Prefetcher(
            data, start_step=start_step,
            device_put_fn=lambda b: jax.device_put(b, bshard))

        if saver is not None:
            ckpt.install_preemption_handler(
                lambda: (saver.save(state, cur_step), saver.wait_all()))

        losses = []
        t0 = time.time()
        cur_step = start_step
        for cur_step in range(start_step, args.steps):
            batch = prefetch.get(cur_step)
            state, metrics = step_fn(state, batch)
            if (cur_step + 1) % args.log_every == 0 or cur_step == start_step:
                loss = float(metrics["loss"])
                losses.append(loss)
                dt = time.time() - t0
                print(f"[train] step {cur_step + 1}/{args.steps} "
                      f"loss={loss:.4f} lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"({dt:.1f}s)")
            if saver is not None and (cur_step + 1) % args.ckpt_every == 0:
                saver.save(state, cur_step + 1)  # async — does not block

        jax.block_until_ready(state)
        prefetch.close()
        if saver is not None:
            saver.save(state, args.steps)
            saver.close()

    if len(losses) >= 2 and losses[-1] >= losses[0]:
        print("[train] WARNING: loss did not improve "
              f"({losses[0]:.4f} -> {losses[-1]:.4f}) — short runs on the "
              "synthetic stream are noisy; see examples/train_lm.py")
    else:
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
