"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b --reduced \
        --steps 100 --batch 8 --seq 128

Full-scale configs target the production mesh; --reduced runs the same
code end-to-end on host devices with checkpointing, deterministic data, and
metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_config, get_reduced_config
from ..data.tokens import TokenStream
from ..models import params as P_
from ..models.sharding import ShardingRules, tree_shardings
from ..models.transformer import Runtime
from ..train.checkpoint import CheckpointManager
from ..train.optimizer import OptConfig, init_opt_state
from ..train.train_step import make_train_step
from .mesh import make_host_mesh


def build(arch: str, reduced: bool, batch: int, seq: int, microbatches: int,
          lr: float, steps: int):
    cfg = get_reduced_config(arch) if reduced else get_config(arch)
    mesh = make_host_mesh()
    rules = ShardingRules(fsdp=False, data_axes=("data",))
    rt = Runtime(mesh=mesh, rules=rules)
    opt = OptConfig(lr=lr, warmup_steps=max(steps // 20, 5),
                    total_steps=steps)
    step_fn = jax.jit(make_train_step(cfg, rt, opt,
                                      microbatches=microbatches),
                      donate_argnums=(0, 1))
    params = P_.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    opt_state = init_opt_state(params, opt)
    stream = TokenStream(cfg.vocab, seq, batch)
    return cfg, mesh, step_fn, params, opt_state, stream


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2.5-14b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg, mesh, step_fn, params, opt_state, stream = build(
        args.arch, args.reduced, args.batch, args.seq, args.microbatches,
        args.lr, args.steps)
    ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
    start = 0
    if ckpt and ckpt.latest_step() is not None:
        like = jax.eval_shape(lambda: (params, opt_state))
        start, (params, opt_state) = ckpt.restore(like)
        print(f"resumed from step {start}")
    with mesh:
        t0 = time.time()
        for step in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, stream.batch(step))
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                m = jax.tree.map(float, metrics)
                print(f"step {step:5d} loss={m['loss']:.4f} "
                      f"ce={m['ce']:.4f} gnorm={m['grad_norm']:.3f} "
                      f"({time.time()-t0:.1f}s)", flush=True)
            if ckpt and step and step % 50 == 0:
                ckpt.save(step, (params, opt_state))
        if ckpt:
            ckpt.save(args.steps, (params, opt_state))
            ckpt.wait()
    print("done")


if __name__ == "__main__":
    main()
