"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 50 --batch 8 --seq 128

It runs on the devices of one host (nothing here initializes
``jax.distributed``); ``--reduced`` runs the reduced config end-to-end.

Distributed-optimization environment (set before jax init): the launcher
exports the XLA flags that enable latency-hiding scheduling so collectives
overlap with compute — the overlap lever referenced in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import os

XLA_PERF_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_megacore_fusion_allow_ags=true "
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
)

if os.environ.get("REPRO_TPU"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + XLA_PERF_FLAGS)

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import Checkpointer
from repro.configs import get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.data import pipeline
from repro.launch.ft import Supervisor
from repro.models import model_api
from repro.optim.optimizers import make_optimizer
from repro.train import trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--backend", default="flash")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    opt = make_optimizer(cfg.optimizer, lr=args.lr, warmup=max(args.steps // 20, 1),
                         total=args.steps)
    step_fn, _ = trainer.make_train_step(cfg, mesh=None, backend=args.backend,
                                         microbatch=args.microbatch,
                                         optimizer=opt)
    step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

    params, _ = model_api.init(cfg, jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"optimizer={cfg.optimizer} backend={args.backend}")

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    state = {"params": params, "opt": opt_state}

    def one_step(state, step):
        batch_np = pipeline.token_batch(cfg, step, args.batch, args.seq,
                                        args.seed)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        p, o, metrics = step_jit(state["params"], state["opt"], batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        return {"params": p, "opt": o}

    if ckpt:
        sup = Supervisor(step_deadline_s=3600)
        state = sup.run(
            n_steps=args.steps,
            make_state=lambda: state,
            step_fn=one_step,
            save=lambda s, st: ckpt.save(s, st),
            restore=lambda: ckpt.restore(state),
            ckpt_every=args.ckpt_every or max(args.steps // 4, 1))
        ckpt.wait()
    else:
        t0 = time.time()
        for step in range(args.steps):
            state = one_step(state, step)
        dt = time.time() - t0
        tok = args.steps * args.batch * args.seq
        print(f"done: {dt:.1f}s, {tok/dt:.0f} tok/s")


if __name__ == "__main__":
    main()
