"""Serving launcher: prefill a batch of prompts, then decode with batched
steps — optionally with the paper's cluster-sparse KV selection.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --prompt-len 256 --gen 32 --batch 4 --backend clusterkv

``--service`` routes through the ClusterKV decode service instead of the
one-shot prefill+decode loop: a continuous-batching engine with plan-cached
sessions (``--mode plan``) or the per-call Morton-sort baseline
(``--mode percall``), emitting the service's JSON telemetry:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --backend clusterkv --service --slots 4 --batch 8 --gen 32 \
      --report report.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_api
from repro.train import trainer


def run_service(cfg, params, args) -> dict:
    """Decode ``args.batch`` synthetic prompts through the ClusterKV
    decode service; returns (and optionally writes) the service report."""
    from repro.serve import ClusterKVEngine
    from repro.train.serve_loop import Request

    engine = ClusterKVEngine(cfg, params, slots=args.slots,
                             max_seq=args.max_seq,
                             prefill_bucket=args.prefill_bucket,
                             mode=args.mode)
    rng = np.random.default_rng(args.seed)
    for i in range(args.batch):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        engine.submit(Request(
            rid=i, tokens=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new=args.gen))
    engine.run()
    report = engine.report()
    print(json.dumps(report, indent=2))
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--backend", default="flash")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--service", action="store_true",
                    help="route through the ClusterKV decode service")
    ap.add_argument("--mode", default="plan", choices=("plan", "percall"))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--prefill-bucket", type=int, default=64)
    ap.add_argument("--report", default=None,
                    help="write the service JSON report here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mod = model_api.module_for(cfg)
    key = jax.random.PRNGKey(args.seed)
    params, _ = model_api.init(cfg, key)

    if args.service:
        run_service(cfg, params, args)
        return

    total = args.prompt_len + args.gen
    batch = model_api.make_small_batch(cfg, key, args.batch, args.prompt_len,
                                       kind="prefill")

    prefill_fn = jax.jit(trainer.make_prefill_step(cfg, None, args.backend))
    decode_fn = jax.jit(trainer.make_decode_step(cfg, None, args.backend))

    t0 = time.time()
    cache, logits = prefill_fn(params, batch)
    # pad cache seq to total length along each entry's discovered seq axis
    # (the config's own cache spec, not shape guessing)
    cache = model_api.grow_cache(cfg, cache, total)
    t1 = time.time()

    toks = jnp.argmax(logits, -1)[:, None]
    outs = [toks]
    for i in range(args.gen - 1):
        if cfg.family == "vlm":
            step_in = {"tokens": jax.random.normal(
                jax.random.fold_in(key, i),
                (args.batch, 1, cfg.d_model)).astype(jnp.bfloat16)}
        else:
            step_in = {"tokens": toks}
        logits, cache = decode_fn(params, cache, step_in)
        toks = jnp.argmax(logits, -1)[:, None]
        outs.append(toks)
    gen = jnp.concatenate(outs, 1)
    t2 = time.time()
    print(f"arch={cfg.name} backend={args.backend}")
    print(f"prefill: {t1-t0:.2f}s ({args.batch*args.prompt_len/(t1-t0):.0f} tok/s)")
    print(f"decode:  {t2-t1:.2f}s ({args.batch*(args.gen-1)/max(t2-t1,1e-9):.0f} tok/s)")
    print("sample tokens:", np.asarray(gen[0][:16]))


if __name__ == "__main__":
    main()
