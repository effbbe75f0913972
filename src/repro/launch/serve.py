"""Serving CLI over ``repro.serve`` — static oracle or continuous batching.

Serves the main global model a FedSDD run produced (or a fresh init):

  # static batch: one prefill + one lax.scan decode program
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \
      --prompt-len 64 --decode-steps 32 --batch 4

  # continuous batching: paged KV pool + Poisson arrivals
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
      --continuous --num-requests 16 --rate 50

The continuous path needs an all-GQA schedule (paged KV blocks have a
sequence axis; MLA latents and SSM states don't) — other families serve
through the static path.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.data.synthetic import make_model_batch
from repro.fedckpt.checkpointer import load_pytree
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import ContinuousEngine, Request, generate_static, run_closed_loop


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=list(ASSIGNED_ARCHS))
    ap.add_argument("--ckpt", default=None, help="npz checkpoint to serve")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV pool")
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=256)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only: no decode (DESIGN.md §3)")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.ckpt:
        params = load_pytree(args.ckpt, params)

    if not args.continuous:
        toks = np.asarray(make_model_batch(
            cfg, args.batch, args.prompt_len, seed=args.seed)["tokens"])
        t0 = time.time()
        out = np.asarray(generate_static(model, params, toks,
                                         args.decode_steps))
        dt = time.time() - t0
        n = args.decode_steps * args.batch
        print(f"static: {n} tokens in {dt:.2f}s ({n / max(dt, 1e-9):.1f} tok/s)")
        for b in range(min(args.batch, 2)):
            print(f"  seq{b}: {out[b][:16].tolist()}...")
        return

    rng = np.random.default_rng(args.seed)
    prompts = np.asarray(make_model_batch(
        cfg, args.num_requests, args.prompt_len, seed=args.seed)["tokens"])
    reqs = [Request(rid=i, tokens=prompts[i],
                    max_new_tokens=int(rng.integers(4, args.decode_steps + 1)))
            for i in range(args.num_requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.num_requests))
    engine = ContinuousEngine(
        model, params, max_batch=args.batch, num_blocks=args.num_blocks,
        block_size=args.block_size,
        max_seq_len=args.prompt_len + args.decode_steps)
    t0 = time.time()
    results = run_closed_loop(engine, reqs, arrivals)
    dt = time.time() - t0
    lat = sorted(r.latency for r in results)
    n = sum(len(r.tokens) for r in results)
    print(f"continuous: {len(results)} requests, {n} tokens in {dt:.2f}s "
          f"({n / max(dt, 1e-9):.1f} tok/s)")
    print(f"  latency p50={lat[len(lat) // 2] * 1e3:.1f}ms "
          f"p99={lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3:.1f}ms  "
          f"engine steps={engine.steps}")


if __name__ == "__main__":
    main()
