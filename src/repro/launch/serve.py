"""Serving launcher: continuous batching with the descriptor-paged KV path.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --requests 8 --capacity 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.runtime import SubmitRequest
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(params, cfg, capacity=args.capacity,
                         max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for uid in range(args.requests):
        engine.submit(SubmitRequest(request=Request(
            uid=uid,
            prompt=list(rng.integers(1, cfg.vocab_size, rng.integers(4, 16))),
            max_new_tokens=args.max_new_tokens)))
    done = engine.run(max_steps=10000)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in done.values())
    print(f"{len(done)}/{args.requests} requests, {tokens} tokens, "
          f"{engine.steps} steps, {dt:.1f}s "
          f"({tokens/max(dt,1e-9):.1f} tok/s aggregate)")
    for uid, r in sorted(done.items()):
        print(f"  req {uid}: {r.output}")


if __name__ == "__main__":
    main()
