"""Serving driver: compile-constant weights + continuous batching demo.

  python -m repro.launch.serve --arch smollm_360m --mode sparse_cfmm \
      --requests 6 --prompt-len 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import nn
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import build_cfg
from repro.models import lm
from repro.serving.engine import Request, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--preset", default="tiny")
    ap.add_argument("--mode", default="int8",
                    choices=("dense", "int8", "cfmm", "sparse_cfmm"))
    ap.add_argument("--sparsity", type=float, default=0.8)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = build_cfg(args.arch, args.preset)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(cfg, params, mode=args.mode,
                           sparsity=args.sparsity, batch_slots=args.slots,
                           max_seq=args.prompt_len + args.max_new + 8)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=list(rng.randint(1, cfg.vocab,
                                            size=args.prompt_len)),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    total_new = sum(len(r.tokens_out) for r in reqs)
    for r in reqs[:3]:
        print(f"[serve] req {r.rid}: {len(r.tokens_out)} tokens "
              f"-> {r.tokens_out[:8]}...")
    print(f"[serve] mode={args.mode} {total_new} tokens in {dt:.1f}s "
          f"({total_new / dt:.1f} tok/s, incl. compile)")
    return reqs


if __name__ == "__main__":
    main()
