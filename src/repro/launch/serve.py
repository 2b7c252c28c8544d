"""Serving launcher: --arch <id>, batched generation over synthetic prompts.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --requests 8
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    args = ap.parse_args()

    import jax
    import numpy as np

    from ..configs import get_config
    from ..models import transformer as T
    from ..serving import ServingEngine
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(cfg, params, batch_size=args.batch,
                           cache_len=args.cache_len)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 32)))
               .astype(np.int32) for _ in range(args.requests)]
    t0 = time.time()
    outs = engine.generate(prompts, max_new=args.max_new)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    print(f"{args.requests} requests -> {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
