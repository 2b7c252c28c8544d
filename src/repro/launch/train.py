"""Training launcher: --arch <id> on the local device mesh, with planner-driven
pipeline mode, checkpointing, elastic re-planning hooks, and the synthetic data
pipeline.  On CPU it trains reduced configs end-to-end; on a TPU, ``--full``
trains at published widths.  ``--mode msl-pp`` runs one pipeline stage per
device: one stage on one chip, the planner's K = device-count chain on more.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 50 \
      [--mode dp|msl-pp] [--reduced] [--ckpt-dir DIR] [--resume]
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mode", choices=("dp", "msl-pp"), default="dp")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--n-micro", type=int, default=2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ..ckpt import CheckpointManager
    from ..configs import get_config
    from ..data import BatchSpec, Prefetcher, SyntheticLM
    from ..models import transformer as T
    from ..optim import make_optimizer
    from ..train import make_train_step
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(cfg.optimizer, lr=args.lr, warmup=5, total=args.steps)
    opt_state = opt.init(params)

    if args.mode == "msl-pp":
        from ..msl import make_pipeline_train_step, plan_on_devices

        # one pipeline stage per device: the whole chain on one chip, the
        # planner's K = device-count chain otherwise
        plan, mesh = plan_on_devices(cfg, jax.device_count(),
                                     seq_len=args.seq,
                                     microbatch=args.batch // args.n_micro)
        print(f"[msl-pp] K={plan.K} segments={plan.segments}")
        step_fn = make_pipeline_train_step(cfg, mesh, plan, args.n_micro, opt)
    else:
        step_fn = make_train_step(cfg, opt)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    ckpt = CheckpointManager(args.ckpt_dir or f"/tmp/repro_{args.arch}_ckpt")
    start = 0
    if args.resume:
        s, state = ckpt.restore()
        if s is not None:
            params = jax.tree.map(jnp.asarray, state["params"])
            opt_state = jax.tree.map(jnp.asarray, state["opt"])
            start = s + 1
            print(f"[resume] from step {s}")

    spec = BatchSpec(args.batch, args.seq, cfg.vocab_size,
                     memory_len=cfg.memory_len, d_model=cfg.d_model)
    prefetch = Prefetcher(SyntheticLM(spec, seed=0), start_step=start)
    t0 = time.time()
    try:
        for step in range(start, args.steps):
            _, host_batch = prefetch.next()
            batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % 10 == 0 or step == args.steps - 1:
                dt = (time.time() - t0) / max(1, step - start + 1)
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"{dt*1e3:.0f} ms/step")
            if args.ckpt_every and step and step % args.ckpt_every == 0:
                ckpt.save(step, {"params": params, "opt": opt_state},
                          blocking=False)
    finally:
        prefetch.close()
    ckpt.save(args.steps - 1, {"params": params, "opt": opt_state})
    print(f"done: {args.steps - start} steps; checkpoint at step "
          f"{ckpt.latest_step()}")


if __name__ == "__main__":
    main()
