#!/usr/bin/env python3
"""Smoke test of both device paths on a TPU, from a seed, in one process.

    python3 chip_smoke.py             # one chip: min-plus kernel, admission,
                                      # one-stage mamba2-370m chain
    python3 chip_smoke.py --chips 4   # four chips: the planner's four-stage
                                      # mamba2-370m chain only

Phases (one chip):

* ``minplus`` — the Pallas min-plus kernel, compiled by Mosaic in float32 at
  the shape ``dfts_scan`` calls it with, equals ``reference_minplus``.
* ``admission`` — a seeded NSFNET/ResNet101 stream through ``ServeGateway``,
  once with the device solver ``bcd_jax`` and once with the host ``bcd``.
  Some tick must hand ``solve_batch`` at least ``SOLVE_BATCH_MIN_BATCH``
  unique instances, so the batched device path runs.  Accepted sets and
  plans must agree; a differing plan passes only as a tie (host-evaluated
  latency equal within 1e-9 relative).
* ``chain`` — ``mamba2-370m`` at published widths through the pipeline API:
  pipelined forward against the sequential one, then three donated train
  steps (``repro.msl.pipeline_check.check_pipeline``).

The script exits non-zero, printing no result, when JAX finds no TPU or a
phase fails.  On success its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "mamba2-370m"
SEQ, BATCH, STEPS = 2048, 8, 3
TIE_REL = 1e-9


def minplus_check(seed: int = 0) -> dict:
    """Kernel vs ``reference_minplus`` in f32 at the DFTS scan shape
    (N, 1, S) x (N, S, S); values and first-argmin indices must be equal."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.minplus import minplus_matmul
    from repro.kernels.ref import reference_minplus

    rng = np.random.default_rng(seed)

    def cost(shape):
        x = rng.uniform(0.0, 10.0, size=shape).astype(np.float32)
        x[rng.uniform(size=shape) < 0.2] = np.inf
        return jnp.asarray(x)

    a, b = cost((64, 1, 16)), cost((64, 16, 16))
    val, idx = minplus_matmul(a, b)
    rval, ridx = reference_minplus(a, b)
    n_val = int(np.sum(np.asarray(val) != np.asarray(rval)))
    n_idx = int(np.sum(np.asarray(idx) != np.asarray(ridx)))
    if n_val or n_idx:
        raise RuntimeError(f"minplus_matmul differs from reference_minplus: "
                           f"{n_val} values, {n_idx} indices")
    return {"shape": "(64,1,16)x(64,16,16)", "dtype": "float32"}


def admission(n_requests: int = 64, seed: int = 0) -> dict:
    """The same seeded gateway stream under ``bcd_jax`` and host ``bcd``."""
    from repro.core import IF, PlanEvaluator
    from repro.core.engine import SOLVE_BATCH_MIN_BATCH
    from repro.serve.gateway import GatewayConfig, ServeGateway
    from repro.serve.requests import generate_fleet
    from repro.sweep.spec import build_profile, build_topology

    net = build_topology("nsfnet")
    prof = build_profile("resnet101")
    # Poisson arrivals grouped into 1 s windows (~8 requests a tick), each
    # request with its own seeded candidate sets and a batch size from the
    # x1/x2/x4 spread: every tick's shapes are new, so presolve misses.
    fleet = generate_fleet(net, n_requests, "v4", "v13", 2, IF, 3, seed=seed,
                           arrival="poisson", arrival_rate_rps=8.0,
                           model_id="resnet101", hold_model="exp",
                           hold_time_s=2.0)
    runs = {}
    for solver in ("bcd_jax", "bcd"):
        gw = ServeGateway(net, prof, solver=solver,
                          config=GatewayConfig(batch_window_s=1.0))
        t0 = time.perf_counter()
        out = gw.run_stream(fleet)
        runs[solver] = (out, gw.stats.ticks, time.perf_counter() - t0)

    dev, dev_ticks, dev_wall = runs["bcd_jax"]
    host, _, host_wall = runs["bcd"]
    batched = [t["plan_cache_misses"] for t in dev_ticks
               if t["plan_cache_misses"] >= SOLVE_BATCH_MIN_BATCH]
    if not batched:
        raise RuntimeError(f"no tick handed solve_batch >= "
                           f"{SOLVE_BATCH_MIN_BATCH} unique instances")
    ties, mismatches = [], []
    for d, h in zip(dev.served, host.served):
        rid = d.request.request_id
        if d.accepted != h.accepted:
            mismatches.append(f"request {rid}: accepted {d.accepted} on "
                              f"bcd_jax, {h.accepted} on bcd")
        elif d.plan != h.plan:
            if d.plan is None or h.plan is None:
                mismatches.append(f"request {rid}: plan {d.plan} vs {h.plan}")
                continue
            ev = PlanEvaluator(net, prof, d.request.chain_request())
            ld, lh = ev.evaluate(d.plan).total_s, ev.evaluate(h.plan).total_s
            if abs(ld - lh) <= TIE_REL * abs(lh):
                ties.append(rid)
            else:
                mismatches.append(f"request {rid}: latency {ld!r} on bcd_jax,"
                                  f" {lh!r} on bcd")
    if mismatches:
        raise RuntimeError("bcd_jax disagrees with bcd:\n  "
                           + "\n  ".join(mismatches))
    return {"requests": len(fleet), "accepted": dev.n_accepted,
            "ticks": len(dev_ticks), "batched_ticks": len(batched),
            "instances_solved_batched": sum(batched), "tied_plans": ties,
            "wall_s_bcd_jax": dev_wall, "wall_s_bcd": host_wall}


def chain(cfg, n_stages: int, *, seq: int = SEQ, batch: int = BATCH,
          steps: int = STEPS) -> dict:
    """The planner's `n_stages`-stage chain of `cfg`, one stage per device,
    against the sequential forward, then `steps` donated train steps.

    M = 2K microbatches: 2 on one chip; 8 on four, where the planner's
    microbatch-1 segments fit HBM and its microbatch-4 ones do not (every
    stage holds max-segment-length group slots)."""
    from repro.msl import plan_on_devices
    from repro.msl.pipeline_check import check_pipeline

    n_micro = 2 * n_stages
    plan, mesh = plan_on_devices(cfg, n_stages, seq_len=seq,
                                 microbatch=batch // n_micro)
    res = check_pipeline(cfg, plan, mesh, batch=batch, seq=seq,
                         n_micro=n_micro, steps=steps)
    res.update(segments=plan.segments, n_micro=n_micro)
    return res


def _run(name: str, fn, *args, **kwargs) -> bool:
    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    try:
        res = fn(*args, **kwargs)
    except Exception:  # report every phase, then fail the run
        traceback.print_exc()
        print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s",
              flush=True)
        return False
    print(f"[{name}] ok in {time.perf_counter() - t0:.1f} s (compile "
          f"included): {json.dumps(res, default=str)}", flush=True)
    return True


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-stage chain on four chips")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError:
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {d0.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(ARCH)
    print(f"device: {d0.device_kind} x{len(devices)}; {ARCH} layers="
          f"{cfg.n_layers} d_model={cfg.d_model} vocab={cfg.vocab_size} "
          f"seq={SEQ} batch={BATCH}", flush=True)
    if args.chips == 1:
        ok = all([_run("minplus", minplus_check),
                  _run("admission", admission),
                  _run("chain K=1", chain, cfg, 1)])
    else:
        ok = _run(f"chain K={args.chips}", chain, cfg, args.chips)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
