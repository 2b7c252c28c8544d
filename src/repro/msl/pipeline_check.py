"""Pipeline-vs-sequential equivalence check for the shard_map chain runtime.

:func:`check_pipeline` runs the pipelined forward against the sequential
``T.forward`` and then takes pipelined train steps with params and optimizer
state donated.  ``python -m repro.msl.pipeline_check [ARCH]`` runs it at
reduced width on a (2, 2) mesh of four host devices (tests invoke it via
subprocess, so the device-count flag is set before jax initializes);
``chip_smoke.py`` runs it at published width on the chip.
"""
from __future__ import annotations

import os
import sys

# bf16 residual-stream accumulation tolerance on the final hidden states
TOL = 5e-2


def check_pipeline(cfg, plan, mesh, *, batch: int, seq: int, n_micro: int,
                   steps: int, seed: int = 0) -> dict:
    """Check `plan` on `mesh` for model `cfg` with random weights from `seed`.

    Returns ``max_err`` (pipelined vs sequential hidden states), the train
    ``losses``, ``param_delta`` (largest parameter change over the steps) and
    ``peak_bytes`` per mesh device (None where the backend reports none).
    Raises RuntimeError when the error exceeds :data:`TOL`, a loss is not
    finite, or the parameters did not change.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import transformer as T
    from ..models.layers import Ctx
    from ..optim import make_optimizer
    from .pipeline import make_pipeline_train_step, pipeline_forward

    params = jax.jit(T.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    data = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                           jnp.int32) for k in ("tokens", "targets")}

    hidden_pp, _ = jax.jit(
        lambda p, b: pipeline_forward(p, b, cfg, mesh, plan, n_micro))(
            params, data)
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    hidden_ref = jax.jit(lambda p, t: T.forward(
        p, cfg, t, Ctx(mode="train", positions=pos))[0])(
            params, data["tokens"])
    err = float(jnp.max(jnp.abs(hidden_pp.astype(jnp.float32)
                                - hidden_ref.astype(jnp.float32))))
    del hidden_pp, hidden_ref
    print(f"pipeline-vs-sequential max_err={err:.6f} (tol {TOL})")
    if not err < TOL:
        raise RuntimeError(f"pipelined forward differs from the sequential "
                           f"one: max_err={err} >= {TOL}")

    opt = make_optimizer(cfg.optimizer, total=10)
    step = jax.jit(make_pipeline_train_step(cfg, mesh, plan, n_micro, opt),
                   donate_argnums=(0, 1))
    before = jax.device_get(params)  # host copy: no device memory held
    opt_state = opt.init(params)
    losses = []
    for _ in range(steps):
        params, opt_state, metrics = step(params, opt_state, data)
        losses.append(float(metrics["loss"]))
    print(f"pipelined train step losses={losses}")
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite train loss: {losses}")
    delta = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree.leaves(before),
                                jax.tree.leaves(jax.device_get(params))))
    if not delta > 0.0:
        raise RuntimeError("train steps left every parameter unchanged")
    peaks = []
    for d in mesh.devices.flat:
        stats = d.memory_stats()
        peaks.append(stats.get("peak_bytes_in_use") if stats else None)
    return {"max_err": err, "losses": losses, "param_delta": delta,
            "peak_bytes": peaks}


def main(arch: str = "qwen3-14b") -> None:
    from ..configs import ARCHS
    from ..configs.registry import PERIODS
    from .pipeline import make_pipeline_mesh
    from .planner import PipelinePlan

    cfg = ARCHS[arch].reduced()
    if arch in PERIODS:  # two whole periods, so that each stage holds one
        cfg = ARCHS[arch].reduced(pattern=PERIODS[arch],
                                  n_layers=2 * len(PERIODS[arch]))
    R = cfg.n_layers // len(cfg.pattern)
    # balanced K=2 segments over R groups (the planner itself is tested in
    # tests/test_msl.py::test_plan_pipeline)
    plan = PipelinePlan(K=2, segments=[(1, R // 2), (R // 2 + 1, R)],
                        placement=["p0g0", "p0g1"], n_groups=R,
                        predicted_latency_s=0.0, breakdown={})
    check_pipeline(cfg, plan, make_pipeline_mesh(2, 2), batch=4, seq=16,
                   n_micro=2, steps=1)
    print("PIPELINE CHECK OK")


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    main(*sys.argv[1:])
