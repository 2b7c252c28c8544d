"""The pipeline train step names its work on the device: every named scope of
an SSD model reaches the compiled program's ``op_name`` metadata, where a
profiler trace (and ``chipbench/scopes.py``) reads it."""
from __future__ import annotations

import dataclasses
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

SSD_SCOPES = ("embed", "restack", "tick", "stage", "block_norm",
              "ssd_in_proj", "ssd_conv", "ssd_scan", "ssd_gate_norm",
              "ssd_out_proj", "head", "optimizer")
_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")


def _segments(path: str) -> set:
    out = set()
    for seg in path.split("/"):
        while (m := _WRAPPER.match(seg)):
            seg = m.group(1)
        out.add(seg)
    return out


@pytest.fixture(scope="module")
def op_names():
    """The ``op_name`` paths of a tiny mamba2 pipeline train step (2 layers,
    d_model 64, seq 256, chunk 64, vocab 256, M=2, remat on), compiled."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.msl import plan_on_devices
    from repro.msl.pipeline import make_pipeline_train_step
    from repro.optim import adamw, cosine_schedule

    cfg = dataclasses.replace(get_config("mamba2-370m"), n_layers=2,
                              d_model=64, vocab_size=256, ssm_chunk=64,
                              remat=True)
    B, S, M = 4, 256, 2
    plan, mesh = plan_on_devices(cfg, 1, seq_len=S, microbatch=B // M)
    opt = adamw(cosine_schedule(3e-4, 10, 100))
    step = jax.jit(make_pipeline_train_step(cfg, mesh, plan, M, opt),
                   donate_argnums=(0, 1))
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
             for k in ("tokens", "targets")}
    text = step.lower(params, jax.eval_shape(opt.init, params),
                      batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope", SSD_SCOPES)
def test_scope_reaches_the_compiled_program(op_names, scope):
    assert any(scope in _segments(p) for p in op_names)


@pytest.mark.parametrize("direction", ["fwd", "bwd", "remat"])
def test_ssd_scan_is_named_in_every_direction(op_names, direction):
    def direction_of(p):
        if "rematted_computation" in p:
            return "remat"
        return "bwd" if "transpose(" in p else "fwd"

    assert any("ssd_scan" in _segments(p) and direction_of(p) == direction
               for p in op_names)
