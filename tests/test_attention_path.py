"""Attention on the training path (``layers._train_attention``).

On the CPU the training branch of ``attention_block`` is the blocked
oracle, unchanged.  Lowered for a TPU (here: the platform choice forced to
its ``tpu`` branch and the Pallas TPU interpreter), it runs the flash kernel
pair where the shapes tile, and gives the oracle's values and gradients.
The kernels mask by index, which equals the oracle's mask by position
because every call without a cache builds its positions as ``arange(S)``:
each such ``Ctx`` constructor is checked here."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from repro.configs import ARCHS, get_config  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import transformer as T  # noqa: E402

B = 2
# Nemotron-H's attention (NoPE GQA, heads of 128) at reduced width
CFG = dataclasses.replace(get_config("nemotron3-nano-30b-a3b"), d_model=128,
                          n_heads=8, n_kv_heads=2)
VARIANTS = {
    "causal": (CFG, True, None),
    "non-causal": (CFG, False, None),           # an encoder's attention
    "window-softcap": (dataclasses.replace(CFG, attn_softcap=30.0), True, 640),
}


def _inputs(cfg, S, seed=0):
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    p = L.init_attention(kp, cfg)
    x = jax.random.normal(kx, (B, S, cfg.d_model), jnp.float32).astype(
        jnp.bfloat16)
    return p, x


def _block(cfg, causal, window, S):
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ctx = L.Ctx(mode="train", positions=pos, causal=causal)

    def f(p, x):
        return L.attention_block(p, cfg, x, ctx, None, window=window)[0]
    return f


def _oracle(cfg, causal, window, S):
    """The training branch as it was: projections, ``blocked_attention``,
    output projection."""
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def f(p, x):
        q, k, v = L._project_qkv(p, cfg, x, x, pos, pos)
        out = L.blocked_attention(cfg, q, k, v, pos, pos, causal, window)
        return out.reshape(B, S, -1) @ p["wo"].astype(L.cdt(cfg))
    return f


def _value_and_grads(f, p, x):
    out, vjp = jax.vjp(f, p, x)
    ct = jax.random.normal(jax.random.PRNGKey(9), out.shape,
                           jnp.float32).astype(out.dtype)
    return out, vjp(ct)


@pytest.fixture
def tpu_branch(monkeypatch):
    """``platform_dependent`` takes its ``tpu`` branch, and Pallas kernels
    lowered for the TPU run in the TPU interpreter."""
    def choose(*args, default=None, **per_platform):
        return per_platform.get("tpu", default)(*args)

    monkeypatch.setattr(jax.lax, "platform_dependent", choose)
    with pltpu.force_tpu_interpret_mode():
        yield


def _has_kernel(f, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_cpu_training_path_is_the_blocked_oracle(variant):
    cfg, causal, window = VARIANTS[variant]
    S = 1024
    p, x = _inputs(cfg, S)
    got = jax.jit(lambda p, x: _value_and_grads(
        _block(cfg, causal, window, S), p, x))(p, x)
    want = jax.jit(lambda p, x: _value_and_grads(
        _oracle(cfg, causal, window, S), p, x))(p, x)
    lowered = jax.jit(_block(cfg, causal, window, S)).lower(p, x).as_text()
    assert "tpu_custom_call" not in lowered
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_kernel_path_gives_the_oracles_values_and_gradients(tpu_branch,
                                                            variant):
    """S = 1024: two 512-row blocks each way, so the pair visits a diagonal
    block, an unmasked one and (causal) skips the one above the diagonal.
    Both paths round to bf16 where the TPU does; the bound is 2e-2 of the
    largest magnitude (observed under 6e-3)."""
    cfg, causal, window = VARIANTS[variant]
    S = 1024
    p, x = _inputs(cfg, S)
    f = _block(cfg, causal, window, S)
    assert _has_kernel(f, p, x)
    got = _value_and_grads(f, p, x)
    want = _value_and_grads(_oracle(cfg, causal, window, S), p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= 2e-2 * np.max(np.abs(b))


@pytest.mark.parametrize("case", ["head_dim-64", "ragged-seq", "mesh-rules"])
def test_kernel_path_falls_back_where_it_does_not_apply(tpu_branch, case):
    """Heads of 64 lanes, a sequence that is not whole 512-row blocks, or
    active mesh rules (GSPMD does not partition a Mosaic kernel) keep the
    blocked path even when lowered for a TPU."""
    from jax.sharding import Mesh

    from repro.models.sharding import MeshRules, mesh_rules

    cfg, S, rules = CFG, 1024, None
    if case == "head_dim-64":
        cfg = dataclasses.replace(CFG, head_dim=64)
    elif case == "ragged-seq":
        S = 640
    else:
        rules = MeshRules(Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                               ("data", "model")))
    p, x = _inputs(cfg, S)
    with mesh_rules(rules):
        assert not _has_kernel(_block(cfg, True, None, S), p, x)


# --- every Ctx of a call without a cache has positions arange(S) -----------

def _tiny(arch):
    return ARCHS[arch].reduced()


def _run_pipeline_step():
    from repro.msl import plan_on_devices
    from repro.msl.pipeline import make_pipeline_train_step
    from repro.optim import adamw, cosine_schedule

    cfg = _tiny("qwen3-14b")
    Bt, S, M = 4, 16, 2
    plan, mesh = plan_on_devices(cfg, 1, seq_len=S, microbatch=Bt // M)
    opt = adamw(cosine_schedule(3e-4, 10, 100))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.ones((Bt, S), jnp.int32) for k in ("tokens", "targets")}
    step = jax.jit(make_pipeline_train_step(cfg, mesh, plan, M, opt))
    jax.block_until_ready(step(params, opt.init(params), batch))
    return S


def _run_loss_fn():
    from repro.train.steps import loss_fn

    cfg = _tiny("qwen3-14b")
    S = 16
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.ones((2, S), jnp.int32) for k in ("tokens", "targets")}
    jax.block_until_ready(jax.jit(jax.grad(
        lambda p: loss_fn(p, cfg, batch)[0]))(params))
    return S


def _run_sequential_forward():
    """``pipeline_check``'s sequential reference: ``T.forward`` with the
    Ctx it builds."""
    from repro.msl.pipeline import make_pipeline_mesh
    from repro.msl.pipeline_check import check_pipeline
    from repro.msl.planner import PipelinePlan

    cfg = _tiny("qwen3-14b")
    R = cfg.n_layers // len(cfg.pattern)
    plan = PipelinePlan(K=1, segments=[(1, R)], placement=["p0g0"],
                        n_groups=R, predicted_latency_s=0.0, breakdown={})
    check_pipeline(cfg, plan, make_pipeline_mesh(1, 1), batch=2, seq=16,
                   n_micro=1, steps=1)
    return 16


def _run_encoder():
    cfg = _tiny("whisper-small")
    M = cfg.memory_len
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    memory = jnp.ones((2, M, cfg.d_model), jnp.float32)
    jax.block_until_ready(T.encode_memory(params, cfg, memory))
    return M


def _run_simulator_stage():
    from repro.core import TR, ServiceChainRequest, tpu_pod_topology
    from repro.msl import group_profile
    from repro.msl.simulator import ChainSimulator

    cfg = _tiny("qwen3-14b")
    S = 16
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    net = tpu_pod_topology(n_groups=2, chips_per_group=2)
    nodes = sorted(net.nodes)
    prof = group_profile(cfg, seq_len=S, mode="train")
    sim = ChainSimulator(cfg, params, net, prof,
                         ServiceChainRequest("qwen3-14b", nodes[0], nodes[-1],
                                             1, TR))
    R = cfg.n_layers // len(cfg.pattern)
    x = T.embed_tokens(params, cfg, jnp.ones((2, S), jnp.int32))
    jax.block_until_ready(sim._stage_fn(1, R)(params["stack"], x))
    return S


SITES = {"msl.pipeline": _run_pipeline_step, "train.steps": _run_loss_fn,
         "msl.pipeline_check": _run_sequential_forward,
         "transformer.encode_memory": _run_encoder,
         "msl.simulator": _run_simulator_stage}


@pytest.mark.parametrize("site", list(SITES))
def test_training_ctx_positions_are_arange(monkeypatch, site):
    seen = []
    inner = L._train_attention

    def spy(cfg, q, k, v, positions, causal, window):
        jax.debug.callback(lambda p: seen.append(np.asarray(p)), positions)
        return inner(cfg, q, k, v, positions, causal, window)

    monkeypatch.setattr(L, "_train_attention", spy)
    S = SITES[site]()
    assert seen, "the site ran no training-path attention"
    for pos in seen:
        np.testing.assert_array_equal(
            pos, np.broadcast_to(np.arange(S), pos.shape))
