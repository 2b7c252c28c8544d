"""The dropless expert-share MoE layer (models/layers.moe_ffn): the shares
of a layer held by different devices add up to the uncut layer, and no
routed token is dropped however skewed the routing."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L

T_, D = 64, 32
SRC = str(Path(__file__).resolve().parents[1] / "src")


def _cfg(arch, **kw):
    """A float32 reduced MoE config (exact dots on the CPU, so the sums
    below are compared to float32 rounding)."""
    return dataclasses.replace(
        get_config(arch).reduced(), d_model=D, moe_d_ff=24,
        compute_dtype="float32", **kw)


def _dense(p, cfg, x, first=0):
    """The held experts as a dense loop over every token, each weighted by
    its routing weight (zero where the token did not choose it), plus the
    shared expert: what the layer must compute, token by token."""
    toks = x.reshape(-1, D)
    idx, w, _ = L.route(p, cfg, toks)
    y = L.mlp(p["shared"], cfg, toks) if "shared" in p else 0.0
    for e in range(cfg.n_experts_held):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        up = toks @ p["w_up"][e]
        if "w_gate" in p:
            h = jax.nn.silu(toks @ p["w_gate"][e]) * up
        else:
            h = L.relu2(up)
        y = y + w_e[:, None] * (h @ p["w_down"][e])
    return y.reshape(x.shape)


def _share(p, lo, hi):
    return {k: (v[lo:hi] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in p.items()}


# float32 on both sides; the sums differ in order only: observed 2.8e-7 of
# the output's scale, limit 1e-5
TOL = 1e-5


@pytest.mark.parametrize("arch,experts,top_k", [
    ("nemotron3-nano-30b-a3b", 32, 6),   # sigmoid router, relu^2, shared
    ("qwen3-moe-30b-a3b", 32, 8),        # softmax router, SwiGLU
])
def test_expert_shares_add_up_to_the_uncut_layer(arch, experts, top_k):
    """16 devices, each holding 2 of the experts: their parts of the
    output, with what all compute alike (the shared expert) counted once,
    add up to the layer that holds every expert."""
    whole = _cfg(arch, n_experts=experts, moe_top_k=top_k,
                 moe_experts_held=0)
    share = dataclasses.replace(whole, moe_experts_held=experts // 16)
    p = L.init_moe(jax.random.PRNGKey(3), whole)
    if "router_bias" in p:
        p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                                   p["router_bias"].shape)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T_ // 2, D))
    want, _ = L.moe_ffn(p, whole, x)
    common = (L.mlp(p["shared"], whole, x) if "shared" in p
              else jnp.zeros_like(x))
    total, rows = common, 0
    Eh = share.n_experts_held
    for r in range(16):
        part, stats = L.moe_ffn(_share(p, r * Eh, (r + 1) * Eh), share, x,
                                first=r * Eh)
        total = total + part - common
        rows += int(stats["moe_rows"])
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(total - want))) <= TOL * scale
    assert rows == T_ * top_k          # every (token, slot) pair, once


@pytest.mark.parametrize("first", [0, 8])
def test_skewed_routing_drops_no_token(first):
    """The correction bias sends every token to expert ``first``, a held
    one, besides its other choices: that expert computes all the tokens
    (a capacity dispatch would keep 1.25 x its even share and drop the
    rest), and the layer equals the dense loop over the held experts."""
    cfg = _cfg("nemotron3-nano-30b-a3b", n_experts=16, moe_top_k=6,
               moe_experts_held=8)
    p = L.init_moe(jax.random.PRNGKey(6), dataclasses.replace(
        cfg, moe_experts_held=0))
    p = _share(p, first, first + 8)
    p["router_bias"] = jnp.zeros((16,)).at[first].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, T_ // 2, D))
    y, stats = L.moe_ffn(p, cfg, x, first=first)
    assert int(stats["moe_max_rows"]) == T_
    idx, _, _ = L.route(p, cfg, x.reshape(-1, D))
    held = (idx >= first) & (idx < first + 8)
    assert int(stats["moe_rows"]) == int(jnp.sum(held))
    want = _dense(p, cfg, x, first)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=TOL * scale)


def test_grouped_matmul_kernel_matches_the_default_path():
    """The megablox kernel (interpret mode) that the TPU runs, against
    ``ragged_dot`` that the CPU runs: rows of the held groups by their
    expert, the last group's rows zero, and both cotangents."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as mblx

    rng = np.random.default_rng(0)
    m, k, n, g = 256, 256, 128, 3
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)), jnp.bfloat16)
    sizes = jnp.asarray([40, 0, 100, m - 140], jnp.int32)

    def kernel(lhs, rhs):
        return mblx.gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
                        tiling=(128, 128, 128),
                        group_offset=jnp.zeros((), jnp.int32), interpret=True)

    def default(lhs, rhs):
        return L._grouped_matmul(lhs, rhs, sizes).astype(jnp.float32)

    out_k, vjp_k = jax.vjp(kernel, lhs, rhs)
    out_d, vjp_d = jax.vjp(default, lhs, rhs)
    assert not np.any(np.asarray(out_d[140:]))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               rtol=1e-2, atol=1e-2 * float(
                                   jnp.max(jnp.abs(out_d))))
    ct = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    # bf16 cotangents on both sides: a relative 2**-8 a product term
    for a, b in zip(vjp_k(ct), vjp_d(ct)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= 2e-2 * np.max(np.abs(b))


_EP_CHECK = r"""
import dataclasses, re, sys
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import layers as L
from repro.models.sharding import make_rules, mesh_rules, param_shardings

E = 8
cfg = dataclasses.replace(
    get_config(sys.argv[1]).reduced(), d_model=32, moe_d_ff=24,
    compute_dtype="float32", n_experts=E, moe_top_k=2, moe_experts_held=0)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = make_rules(mesh, "2d")
p = L.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

def loss(p, x):
    y, stats = L.moe_ffn(p, cfg, x)
    return jnp.sum(y * jnp.cos(x)), (y, stats)

want = jax.grad(loss, argnums=(0, 1), has_aux=True)(p, x)
pd = jax.device_put(p, param_shardings({"moe": p}, rules)["moe"])
with mesh_rules(rules):
    fn = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    jaxpr = str(jax.make_jaxpr(fn)(pd, x))
    text = fn.lower(pd, x).compile().as_text()
    got = fn(pd, x)
assert "shard_map" in jaxpr         # the experts run as per-device shares
(gp, gx), (y, stats) = got
(wp, wx), (wy, wstats) = want
scale = float(jnp.max(jnp.abs(wy)))
# float32 on both sides, sums in another order: observed 1e-7 of the scale
assert float(jnp.max(jnp.abs(y - wy))) <= 1e-5 * scale
for a, b in zip(jax.tree.leaves((gp, gx)), jax.tree.leaves((wp, wx))):
    assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(jnp.max(jnp.abs(b)))
for k in ("moe_rows", "moe_max_rows"):
    assert int(stats[k]) == int(wstats[k]), k
assert int(stats["moe_rows"]) == 64 * 2
# the experts stay sharded: no collective gathers a tensor of all E experts
gathered = re.findall(r"= \w+\[(\d+),\d+,\d+\][^ ]* all-gather", text)
assert str(E) not in gathered, gathered
print("EP OK")
"""


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b",
                                  "nemotron3-nano-30b-a3b"])
def test_expert_parallel_layer_matches_one_device(arch):
    """Under mesh rules whose ``expert`` axis shards every expert (a 2 x 2
    mesh of host devices, the MoE archs' '2d' rules), the layer runs each
    device's experts as its share: output, gradients and counters equal
    the one-device layer, and no all-gather assembles the whole expert
    stack."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable, "-c", _EP_CHECK, arch],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "EP OK" in proc.stdout
