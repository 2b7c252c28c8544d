"""Compile-only checks of the device paths for a described TPU v5e (2x2).

Nothing runs on a chip here: each test lowers a jitted program for one
described v5e device and compiles it with the TPU compiler, which refuses
what the chip would refuse (unlowerable Pallas primitives, float64 in
Mosaic, programs over the device's memory).  The topology is described
inside a module fixture, never at import, so every pytest-xdist worker
collects the same tests and only the one running this file loads libtpu.
"""
from __future__ import annotations

import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache but
    # never read back without the chip; keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# Solver shapes: N=64 instances, K=4 stages, S=16 candidates (the min-plus
# kernel's (64,1,16)x(64,16,16) call inside dfts_scan); ResNet101's L=37
# layers give (L+1)=38 segmentation grids, T=64 padded bottleneck caps.
N, K, S, LP1, T = 64, 4, 16, 38, 64


def _scan_args(name, sh):
    f64 = jnp.float64
    if name == "dfts_scan":
        return (_spec((N, K, S), f64, sh), _spec((N, K - 1, S, S), f64, sh),
                _spec((N, S), f64, sh))
    if name == "kseq_scan":
        return (_spec((K, LP1, LP1), f64, sh), _spec((K, LP1), jnp.bool_, sh))
    return (_spec((K, LP1, LP1), f64, sh), _spec((K, LP1, LP1), f64, sh),
            _spec((K, LP1), jnp.bool_, sh), _spec((T,), f64, sh))


@pytest.mark.parametrize("name", ["dfts_scan", "kseq_scan", "kseq_pipe_scan"])
def test_solver_scan_compiles_f64(one_chip, name):
    from repro.core.jax_solvers import _jx

    fn = getattr(_jx(), name)
    with jax.enable_x64(True):
        compiled = fn.lower(*_scan_args(name, one_chip)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("a_shape,b_shape", [
    ((64, 1, 16), (64, 16, 16)),  # the dfts_scan call
    ((2, 9, 130), (2, 130, 3)),   # crosses the (8, 128) tile on M and K
])
def test_minplus_compiles_f32_through_mosaic(one_chip, a_shape, b_shape):
    from repro.kernels.minplus import minplus_matmul

    compiled = minplus_matmul.lower(
        _spec(a_shape, jnp.float32, one_chip),
        _spec(b_shape, jnp.float32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_kernels_compile_at_cell_shapes(one_chip):
    """The SSD scan's forward and backward kernels alone, at the shapes one
    mamba2-370m layer gives them in the benchmark cell: microbatch 4, seq
    2048, 32 heads of 64, state 128, chunk 256."""
    from repro.kernels import ops

    B, S, H, P, N = 4, 2048, 32, 64, 128

    def loss(x, dt, A, Bm, Cm, D):
        y, h = ops.ssd_scan(x, dt, A, Bm, Cm, D, None, chunk=256)
        return jnp.sum(y) + jnp.sum(h)

    f32 = jnp.float32
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        _spec((B, S, H * P), jnp.bfloat16, one_chip),
        _spec((B, S, H), f32, one_chip), _spec((H,), f32, one_chip),
        _spec((B, S, N), f32, one_chip), _spec((B, S, N), f32, one_chip),
        _spec((H,), f32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


def test_flash_attention_kernels_compile_at_cell_shapes(one_chip):
    """The flash-attention forward and backward kernels alone, at the shapes
    the Nemotron-H attention layer gives them in the benchmark cell:
    microbatch 1, seq 4096, 32 query heads and 2 kv heads of 128, bf16,
    the training path's blocks."""
    from repro.kernels import ops
    from repro.models.layers import FLASH_BLOCKS

    B, S, Hq, Hkv, hd = 1, 4096, 32, 2, 128

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, causal=True,
                                block_q=FLASH_BLOCKS[0],
                                block_k=FLASH_BLOCKS[1], interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    bf16 = jnp.bfloat16
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _spec((B, S, Hq, hd), bf16, one_chip),
        _spec((B, S, Hkv, hd), bf16, one_chip),
        _spec((B, S, Hkv, hd), bf16, one_chip)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text


def test_stage_apply_group_compiles_full_width(one_chip):
    """Forward and backward of one mamba2-370m group at published width, as
    a pipeline stage runs it (microbatch 4, seq 2048).  Lowered for the TPU,
    the SSD scan runs as the Pallas kernel pair through Mosaic."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.layers import Ctx
    from repro.msl.pipeline import _stage_apply

    cfg = get_config("mamba2-370m")
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    group = tuple(
        jax.tree.map(lambda p: _spec((1,) + p.shape[1:], p.dtype, one_chip),
                     g) for g in params["stack"]["groups"])
    mb, seq = 4, 2048
    x = _spec((mb, seq, cfg.d_model), jnp.bfloat16, one_chip)

    def loss(g, x):
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (mb, seq))
        h, stats = _stage_apply(g, jnp.ones((1,), bool), cfg, x,
                                Ctx(mode="train", positions=pos))
        return jnp.sum(h.astype(jnp.float32)) + stats["aux"]

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        group, x).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 1024**3
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


def test_nemotron_stage_group_compiles_at_cell_widths(one_chip):
    """Forward and backward of the Nemotron-H period (E M E M E M *) at
    published widths with 8 of 128 experts held, as the benchmark cell's
    stage runs it (microbatch 1, seq 4096).  Lowered for the TPU, the SSD
    scan runs as the grouped kernel pair (8 SSM groups), the held experts as
    grouped-matmul kernels and attention as the flash kernel pair (its
    backward, and the forward the backward recomputes)."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.nemotron3_nano_30b_a3b import PERIOD
    from repro.models import transformer as T
    from repro.models.layers import Ctx
    from repro.msl.pipeline import _stage_apply

    cfg = dataclasses.replace(get_config("nemotron3-nano-30b-a3b"),
                              n_layers=len(PERIOD), pattern=PERIOD,
                              vocab_size=16384, moe_experts_held=8)
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    group = tuple(
        jax.tree.map(lambda p: _spec((1,) + p.shape[1:], p.dtype, one_chip),
                     g) for g in params["stack"]["groups"])
    mb, seq = 1, 4096
    x = _spec((mb, seq, cfg.d_model), jnp.bfloat16, one_chip)

    def loss(g, x):
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (mb, seq))
        h, stats = _stage_apply(g, jnp.ones((1,), bool), cfg, x,
                                Ctx(mode="train", positions=pos))
        return jnp.sum(h.astype(jnp.float32)), stats

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        group, x).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 1024**3
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert "gmm" in text and "tgmm" in text
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
