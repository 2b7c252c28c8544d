"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.slow  # interpret-mode kernel sweeps (~30s)

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 6e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,Hkv,hd,causal,window,softcap",
    [
        (2, 128, 128, 4, 2, 64, True, None, None),
        (1, 200, 200, 8, 1, 64, True, None, 50.0),  # MQA + softcap + ragged
        (2, 256, 256, 4, 4, 128, True, 64, None),  # sliding window
        (1, 64, 256, 2, 2, 64, False, None, None),  # cross attention
        (1, 96, 96, 6, 3, 32, True, 32, 30.0),  # everything + tiny head
    ],
)
def test_flash_attention(B, Sq, Sk, Hq, Hkv, hd, causal, window, softcap, dtype):
    q = _rand((B, Sq, Hq, hd), dtype)
    k = _rand((B, Sk, Hkv, hd), dtype)
    v = _rand((B, Sk, Hkv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=64, block_k=64)
    expect = ref.reference_attention(q, k, v, causal=causal, window=window,
                                     softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


# The flash-attention kernel pair (forward, and the backward of its
# custom_vjp) against ref.reference_attention in float32 and the model's
# blocked_attention, the training path's oracle.  Causal GQA at Nemotron-H's
# 16:1 head ratio over four blocks each way (diagonal, unmasked and skipped
# blocks), non-causal, and the window with a softcap over q blocks half the
# size of the kv blocks.  Observed, as a share
# of the largest magnitude: float32 within 2e-6 of both; bf16 within 5e-3
# of the float32 reference (the blocked path: 9.3e-3, as it sums dK and dV
# over its chunks in bf16) and 9.3e-3 of the blocked path.
FLASH_PAIR = [  # B, S, Hq, Hkv, hd, block_q, block_k, causal, window, softcap
    pytest.param(1, 512, 16, 1, 128, 128, 128, True, None, None,
                 id="gqa16-causal"),
    pytest.param(2, 256, 4, 2, 128, 128, 128, False, None, None,
                 id="non-causal"),
    pytest.param(1, 384, 4, 2, 64, 64, 128, True, 200, 30.0,
                 id="window-softcap"),
]
FLASH_TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,hd,block_q,block_k,causal,window,softcap", FLASH_PAIR)
def test_flash_attention_pair_values_and_gradients(B, S, Hq, Hkv, hd, block_q,
                                                    block_k, causal, window,
                                                    softcap, dtype):
    import dataclasses

    from repro.configs import get_config
    from repro.models.layers import blocked_attention

    cfg = dataclasses.replace(get_config("nemotron3-nano-30b-a3b"),
                              q_chunk=block_q, attn_softcap=softcap)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q = _rand((B, S, Hq, hd), dtype)
    k = _rand((B, S, Hkv, hd), dtype)
    v = _rand((B, S, Hkv, hd), dtype)
    ct = _rand((B, S, Hq, hd), dtype)
    got, vjp = jax.vjp(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k), q, k, v)
    got = (got, *vjp(ct))
    oracles = {
        "reference": lambda q, k, v: ref.reference_attention(
            q, k, v, causal=causal, window=window, softcap=softcap),
        "blocked": lambda q, k, v: blocked_attention(
            cfg, q, k, v, pos, pos, causal, window),
    }
    for name, oracle in oracles.items():
        want, vjp = jax.vjp(oracle, q, k, v)
        want = (want, *vjp(ct))
        for part, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            err = float(np.max(np.abs(a - b)))
            assert err <= FLASH_TOL[dtype] * float(np.max(np.abs(b))), (
                name, part, err)


@pytest.mark.parametrize("B,S,W,bs,bw", [
    (2, 64, 128, 16, 64),
    (1, 100, 48, 32, 32),  # ragged
    (3, 256, 512, 64, 256),
])
def test_rglru_scan(B, S, W, bs, bw):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (B, S, W)), jnp.float32)
    b = _rand((B, S, W), jnp.float32) * 0.1
    out = ops.rglru_scan(a, b, block_s=bs, block_w=bw)
    expect = ref.reference_rglru_scan(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5,
                               rtol=1e-5)


# SSD scan kernels (interpret mode) against the model's _ssd_chunked oracle.
# The kernels round every dot operand to bfloat16, as the TPU's default
# precision does; the oracle on the CPU multiplies in float32.  x, B and C
# are bf16-exact (as the conv's output is), so what differs is the rounding
# of the decay-weighted scores, the states and the cotangents fed to the
# dots: a relative error of 2**-9 per operand, summed over a chunk's terms.
# Observed: at most 7e-3 of the largest magnitude (A's cotangent, a sum over
# every position); the bound is 2e-2.  The same kernels with float32 dot
# operands agree with the oracle to 2e-5.
SSD_TOL = 2e-2
SSD_SHAPES = [  # B, S, H, P, N, Q, G (SSM groups; B and C (B, S, G, N))
    pytest.param(1, 256, 2, 64, 128, 128, 1,   # one head group, two chunks
                 id="1-256-2-64-128-128"),
    pytest.param(1, 256, 4, 32, 128, 128, 1,   # four heads a group
                 id="1-256-4-32-128-128"),
    pytest.param(1, 256, 2, 128, 128, 128, 1,  # one head a group
                 id="1-256-2-128-128-128"),
    pytest.param(2, 512, 4, 64, 128, 256, 1,   # mamba2-370m's Q, P, N
                 id="2-512-4-64-128-256"),
    # Nemotron-H's Q, P, N: SSM groups of 8 heads of 64, one 512-lane step
    # each; two such steps, then all 8 groups of the published 64 heads
    pytest.param(1, 256, 16, 64, 128, 128, 2, id="1-256-16-64-128-128-g2"),
    pytest.param(1, 256, 64, 64, 128, 128, 8, id="1-256-64-64-128-128-g8"),
    # four heads a group of two 128-lane head groups: C B^T per grid step
    pytest.param(1, 256, 8, 64, 128, 128, 2, id="1-256-8-64-128-128-g2"),
]


def _ssd_args(B, S, H, P, N, G=1):
    def bf16_exact(a):
        return jnp.asarray(a, jnp.float32).astype(jnp.bfloat16).astype(
            jnp.float32)

    bc = (B, S, N) if G == 1 else (B, S, G, N)
    return (bf16_exact(RNG.standard_normal((B, S, H, P))),
            jnp.asarray(RNG.uniform(0.01, 0.2, (B, S, H)), jnp.float32),
            -jnp.asarray(RNG.uniform(0.5, 4.0, (H,)), jnp.float32),
            bf16_exact(0.3 * RNG.standard_normal(bc)),
            bf16_exact(0.3 * RNG.standard_normal(bc)),
            jnp.asarray(RNG.uniform(0.5, 1.5, (H,)), jnp.float32),
            jnp.asarray(0.1 * RNG.standard_normal((B, H, P, N)), jnp.float32))


def _ssd_kernel_path(Q):
    def run(x, dt, A, Bm, Cm, D, h0):
        B, S, H, P = x.shape
        y, h = ops.ssd_scan(x.reshape(B, S, H * P), dt, A, Bm, Cm, D, h0,
                            chunk=Q, interpret=True)
        return y.reshape(x.shape), h
    return run


def _ssd_oracle(Q):
    """_ssd_chunked and the skip ``D x`` the kernels add."""
    from repro.models.layers import _ssd_chunked

    def run(x, dt, A, Bm, Cm, D, h0):
        y, h = _ssd_chunked(x, dt, A, Bm, Cm, h0, chunk=Q)
        return y + D[:, None] * x, h
    return run


def _assert_close(got, want, name):
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= SSD_TOL * scale, (name, err, scale)


@pytest.mark.parametrize("B,S,H,P,N,Q,G", SSD_SHAPES)
def test_ssd_intra_chunk(B, S, H, P, N, Q, G):
    """Forward of the kernel path (y and h_last) against the oracle; without
    an incoming state the intra-chunk and chunk-state terms alone."""
    x, dt, A, Bm, Cm, D, h0 = _ssd_args(B, S, H, P, N, G)
    for init in (None, h0):
        y_k, h_k = _ssd_kernel_path(Q)(x, dt, A, Bm, Cm, D, init)
        y_m, h_m = _ssd_oracle(Q)(x, dt, A, Bm, Cm, D, init)
        _assert_close(y_k, y_m, "y")
        _assert_close(h_k, h_m, "h_last")


@pytest.mark.parametrize("B,S,H,P,N,Q,G", SSD_SHAPES)
def test_ssd_vjp_matches_model_layer(B, S, H, P, N, Q, G):
    """jax.vjp of the kernel path: y, h_last and the cotangents of x, dt, A,
    B, C, D and h0 against the oracle's."""
    args = _ssd_args(B, S, H, P, N, G)
    (y_k, h_k), vjp_k = jax.vjp(_ssd_kernel_path(Q), *args)
    (y_m, h_m), vjp_m = jax.vjp(_ssd_oracle(Q), *args)
    cts = (jnp.asarray(RNG.standard_normal(y_m.shape), jnp.float32),
           jnp.asarray(RNG.standard_normal(h_m.shape), jnp.float32))
    _assert_close(y_k, y_m, "y")
    _assert_close(h_k, h_m, "h_last")
    for name, g_k, g_m in zip(("x", "dt", "A", "B", "C", "D", "h0"),
                              vjp_k(cts),
                              vjp_m(cts)):
        _assert_close(g_k, g_m, name)


def test_ssd_forward_matches_model_layer():
    """The model layer's dispatch runs the oracle on the CPU and the kernel
    path agrees with it there, at the cell's widths."""
    from repro.models.layers import _ssd_scan

    B, S, H, P, N, Q = 1, 512, 4, 64, 128, 256
    x, dt, A, Bm, Cm, D, _ = _ssd_args(B, S, H, P, N)
    y_d, h_d = _ssd_scan(x.reshape(B, S, H * P), dt, A, Bm, Cm, D, None, Q)
    y_m, h_m = _ssd_oracle(Q)(x, dt, A, Bm, Cm, D, None)
    np.testing.assert_array_equal(np.asarray(y_d), np.asarray(
        y_m.reshape(B, S, H * P)))
    np.testing.assert_array_equal(np.asarray(h_d), np.asarray(h_m))
    y_k, h_k = _ssd_kernel_path(Q)(x, dt, A, Bm, Cm, D, None)
    _assert_close(y_k.reshape(B, S, H * P), y_d, "y")
    _assert_close(h_k, h_d, "h_last")


@pytest.mark.parametrize("seed", range(5))
def test_flash_attention_property(seed):
    """Hypothesis-style randomized shapes (GQA divisibility respected)."""
    rng = np.random.default_rng(seed)
    hd = int(rng.choice([32, 64, 128]))
    Hkv = int(rng.choice([1, 2, 4]))
    G = int(rng.choice([1, 2, 4]))
    Sq = int(rng.integers(16, 200))
    q = _rand((1, Sq, Hkv * G, hd), jnp.float32)
    k = _rand((1, Sq, Hkv, hd), jnp.float32)
    v = _rand((1, Sq, Hkv, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    expect = ref.reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-4,
                               rtol=2e-4)
