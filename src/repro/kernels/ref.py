"""Pure-jnp oracles for the Pallas kernels (the ground truth the shape/dtype
sweeps in tests/test_kernels.py assert against).  The SSD scan's oracle is
the model's own path, ``models.layers._ssd_chunked``."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def reference_attention(q, k, v, *, causal=True, window=None, softcap=None,
                        scale=None):
    """Naive attention, same semantics as kernels.flash_attention."""
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    q32 = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q32, k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qi = jnp.arange(Sq)[:, None]
    ki = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qi >= ki
    if window is not None:
        mask &= qi - ki < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, hd).astype(q.dtype)


def reference_rglru_scan(a, b):
    """h_t = a_t h_{t-1} + b_t via associative scan (the model's own path)."""

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def reference_minplus(a, b):
    """Tropical matmul oracle, same semantics as kernels.minplus_matmul:
    val[..., m, n] = min_k a[..., m, k] + b[..., k, n]; idx = first argmin k
    (int32; 0 for all-+inf columns, the jnp.argmin convention)."""
    cand = a[..., :, :, None] + b[..., None, :, :]  # (..., M, K, N)
    return cand.min(axis=-2), cand.argmin(axis=-2).astype(jnp.int32)
